"""Contention-aware network subsystem: transfer_delay edge cases, the
fair-share link_scan kernel (Pallas/XLA/oracle agreement, TPU lane
shapes, conservation), zero-contention bitwise identity with the
analytic path (incl. the golden 20-user WWG scenario), contended-path
batch identity, background traffic, and the maintenance-window sugar
over the reservation source."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without dev deps: seeded fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import (des, engine, gridlet, network, reservation,
                        resource, simulation, types)
from repro.kernels import ops, ref
from repro.kernels import event_scan as event_scan_mod
from _golden_farm import golden_farm


# ----------------------------------------------------------------------
# transfer_delay edge cases: finite, nonnegative, monotone in bytes.
# ----------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(nbytes=st.floats(0.0, 1e30), baud=st.sampled_from(
    [0.0, 1e-35, 1.0, 9600.0, 2.8e4, 1e30, float("inf")]))
def test_transfer_delay_finite_nonnegative(nbytes, baud):
    d = float(network.transfer_delay(nbytes, baud))
    assert np.isfinite(d) and d >= 0.0
    # zero bytes and infinite baud are exactly instantaneous
    assert float(network.transfer_delay(0.0, baud)) == network.LATENCY
    assert float(network.transfer_delay(nbytes, jnp.inf)) == \
        network.LATENCY


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999), baud=st.sampled_from(
    [0.0, 1.0, 9600.0, float("inf")]))
def test_transfer_delay_monotone_in_bytes(seed, baud):
    """More bytes never arrive earlier -- including the zero-baud case,
    where the quotient overflows f32 and must clamp to the finite BIG
    horizon instead of wrapping to 'instantaneous'."""
    rng = np.random.RandomState(seed)
    sizes = np.sort(rng.uniform(0.0, 1e30, 16).astype(np.float32))
    d = np.asarray(network.transfer_delay(jnp.asarray(sizes), baud))
    assert np.all(np.isfinite(d)) and np.all(d >= 0.0)
    assert np.all(np.diff(d) >= 0.0)


def test_link_tabled_predicate():
    """Only positive payloads over finite-positive links contend."""
    tab = network.link_tabled
    assert bool(tab(100.0, 9600.0))
    assert not bool(tab(0.0, 9600.0))        # empty payload: instant
    assert not bool(tab(100.0, jnp.inf))     # infinite link: instant
    assert not bool(tab(100.0, 0.0))         # dead link: never arrives
    assert not bool(tab(-1.0, 9600.0))


# ----------------------------------------------------------------------
# link_scan: three-way agreement, conservation, TPU lane shapes.
# ----------------------------------------------------------------------
def _random_link_case(seed, l=8, t=12):
    rng = np.random.RandomState(seed)
    rem = rng.exponential(1e5, (l, t)).astype(np.float32)
    rem[rng.rand(l, t) < 0.4] = 0.0          # free slots
    if seed % 2:  # integer payloads force exact forecast ties
        rem = np.where(rem > 0,
                       (rng.randint(1, 5, (l, t)) * 1024.0)
                       .astype(np.float32), 0.0)
    baud = rng.uniform(100.0, 1e4, (l,)).astype(np.float32)
    baud[seed % l] = 0.0                     # dead link
    baud[(seed + 3) % l] = np.inf            # uncontended link
    bg = rng.choice([0.0, 1.0, 2.5], (l,)).astype(np.float32)
    tie = rng.permutation(l * t).reshape(l, t).astype(np.float32)
    return rem, baud, bg, tie


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999))
def test_link_scan_paths_agree(seed):
    """Pallas interpret, the XLA fallback (the engine's CPU hot path)
    and the numpy oracle agree on random transfer tables with dead and
    infinite links, fractional background flows and forecast ties."""
    rem, baud, bg, tie = _random_link_case(seed)
    args = (jnp.asarray(rem), jnp.asarray(baud))
    kw = dict(bg=jnp.asarray(bg), tie=jnp.asarray(tie))
    pallas_out = ops.link_scan(*args, **kw, interpret=True)
    xla_out = event_scan_mod.link_scan_xla(*args, **kw)
    ref_out = ref.link_scan_ref(rem, baud, bg=bg, tie=tie)
    for got, name in ((xla_out, "xla"), (ref_out, "oracle")):
        np.testing.assert_allclose(np.asarray(pallas_out[0]),
                                   np.asarray(got[0]), rtol=1e-4,
                                   atol=1e-4, err_msg=name)
        np.testing.assert_allclose(np.asarray(pallas_out[1]),
                                   np.asarray(got[1]), rtol=1e-4,
                                   err_msg=name)
        assert np.array_equal(np.asarray(pallas_out[3]),
                              np.asarray(got[3])), name
    assert np.array_equal(np.asarray(pallas_out[2]),
                          np.asarray(xla_out[2]))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999))
def test_link_scan_fair_share_conservation(seed):
    """Fair-share invariant: active transfers split the link equally
    and their rates sum to baud * m / (m + bg); with no background
    traffic the whole link is consumed."""
    rem, baud, bg, tie = _random_link_case(seed)
    rate, _, _, occ = event_scan_mod.link_scan_xla(
        jnp.asarray(rem), jnp.asarray(baud), bg=jnp.asarray(bg),
        tie=jnp.asarray(tie))
    rate, occ = np.asarray(rate), np.asarray(occ)
    live = (baud > 0) & np.isfinite(baud)
    m = occ.astype(np.float64)
    safe_baud = np.where(live, baud, 0.0)    # inf links carry rate 0
    expect = np.where(live & (m > 0),
                      safe_baud * m / np.maximum(m + bg, 1.0), 0.0)
    np.testing.assert_allclose(rate.sum(axis=1), expect, rtol=1e-4)
    # equal shares: every active transfer runs at the same rate
    for r in range(rem.shape[0]):
        active = rate[r][rate[r] > 0]
        if active.size:
            np.testing.assert_allclose(active, active[0], rtol=1e-5)


def test_link_scan_lowers_for_tpu_shapes():
    """The link kernel must trace at fleet scale with a lane-padded
    transfer axis (L=256 links, T=600 -> padded to 640).  Interpret-mode
    shape tracing only: Mosaic never sees it (the compiles for a
    described TPU are in tests/test_tpu_compile.py)."""
    l, t = 256, 600
    rem = jax.ShapeDtypeStruct((l, t), jnp.float32)
    v = jax.ShapeDtypeStruct((l,), jnp.float32)
    jax.eval_shape(lambda a, b, g: ops.link_scan(
        a, b, bg=g, interpret=True), rem, v, v)


def test_link_scan_lane_padding_roundtrip():
    """Outputs come back at the caller's T with the empty-row sentinel
    remapped, padding never wins the argmin."""
    rem, baud, bg, tie = _random_link_case(7, l=8, t=130)  # pads to 256
    p = ops.link_scan(jnp.asarray(rem), jnp.asarray(baud),
                      bg=jnp.asarray(bg), tie=jnp.asarray(tie),
                      interpret=True)
    x = event_scan_mod.link_scan_xla(jnp.asarray(rem), jnp.asarray(baud),
                                     bg=jnp.asarray(bg),
                                     tie=jnp.asarray(tie))
    assert p[0].shape == (8, 130)
    assert int(np.asarray(p[2]).max()) <= 130
    np.testing.assert_allclose(np.asarray(p[0]), np.asarray(x[0]),
                               rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(p[2]), np.asarray(x[2]))


# ----------------------------------------------------------------------
# Zero-contention == analytic path, bit for bit.
# ----------------------------------------------------------------------
def _grid_fields(res):
    return {f: np.asarray(getattr(res.gridlets, f))
            for f in ("status", "start", "finish", "returned",
                      "resource", "cost")}


def test_single_transfer_bitwise_matches_analytic():
    """One transfer per link at a time (power-of-two payloads so every
    advance is exact): the fair-share subsystem reproduces the analytic
    timestamps bitwise -- entry, arrival, completion and return."""
    fleet = resource.make_fleet([1], 1.0, 1.0, types.TIME_SHARED,
                                baud_rate=16.0)
    g = gridlet.make_batch([8.0], in_bytes=64.0, out_bytes=32.0)
    analytic = engine.run_direct(g, fleet, 0, 0.0, max_events=64,
                                 batch=1)
    net = engine.run_direct(g, fleet, 0, 0.0, max_events=64, net_cap=2,
                            batch=1)
    a, b = _grid_fields(analytic), _grid_fields(net)
    for f in a:
        assert np.array_equal(a[f], b[f]), f
    # arrival 64/16 = 4, finish 4+8 = 12, return 12+32/16 = 14
    np.testing.assert_allclose(b["returned"], [14.0])
    assert int(net.overflow) == 0


def test_infinite_baud_net_mode_fully_identical():
    """Infinite links table nothing: the run with the subsystem on is
    identical to the analytic run superstep-for-superstep (trace
    included), not just in results."""
    g = gridlet.make_batch([10.0, 8.5, 9.5], in_bytes=5e4, out_bytes=2e4)
    fleet = resource.table1_resource(types.TIME_SHARED)   # baud = inf
    base = engine.run_direct(g, fleet, 0, jnp.array([0.0, 4.0, 7.0]),
                             max_events=64)
    net = engine.run_direct(g, fleet, 0, jnp.array([0.0, 4.0, 7.0]),
                            max_events=64, net_cap=3)
    for a, b in zip(base.trace, net.trace):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(base.n_steps) == int(net.n_steps)
    assert int(base.n_events) == int(net.n_events)
    a, b = _grid_fields(base), _grid_fields(net)
    for f in a:
        assert np.array_equal(a[f], b[f]), f


def test_zero_byte_wwg_golden_identical_with_net_on():
    """The acceptance bar: the golden 20-user WWG scenario (zero-byte
    payloads -- nothing can contend) is bit-for-bit identical with the
    network subsystem enabled, counters included."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=100, n_users=20)
    kw = dict(deadline=2000.0, budget=22000.0, opt=types.OPT_COST,
              n_users=20)
    base = simulation.run_experiment(g, fleet, **kw)
    net = simulation.run_experiment(g, fleet, **kw, net_cap=None)
    for f in ("n_done", "spent", "term_time", "n_events", "n_steps",
              "n_spec", "n_reseeds", "overflow"):
        assert np.array_equal(np.asarray(getattr(base, f)),
                              np.asarray(getattr(net, f))), f
    a, b = _grid_fields(base), _grid_fields(net)
    for f in a:
        assert np.array_equal(a[f], b[f]), f


# ----------------------------------------------------------------------
# Contended links: fair-share physics and batch identity.
# ----------------------------------------------------------------------
def test_fair_share_contention_trace():
    """Two simultaneous 128-byte stagings over a 16 B/unit link halve
    each other's bandwidth (arrive at 16, not 8); the two 64-byte
    returns contend the same way.  Hand-computed from the fair-share
    rule, all values powers of two."""
    fleet = resource.make_fleet([2], 1.0, 1.0, types.TIME_SHARED,
                                baud_rate=16.0)
    g = gridlet.make_batch([8.0, 8.0], in_bytes=128.0, out_bytes=64.0)
    r = engine.run_direct(g, fleet, 0, 0.0, max_events=64, net_cap=4,
                          batch=1)
    np.testing.assert_allclose(np.asarray(r.gridlets.start), 16.0)
    np.testing.assert_allclose(np.asarray(r.gridlets.finish), 24.0)
    np.testing.assert_allclose(np.asarray(r.gridlets.returned), 32.0)
    assert int(r.overflow) == 0
    tt, kind, _ = (np.asarray(x) for x in r.trace)
    assert 16.0 in tt[kind == des.K_NETWORK]     # staging drains
    assert 32.0 in tt[kind == des.K_NETWORK]     # returns drain
    # analytic run: uncontended arrivals at 8, returns 4 after finish
    ra = engine.run_direct(g, fleet, 0, 0.0, max_events=64, batch=1)
    np.testing.assert_allclose(np.asarray(ra.gridlets.start), 8.0)
    np.testing.assert_allclose(np.asarray(ra.gridlets.returned), 20.0)


def test_staggered_entries_piecewise_constant_rates():
    """A transfer entering mid-flight re-shares the link from that
    instant on (piecewise-constant integration): 128 B at t=0 plus
    128 B at t=4 over a 16 B/unit link -> arrivals at 12 and 16."""
    fleet = resource.make_fleet([1], 1.0, 1.0, types.TIME_SHARED,
                                baud_rate=16.0)
    g = gridlet.make_batch([4.0, 4.0], in_bytes=128.0)
    r = engine.run_direct(g, fleet, 0, jnp.asarray([0.0, 4.0]),
                          max_events=64, net_cap=2, batch=1)
    np.testing.assert_allclose(np.asarray(r.gridlets.start),
                               [12.0, 16.0])


def test_background_flows_take_their_share():
    """One phantom background flow halves a lone transfer's share."""
    fleet = resource.make_fleet([1], 1.0, 1.0, types.TIME_SHARED,
                                baud_rate=16.0)
    g = gridlet.make_batch([4.0], in_bytes=128.0)
    r = engine.run_direct(g, fleet, 0, 0.0, max_events=64, net_cap=2,
                          bg_flows=1.0, batch=1)
    np.testing.assert_allclose(np.asarray(r.gridlets.start), [16.0])
    r0 = engine.run_direct(g, fleet, 0, 0.0, max_events=64, net_cap=2,
                           batch=1)
    np.testing.assert_allclose(np.asarray(r0.gridlets.start), [8.0])


@settings(max_examples=6, deadline=None)
@given(batch=st.sampled_from([2, 3, 8]), seed=st.integers(0, 99))
def test_contended_batch_property_identical(batch, seed):
    """The contended path is bit-identical for every batch value: full
    gridlet state and event trace, over random payload mixes (some
    zero-byte, so tabled and instant transfers coexist)."""
    rng = np.random.RandomState(seed)
    fleet = resource.make_fleet([2, 2], [1.0, 1.0], [1.0, 2.0],
                                types.TIME_SHARED, baud_rate=64.0)
    n = 10
    in_b = np.where(rng.rand(n) < 0.3, 0.0,
                    rng.randint(1, 9, n) * 32.0).astype(np.float32)
    out_b = np.where(rng.rand(n) < 0.3, 0.0,
                     rng.randint(1, 5, n) * 16.0).astype(np.float32)
    g = gridlet.make_batch(jnp.full((n,), 25.0),
                           in_bytes=jnp.asarray(in_b),
                           out_bytes=jnp.asarray(out_b))
    kw = dict(deadline=1000.0, budget=50000.0, opt=types.OPT_COST,
              n_users=1, net_cap=None)
    r1 = simulation.run_experiment(g, fleet, **kw, batch=1)
    rk = simulation.run_experiment(g, fleet, **kw, batch=batch)
    for f in ("n_done", "spent", "term_time", "n_events", "overflow"):
        assert np.array_equal(np.asarray(getattr(r1, f)),
                              np.asarray(getattr(rk, f))), f
    a, b = _grid_fields(r1), _grid_fields(rk)
    for f in a:
        assert np.array_equal(a[f], b[f]), f
    assert int(r1.n_steps) == int(rk.n_steps) + int(rk.n_spec)
    assert int(r1.overflow) == 0


def test_queued_tabled_return_cuts_speculation():
    """Regression: a QUEUED gridlet with a contending return payload
    must cut the speculation horizon -- a mid-slab queue admission can
    turn it RUNNING and complete it inside the slab, creating its
    return transfer where no NETWORK apply will run.  batch=k must stay
    bit-identical to batch=1 (the third gridlet queues at t=0, admits
    at t=8, completes at t=16 and its 64-byte return drains at t=20)."""
    fleet = resource.make_fleet([2], 1.0, 1.0, types.SPACE_SHARED,
                                baud_rate=16.0)
    g = gridlet.make_batch([8.0, 24.0, 8.0],
                           out_bytes=jnp.asarray([0.0, 0.0, 64.0]))
    r1 = engine.run_direct(g, fleet, 0, 0.0, max_events=64, net_cap=2,
                           batch=1)
    rk = engine.run_direct(g, fleet, 0, 0.0, max_events=64, net_cap=2)
    np.testing.assert_allclose(np.asarray(r1.gridlets.returned),
                               [8.0, 24.0, 20.0])
    a, b = _grid_fields(r1), _grid_fields(rk)
    for f in a:
        assert np.array_equal(a[f], b[f]), f
    for x, y in zip(r1.trace, rk.trace):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_big_finite_baud_is_uncontended_not_stuck():
    """Regression: a finite baud at/above the kernel's BIG horizon must
    route like an infinite link (analytic, instantaneous) -- not into
    the transfer table, where the link row would be masked dead and the
    transfer could never drain."""
    assert not bool(network.link_tabled(100.0, 3.3e38))
    fleet = resource.make_fleet([1], 1.0, 1.0, types.TIME_SHARED,
                                baud_rate=3.3e38)
    g = gridlet.make_batch([8.0], in_bytes=64.0, out_bytes=32.0)
    r = engine.run_direct(g, fleet, 0, 0.0, max_events=64, net_cap=2,
                          batch=1)
    assert np.all(np.asarray(r.gridlets.status) == types.DONE)
    np.testing.assert_allclose(np.asarray(r.gridlets.returned), [8.0])


def test_contended_broker_run_with_failures_batch_identical():
    """Contention + failure/recovery streams together: transfers to a
    down resource still fail-and-refund on arrival, and the batched
    path stays bit-identical."""
    fleet = resource.make_fleet([2, 2], [1.0, 1.0], [1.0, 2.0],
                                types.TIME_SHARED, baud_rate=64.0)
    g = gridlet.make_batch(jnp.full((10,), 25.0), in_bytes=128.0,
                           out_bytes=64.0)
    sc = simulation.Scenario(mtbf=80.0, mttr=8.0, seed=3)
    kw = dict(deadline=1000.0, budget=50000.0, opt=types.OPT_COST,
              n_users=1, scenario=sc, net_cap=None)
    r1 = simulation.run_experiment(g, fleet, **kw, batch=1)
    rk = simulation.run_experiment(g, fleet, **kw)
    for f in ("n_done", "spent", "term_time", "n_events", "n_failed",
              "n_resubmits"):
        assert np.array_equal(np.asarray(getattr(r1, f)),
                              np.asarray(getattr(rk, f))), f
    assert int(r1.n_steps) == int(rk.n_steps) + int(rk.n_spec)
    assert np.all(np.asarray(r1.gridlets.status) == types.DONE)


# ----------------------------------------------------------------------
# Satellites: batched golden trace identity, maintenance windows.
# ----------------------------------------------------------------------
def test_golden_wwg_trace_identical_across_batch():
    """The while-loop condition now consumes the carried _user_flags
    instead of recomputing them: the golden 20-user WWG run must stay
    trace-identical (times, kinds, actors) between batch=1 and the
    default batch."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=50, n_users=20)
    params = simulation._scenario_params(fleet, 2000.0, 22000.0,
                                         types.OPT_COST, 20, None)
    max_jobs = simulation.safe_max_jobs(g, params, fleet)
    r1 = engine.run(g, fleet, params, 20, 4000, max_jobs=max_jobs,
                    batch=1)
    rk = engine.run(g, fleet, params, 20, 4000, max_jobs=max_jobs)
    for a, b in zip(r1.trace, rk.trace):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(r1.n_steps) == int(rk.n_steps) + int(rk.n_spec)
    assert np.array_equal(np.asarray(r1.spent), np.asarray(rk.spent))


def test_maintenance_window_blocks_whole_resource():
    """reservation.maintenance holds every PE: a space-shared resource
    admits nothing during the window (arrivals queue and run at its
    close), and a time-shared resident pauses exactly for the window
    (zero effective shares)."""
    fleet = resource.make_fleet([2], 1.0, 1.0, types.SPACE_SHARED,
                                baud_rate=jnp.inf)
    g = gridlet.make_batch([10.0, 10.0])
    maint = reservation.maintenance(fleet.num_pe, [(0, 0.0, 5.0)])
    r = engine.run_direct(g, fleet, 0, 0.0, max_events=64,
                          reservations=maint)
    np.testing.assert_allclose(np.asarray(r.gridlets.finish), 15.0)
    tt, kind, _ = (np.asarray(x) for x in r.trace)
    np.testing.assert_allclose(tt[kind == des.K_RESERVATION], [5.0])
    # time-shared: the resident pauses over [4, 6) -> finish slips by 2
    fleet_ts = resource.make_fleet([1], 1.0, 1.0, types.TIME_SHARED,
                                   baud_rate=jnp.inf)
    g1 = gridlet.make_batch([10.0])
    r_ts = engine.run_direct(
        g1, fleet_ts, 0, 0.0, max_events=64,
        reservations=reservation.maintenance(fleet_ts.num_pe,
                                             [(0, 4.0, 6.0)]))
    np.testing.assert_allclose(np.asarray(r_ts.gridlets.finish), 12.0)


def test_maintenance_book_method_conflicts():
    """ReservationBook.book_maintenance holds all PEs and refuses to
    stack on top of existing bookings."""
    book = reservation.ReservationBook([4, 2])
    book.book(0, 2, 10.0, 20.0)
    with pytest.raises(ValueError):
        book.book_maintenance(0, 15.0, 25.0)   # 2 PEs already held
    res = book.book_maintenance(1, 0.0, 5.0)
    assert res.pes == 2
    assert book.reserved_pes(1, 2.0) == 2


def test_fastest_drain_membership_invariant_bound():
    """fastest_drain is the sole-member (fastest possible) drain time:
    it lower-bounds the actual fair-share drain for every occupancy m
    and never decreases when members join, with transfer_delay's exact
    clamping at the edges."""
    fd = network.fastest_drain
    # m members at baud/(m+bg): actual drain m*(..) >= bound for m >= 1
    for m in (1, 2, 7):
        for bg in (0.0, 1.0, 2.5):
            actual = 1e5 * (m + bg) / 9600.0
            assert actual >= float(fd(1e5, 9600.0, bg)) - 1e-3
    assert float(fd(1e5, 9600.0, 0.0)) == pytest.approx(1e5 / 9600.0)
    assert float(fd(0.0, 9600.0, 1.0)) == 0.0       # empty payload
    assert float(fd(1e5, jnp.inf, 1.0)) == 0.0      # infinite link
    d_dead = float(fd(1e5, 0.0, 1.0))               # dead link: never
    assert np.isfinite(d_dead) and d_dead >= 1e30
    assert float(fd(1e38, 1e-30, 9.0)) == \
        float(np.float32(network.BIG))              # overflow -> BIG


def test_golden_net_trace_pinned_across_batch():
    """The contended engine_20u_100j_net BENCH row replays the
    committed golden trace bitwise -- times, kinds, actors, per-gridlet
    returns, spend, termination -- at batch=1 AND the default batch, so
    network-slab changes (the associative-scan carry-through) can never
    silently reorder events."""
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "golden_net_20u.json")) as f:
        gold = json.load(f)
    fleet = resource.wwg_fleet()
    g = golden_farm("seed3_100x20", in_bytes=200_000.0,
                    out_bytes=100_000.0)
    sc = simulation.Scenario(baud_rate=28_000.0, bg_flows=1.0)
    params = simulation._scenario_params(fleet, 2000.0, 22000.0,
                                         types.OPT_COST, 20, sc)
    net_cap = simulation.safe_net_cap(g, params, fleet, 20)
    max_jobs = simulation.safe_max_jobs(g, params, fleet)
    for batch in (1, None):
        kw = {} if batch is None else dict(batch=batch)
        r = engine.run(g, fleet, params, 20, 16384, max_jobs=max_jobs,
                       net_cap=net_cap, **kw)
        tt, kind, who = (np.asarray(x) for x in r.trace)
        m = kind >= 0
        assert np.array_equal(tt[m],
                              np.asarray(gold["trace_t"], np.float32))
        assert np.array_equal(kind[m], np.asarray(gold["trace_kind"]))
        assert np.array_equal(who[m], np.asarray(gold["trace_who"]))
        assert np.array_equal(
            np.asarray(r.gridlets.returned),
            np.asarray(gold["returned"], np.float32))
        # spend sums per-job costs, whose last bits follow XLA's
        # reduction order; the trace above stays exact
        np.testing.assert_allclose(np.asarray(r.spent),
                                   np.asarray(gold["spent"], np.float32),
                                   rtol=1e-6)
        assert np.array_equal(np.asarray(r.term_time),
                              np.asarray(gold["term_time"], np.float32))
        assert int(np.asarray(r.n_events)) == gold["n_events"]
        assert int(np.asarray(r.overflow)) == gold["overflow"]
        assert int((np.asarray(r.gridlets.status)
                    == types.DONE).sum()) == gold["n_done"]
