"""Superstep engine contract: golden Table 1 trace, pre-refactor
result equivalence, engine <-> kernel <-> oracle rate agreement, the
job-slot / calendar overflow invariants, the pluggable event sources
(failure/recovery, calendar load steps, reservations), and the k-step
speculative batching path (bit-identity with k=1, horizon cuts)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without dev deps: seeded fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import des, engine, gridlet, resource, simulation, types
from repro.core.types import replace as treplace
from repro.kernels import ops, ref
from repro.kernels.event_scan import event_scan_xla
from _golden_farm import golden_farm
from benchmarks.table1 import ARRIVALS, LENGTHS, TRACES

GOLDEN = json.load(open(os.path.join(os.path.dirname(__file__), "data",
                                     "golden_pre_refactor.json")))


# ----------------------------------------------------------------------
# Golden event trace (paper Table 1 / Figs 9 and 12): the superstep
# engine must reproduce the exact times, kinds and FIFO order.
# ----------------------------------------------------------------------
def _trace(policy, batch=engine.DEFAULT_BATCH):
    g = gridlet.make_batch(LENGTHS)
    fleet = resource.table1_resource(policy)
    res = engine.run_direct(g, fleet, 0, ARRIVALS, max_events=64,
                            batch=batch)
    tt, kind, who = (np.asarray(x) for x in res.trace)
    m = kind >= 0
    return res, list(zip(tt[m].tolist(), kind[m].tolist(),
                         who[m].tolist()))


def test_time_shared_golden_trace():
    # kinds: 0=completion, 1=return, 2=arrival, 3=broker
    res, trace = _trace(types.TIME_SHARED, batch=1)
    assert trace == TRACES[types.TIME_SHARED]
    # zero-delay returns fold into their completion superstep: 9 events
    # in 6 supersteps.
    assert int(res.n_events) == 9 and int(res.n_steps) == 6
    assert int(res.overflow) == 0 and int(res.n_spec) == 0


def test_time_shared_golden_trace_batched():
    """The k-step batched path replays the identical golden trace; the
    three completion supersteps (10/14/18: no arrival, broker or
    boundary can intervene) speculate into the t=7 arrival iteration."""
    res, trace = _trace(types.TIME_SHARED)          # default batch
    assert trace == TRACES[types.TIME_SHARED]
    assert int(res.n_events) == 9
    assert int(res.n_steps) == 3 and int(res.n_spec) == 3
    assert int(res.overflow) == 0


def test_space_shared_golden_trace():
    res, trace = _trace(types.SPACE_SHARED, batch=1)
    assert trace == TRACES[types.SPACE_SHARED]
    assert int(res.n_steps) == 6 and int(res.overflow) == 0
    # batched: same trace (queue admissions are speculation-safe: they
    # ride inside the completion superstep), half the iterations
    res_b, trace_b = _trace(types.SPACE_SHARED)
    assert trace_b == trace
    assert int(res_b.n_steps) == 3 and int(res_b.n_spec) == 3


def test_simultaneous_events_apply_in_one_superstep():
    """4 equal jobs on 4 PEs: one arrival superstep admits all four, one
    completion superstep completes AND returns all four (12 events)."""
    g = gridlet.make_batch([10.0] * 4)
    fleet = resource.make_fleet([4], 1.0, 1.0, types.TIME_SHARED)
    res = engine.run_direct(g, fleet, 0, jnp.zeros(4), max_events=64,
                            batch=1)
    assert int(res.n_steps) == 2
    assert int(res.n_events) == 12
    np.testing.assert_allclose(np.asarray(res.gridlets.finish), 10.0)
    # batched: the completion superstep speculates into the arrival
    # iteration -- 12 events in ONE while-loop iteration
    res_b = engine.run_direct(g, fleet, 0, jnp.zeros(4), max_events=64)
    assert int(res_b.n_steps) == 1 and int(res_b.n_spec) == 1
    assert int(res_b.n_events) == 12


# ----------------------------------------------------------------------
# Pre-refactor equivalence: same ExperimentResult, fewer iterations.
# ----------------------------------------------------------------------
def test_matches_pre_refactor_engine_results():
    ref_run = GOLDEN["1u_200j"]
    fleet = resource.wwg_fleet()
    g = golden_farm("seed3_200x1")
    r = simulation.run_experiment(g, fleet, deadline=2000.0,
                                  budget=22000.0, opt=types.OPT_COST,
                                  n_users=1)
    np.testing.assert_allclose(np.asarray(r.n_done), ref_run["n_done"])
    np.testing.assert_allclose(np.asarray(r.spent), ref_run["spent"],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r.term_time),
                               ref_run["term_time"], rtol=1e-5)
    # batching must strictly reduce loop iterations (the 2x target on
    # the 20-user scenario is asserted by benchmarks/engine_bench.py)
    assert int(r.n_steps) < ref_run["iterations"]
    assert int(r.overflow) == 0


# ----------------------------------------------------------------------
# Engine <-> kernel <-> oracle agreement on random [R, J] states.
# ----------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999), r=st.sampled_from([8, 16]),
       j=st.sampled_from([8, 24]))
def test_event_scan_paths_agree(seed, r, j):
    """Pallas interpret, the XLA fallback (the engine's CPU hot path)
    and the numpy oracle agree on random states with tie keys and mixed
    policies."""
    rng = np.random.RandomState(seed)
    remaining = rng.exponential(50.0, (r, j)).astype(np.float32)
    remaining[rng.rand(r, j) < 0.4] = 0.0
    mips = rng.uniform(1.0, 500.0, (r,)).astype(np.float32)
    pes = rng.randint(1, 9, (r,)).astype(np.int32)
    tie = rng.permutation(r * j).reshape(r, j).astype(np.float32)
    pol = rng.randint(0, 2, (r,)).astype(np.int32)
    args = (jnp.asarray(remaining), jnp.asarray(mips), jnp.asarray(pes))
    kw = dict(tie=jnp.asarray(tie), policy=jnp.asarray(pol))
    pallas_out = ops.event_scan(*args, **kw, interpret=True)
    xla_out = event_scan_xla(*args, **kw)
    ref_out = ref.event_scan_ref(remaining, mips, pes, tie=tie,
                                 policy=pol)
    for got in (xla_out, ref_out):
        np.testing.assert_allclose(np.asarray(pallas_out[0]),
                                   np.asarray(got[0]), rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(pallas_out[1]),
                                   np.asarray(got[1]), rtol=1e-4)
        assert np.array_equal(np.asarray(pallas_out[3]),
                              np.asarray(got[3]))
    assert np.array_equal(np.asarray(pallas_out[2]),
                          np.asarray(xla_out[2]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999), n_jobs=st.integers(1, 24),
       num_pe=st.integers(1, 6))
def test_kernel_agrees_with_engine_rates(seed, n_jobs, num_pe):
    """The kernel evaluated on the resource-major table must reproduce
    engine._rates (the flat XLA reference the superstep loop replaced),
    including FIFO tie-breaks on equal remaining work."""
    rng = np.random.RandomState(seed)
    rem = rng.randint(1, 6, (n_jobs,)).astype(np.float32)  # forces ties
    g = gridlet.make_batch(jnp.full((n_jobs,), 100.0))
    g = treplace(g, status=jnp.full((n_jobs,), types.RUNNING, jnp.int32),
                 resource=jnp.zeros((n_jobs,), jnp.int32),
                 remaining=jnp.asarray(rem))
    fleet = resource.make_fleet([num_pe], 3.0, 1.0, types.TIME_SHARED)
    st_ = engine.init_state(g, fleet, 1)
    st_ = treplace(st_, g=g)
    flat = np.asarray(engine._rates(st_, fleet, 1))

    table = jnp.pad(jnp.asarray(rem).reshape(1, n_jobs),
                    ((0, 7), (0, 0)))
    tie = jnp.pad(
        jnp.arange(n_jobs, dtype=jnp.float32).reshape(1, n_jobs),
        ((0, 7), (0, 0)))
    rate, tmin, amin, occ = ops.event_scan(
        table, jnp.full((8,), 3.0), jnp.full((8,), num_pe, jnp.int32),
        tie=tie, policy=jnp.zeros((8,), jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(rate)[0], flat, rtol=1e-5)
    assert int(occ[0]) == n_jobs
    t = rem / np.maximum(flat, 1e-30)
    assert float(tmin[0]) == pytest.approx(float(t.min()))
    # argmin: earliest completion, FIFO among ties
    want = min(range(n_jobs), key=lambda i: (np.float32(t[i]), i))
    assert int(amin[0]) == want


# ----------------------------------------------------------------------
# Slot-table invariants.
# ----------------------------------------------------------------------
def test_no_slot_overflow_across_policies():
    for policy in (types.TIME_SHARED, types.SPACE_SHARED):
        g = gridlet.make_batch(jnp.arange(1.0, 13.0))
        fleet = resource.make_fleet([2], 1.0, 1.0, policy)
        res = engine.run_direct(g, fleet, 0, jnp.zeros(12),
                                max_events=256)
        assert int(res.overflow) == 0
        assert np.all(np.asarray(res.gridlets.status) == types.DONE)


def test_broker_experiment_overflow_zero():
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(11), n_jobs=40, n_users=2)
    r = simulation.run_experiment(g, fleet, deadline=800.0, budget=9000.0,
                                  opt=types.OPT_COST, n_users=2)
    assert int(r.overflow) == 0
    assert float(np.asarray(r.n_done).sum()) > 0


# ----------------------------------------------------------------------
# Pluggable event sources.
# ----------------------------------------------------------------------
def test_zero_rate_sources_reproduce_golden():
    """With all three new sources registered but their rates zero/empty,
    the 20-user WWG scenario is bit-for-bit identical to a run without
    any scenario (which itself must match the pre-refactor golden)."""
    ref_run = GOLDEN["20u_100j"]
    fleet = resource.wwg_fleet()
    g = golden_farm("seed3_100x20")
    kw = dict(deadline=2000.0, budget=22000.0, opt=types.OPT_COST,
              n_users=20)
    base = simulation.run_experiment(g, fleet, **kw)
    zero = simulation.run_experiment(
        g, fleet, **kw,
        scenario=simulation.Scenario(mtbf=0.0, mttr=0.0,
                                     reservations=[], seed=123))
    for f in ("n_done", "spent", "term_time", "n_steps", "n_spec",
              "n_events"):
        assert np.array_equal(np.asarray(getattr(base, f)),
                              np.asarray(getattr(zero, f))), f
    assert int(zero.n_failed) == 0 and int(zero.n_resubmits) == 0
    np.testing.assert_allclose(np.asarray(zero.n_done), ref_run["n_done"])
    np.testing.assert_allclose(np.asarray(zero.spent), ref_run["spent"],
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(zero.term_time),
                               ref_run["term_time"], rtol=1e-5)


def test_failure_resubmits_without_double_billing():
    """Failures mid-execution move gridlets to FAILED with a refund; the
    broker resubmits them and the total spend is exactly the sum of the
    committed costs of the jobs that eventually completed."""
    fleet = resource.make_fleet([2, 2], [1.0, 1.0], [1.0, 2.0],
                                types.TIME_SHARED)
    g = gridlet.make_batch(jnp.full((12,), 30.0))
    sc = simulation.Scenario(mtbf=60.0, mttr=10.0, seed=0)
    r = simulation.run_experiment(g, fleet, deadline=2000.0,
                                  budget=100000.0, opt=types.OPT_COST,
                                  n_users=1, scenario=sc)
    status = np.asarray(r.gridlets.status)
    assert int(r.n_failed) > 0                  # seed 0 produces failures
    # every FAILED gridlet was eventually resubmitted and completed
    assert np.all(status == types.DONE)
    assert int(r.n_resubmits) >= int(r.n_failed) > 0
    # no double billing: spend == committed cost of completed gridlets
    cost_done = float(np.asarray(r.gridlets.cost)[status ==
                                                  types.DONE].sum())
    assert float(r.spent[0]) == pytest.approx(cost_done, rel=1e-6)
    assert float(np.asarray(r.downtime).sum()) > 0.0
    assert int(r.overflow) == 0


def test_calendar_step_alone_advances_time():
    """A weekend boundary is a first-class event: the engine lands a
    superstep on it with no other event due, and the piecewise-constant
    load integrates exactly (200 MI at rate 1 until t=120, rate 0.5 over
    the 48 h weekend, rate 1 after t=168 -> finish at 224)."""
    fleet = resource.make_fleet([1], 1.0, 1.0, types.TIME_SHARED,
                                weekend_load=0.5, baud_rate=jnp.inf)
    g = gridlet.make_batch([200.0])
    r = engine.run_direct(g, fleet, 0, 0.0, max_events=64)
    assert float(r.gridlets.finish[0]) == 224.0
    tt, kind, _ = (np.asarray(x) for x in r.trace)
    m = kind >= 0
    steps = tt[kind == des.K_CALENDAR]
    np.testing.assert_allclose(steps[:2], [120.0, 168.0])
    # the two boundary supersteps carry ONLY the calendar event
    assert list(zip(tt[m].tolist(), kind[m].tolist())) == [
        (0.0, des.K_ARRIVAL), (120.0, des.K_CALENDAR),
        (168.0, des.K_CALENDAR), (224.0, des.K_COMPLETION),
        (224.0, des.K_RETURN)]


def test_reservation_blocks_reserved_pes():
    """A [0, 12) window holding 2 of 4 space-shared PEs admits only two
    of four simultaneous arrivals; the other two run when the window
    closes (a RESERVATION event re-admits them at t=12)."""
    fleet = resource.make_fleet([4], 1.0, 1.0, types.SPACE_SHARED,
                                baud_rate=jnp.inf)
    g = gridlet.make_batch([20.0] * 4)
    r = engine.run_direct(g, fleet, 0, 0.0, max_events=64,
                          reservations=[(0, 2, 0.0, 12.0)])
    np.testing.assert_allclose(sorted(np.asarray(r.gridlets.finish)),
                               [20.0, 20.0, 32.0, 32.0])
    tt, kind, _ = (np.asarray(x) for x in r.trace)
    assert 12.0 in tt[kind == des.K_RESERVATION]
    # without the reservation all four PEs admit immediately
    r0 = engine.run_direct(g, fleet, 0, 0.0, max_events=64)
    np.testing.assert_allclose(np.asarray(r0.gridlets.finish), 20.0)
    assert int(r.overflow) == 0


def test_reservation_shrinks_time_shared_shares():
    """Blocked PEs leave the time-shared share pool: 2 equal jobs on a
    2-PE resource with 1 PE reserved run at half speed each."""
    fleet = resource.make_fleet([2], 1.0, 1.0, types.TIME_SHARED,
                                baud_rate=jnp.inf)
    g = gridlet.make_batch([10.0, 10.0])
    r = engine.run_direct(g, fleet, 0, 0.0, max_events=64,
                          reservations=[(0, 1, 0.0, 100.0)])
    np.testing.assert_allclose(np.asarray(r.gridlets.finish), 20.0)


# ----------------------------------------------------------------------
# k-step speculative batching (engine.step_batched).
# ----------------------------------------------------------------------
def _assert_same_run(r1, rk, check_failures=False):
    fields = ["n_done", "spent", "term_time", "n_events", "overflow"]
    if check_failures:
        fields += ["n_failed", "n_resubmits"]
    for f in fields:
        assert np.array_equal(np.asarray(getattr(r1, f)),
                              np.asarray(getattr(rk, f))), f
    np.testing.assert_allclose(np.asarray(r1.downtime),
                               np.asarray(rk.downtime))
    for f in ("status", "finish", "returned", "cost", "resource"):
        assert np.array_equal(np.asarray(getattr(r1.gridlets, f)),
                              np.asarray(getattr(rk.gridlets, f))), f


def test_batched_engine_bit_identical_on_golden_and_failure():
    """The acceptance contract of the k-step path: on the golden
    20-user WWG scenario AND on the seeded failure scenario, batch=k is
    bit-for-bit identical to batch=1 while running >= 1.5x fewer
    while-loop iterations; the supersteps merely repartition
    (n_steps_k1 == n_steps_k + n_spec_k)."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=100, n_users=20)
    kw = dict(deadline=2000.0, budget=22000.0, opt=types.OPT_COST,
              n_users=20)
    for sc in (None, simulation.Scenario(mtbf=500.0, mttr=25.0, seed=1)):
        r1 = simulation.run_experiment(g, fleet, **kw, scenario=sc,
                                       batch=1)
        rk = simulation.run_experiment(g, fleet, **kw, scenario=sc)
        _assert_same_run(r1, rk, check_failures=sc is not None)
        assert int(r1.n_spec) == 0
        assert int(r1.n_steps) == int(rk.n_steps) + int(rk.n_spec)
        assert int(r1.n_steps) >= 1.5 * int(rk.n_steps), \
            (int(r1.n_steps), int(rk.n_steps))


@settings(max_examples=4, deadline=None)
@given(batch=st.sampled_from([2, 3, 5, 8]), seed=st.integers(0, 99))
def test_batched_engine_property_identical(batch, seed):
    """Property form: for random failure seeds and odd batch depths the
    full event trace (times, kinds, actors) is identical to k=1."""
    fleet = resource.make_fleet([2, 2], [1.0, 1.0], [1.0, 2.0],
                                types.TIME_SHARED)
    g = gridlet.make_batch(jnp.full((10,), 25.0))
    sc = simulation.Scenario(mtbf=80.0, mttr=8.0, seed=seed)
    kw = dict(deadline=1000.0, budget=50000.0, opt=types.OPT_COST,
              n_users=1, scenario=sc)
    r1 = simulation.run_experiment(g, fleet, **kw, batch=1)
    rk = simulation.run_experiment(g, fleet, **kw, batch=batch)
    _assert_same_run(r1, rk, check_failures=True)
    assert int(r1.n_steps) == int(rk.n_steps) + int(rk.n_spec)


def test_reservation_boundary_cuts_speculation():
    """Horizon-boundary contract: a reservation window opening mid-slab
    is an interference point.  3 jobs on a 1-PE time-shared resource
    finish at 30/55/65 around a [40, 45) full-capacity hold; without the
    window the whole run folds into one iteration, with it the engine
    must commit both boundaries (and the completions they displace) in
    separate iterations -- while staying bit-identical to k=1."""
    fleet = resource.make_fleet([1], 1.0, 1.0, types.TIME_SHARED,
                                baud_rate=jnp.inf)
    g = gridlet.make_batch([10.0, 20.0, 30.0])
    resv = [(0, 1, 40.0, 45.0)]
    free = engine.run_direct(g, fleet, 0, 0.0, max_events=64)
    assert int(free.n_steps) == 1          # arrivals + 3 speculated waves
    r1 = engine.run_direct(g, fleet, 0, 0.0, max_events=64,
                           reservations=resv, batch=1)
    rk = engine.run_direct(g, fleet, 0, 0.0, max_events=64,
                           reservations=resv)
    np.testing.assert_allclose(np.asarray(rk.gridlets.finish),
                               [30.0, 55.0, 65.0])
    for a, b in zip(r1.trace, rk.trace):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(r1.n_steps) == int(rk.n_steps) + int(rk.n_spec)
    # the two boundary commits forced >= 3 iterations (vs 1 unreserved)
    assert int(rk.n_steps) >= 3
    tt, kind, _ = (np.asarray(x) for x in rk.trace)
    np.testing.assert_allclose(tt[kind == des.K_RESERVATION],
                               [40.0, 45.0])


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 99), n_commits=st.sampled_from([0, 3, 9]))
def test_event_frontier_matches_stacked_source_mins(seed, n_commits):
    """The fused frontier pass over the sources' candidate arrays is
    exactly the stacked per-source ``next_time``/``horizon`` scalar
    reductions it replaced -- on real engine states from scenarios with
    live failure streams and reservation windows, at several points of
    the run."""
    from repro.kernels import ops as kernel_ops
    fleet = resource.make_fleet([2, 3], [1.0, 1.0], [1.0, 2.0],
                                types.TIME_SHARED,
                                weekend_load=jnp.asarray([0.0, 0.5]))
    g = gridlet.make_batch(jnp.full((8,), 40.0) +
                           jnp.arange(8, dtype=jnp.float32))
    params = engine.default_params(
        500.0, 50000.0, types.OPT_COST, 1, fleet.r, mtbf=90.0, mttr=9.0,
        reservations=[(0, 1, 30.0, 60.0)],
        fail_key=jax.random.PRNGKey(seed))
    state = engine.init_state(g, fleet, 1, params=params)
    commit = jax.jit(lambda s: engine._step_commit(
        s, fleet, params, 1, engine._empty_slab(s))[0])
    for _ in range(n_commits):
        state = commit(state)

    ctx = {}
    sources = engine._make_sources(fleet, params, 1, ctx)
    r_pad = state.row_gridlet.shape[0]
    ctx["scan"] = engine._scan_events(state, fleet, params, fleet.r,
                                      r_pad)
    cands = [s.candidates(state) for s in sources]
    sizes = tuple(c.shape[0] for c in cands)
    t_star, fired, counts, _, mins = kernel_ops.event_frontier(
        jnp.concatenate(cands), sizes)
    # the stacked scalar fan-in the frontier replaced
    times = np.asarray(jnp.stack([s.next_time(state) for s in sources]))
    assert np.array_equal(np.asarray(mins), times)
    t_ref = times.min()
    assert np.asarray(t_star) == np.float32(t_ref) or \
        (np.isinf(t_ref) and np.isinf(np.asarray(t_star)))
    want_fired = np.isfinite(times) & (times <= t_ref)
    assert np.array_equal(np.asarray(fired), want_fired)
    # oracle agreement on the identical candidate vector
    oracle = ref.event_frontier_ref(
        np.asarray(jnp.concatenate(cands)), sizes)
    for a, b in zip((t_star, fired, counts), oracle):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the horizon frontier == the stacked per-source horizon mins
    t_safe = engine._speculation_horizon(state, fleet, params, 1)
    horizons = np.asarray(
        jnp.stack([s.horizon(state, types.INF) for s in sources]))
    assert np.asarray(t_safe) == horizons.min() or \
        (np.isinf(horizons.min()) and np.isinf(np.asarray(t_safe)))


def test_slab_carry_keeps_sorts_rare():
    """The slab-fed scan must actually engage: on the 20-user WWG
    scenario the overwhelming majority of supersteps run sort-free
    (the carry only reseeds when the table restructures), and the
    reseed count is identical for batch=1 and batch=k (sorts happen
    exactly where the physics demands, not where the batching does)."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=50, n_users=10)
    kw = dict(deadline=2000.0, budget=22000.0, opt=types.OPT_COST,
              n_users=10)
    rk = simulation.run_experiment(g, fleet, **kw)
    r1 = simulation.run_experiment(g, fleet, **kw, batch=1)
    assert int(rk.n_reseeds) == int(r1.n_reseeds)
    assert int(rk.n_scans) >= int(rk.n_steps) + int(rk.n_spec)
    assert int(rk.n_reseeds) < 0.35 * int(rk.n_scans), \
        (int(rk.n_reseeds), int(rk.n_scans))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999))
def test_event_scan_mask_paths_agree(seed):
    """The pe_blocked / row_ok masking agrees across Pallas interpret,
    the XLA fallback and the numpy oracle."""
    rng = np.random.RandomState(seed)
    r, j = 8, 12
    remaining = rng.exponential(50.0, (r, j)).astype(np.float32)
    remaining[rng.rand(r, j) < 0.3] = 0.0
    mips = rng.uniform(1.0, 500.0, (r,)).astype(np.float32)
    pes = rng.randint(1, 9, (r,)).astype(np.int32)
    tie = rng.permutation(r * j).reshape(r, j).astype(np.float32)
    pol = rng.randint(0, 2, (r,)).astype(np.int32)
    blocked = rng.randint(0, 9, (r,)).astype(np.float32)
    ok = (rng.rand(r) < 0.7).astype(np.float32)
    args = (jnp.asarray(remaining), jnp.asarray(mips), jnp.asarray(pes))
    kw = dict(tie=jnp.asarray(tie), policy=jnp.asarray(pol),
              pe_blocked=jnp.asarray(blocked), row_ok=jnp.asarray(ok))
    pallas_out = ops.event_scan(*args, **kw, interpret=True)
    xla_out = event_scan_xla(*args, **kw)
    ref_out = ref.event_scan_ref(remaining, mips, pes, tie=tie,
                                 policy=pol, pe_blocked=blocked,
                                 row_ok=ok)
    for got in (xla_out, ref_out):
        np.testing.assert_allclose(np.asarray(pallas_out[0]),
                                   np.asarray(got[0]), rtol=1e-4,
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(pallas_out[1]),
                                   np.asarray(got[1]), rtol=1e-4)
        assert np.array_equal(np.asarray(pallas_out[3]),
                              np.asarray(got[3]))
    assert np.array_equal(np.asarray(pallas_out[2]),
                          np.asarray(xla_out[2]))
