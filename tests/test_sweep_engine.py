"""Sweep engine contract: the select-free path (engine.run_sweep /
simulation.sweep(select_free=True)) is bit-for-bit identical to the
reference batch=1 path over random deadline x budget grids crossed with
{OPT_COST, OPT_TIME} x failure seeds x net on/off; the sharded scenario
axis (simulation.sweep_sharded) matches the unsharded sweep exactly,
including under a forced multi-device host; and the slab kernels'
``live`` masked no-op gate is a bitwise no-op on all three backends.
"""
import contextlib
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without dev deps: seeded fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import engine, gridlet, resource, simulation, types
from repro.kernels import ops, ref
from repro.kernels import event_scan as event_scan_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The "how" counters may pack the same events into supersteps
# differently between the reference and sweep loops (a mid-slab carry
# invalidation declines a micro-step the reference path would commit);
# every "what" field must match bitwise.
HOW_COUNTERS = {"n_steps", "n_spec", "n_scans", "n_reseeds"}


def assert_results_identical(a, b, tag=""):
    for name in a._fields:
        if name in HOW_COUNTERS:
            continue
        la = jax.tree_util.tree_leaves(getattr(a, name))
        lb = jax.tree_util.tree_leaves(getattr(b, name))
        assert len(la) == len(lb), name
        for i, (x, y) in enumerate(zip(la, lb)):
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                f"{tag}{name}[leaf {i}] differs"


@contextlib.contextmanager
def count_traces():
    """Count jaxpr traces inside the block: the event the benchmark's
    ``traces_in_window`` counts."""
    n = [0]

    def listener(name, _secs, **_):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            n[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield n
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def _case(seed, with_failures, with_net):
    rng = np.random.RandomState(seed)
    n_users = int(rng.randint(2, 4))
    n_jobs = int(rng.randint(4, 9))
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(seed), n_jobs=n_jobs,
                          n_users=n_users)
    deadlines = np.sort(rng.uniform(300.0, 2500.0, size=2)).tolist()
    budgets = np.sort(rng.uniform(3000.0, 25000.0, size=2)).tolist()
    scenario = simulation.Scenario(
        mtbf=float(rng.uniform(200.0, 600.0)) if with_failures else None,
        mttr=float(rng.uniform(20.0, 120.0)) if with_failures else None,
        seed=seed,
        baud_rate=1e6 if with_net else None)
    net_cap = None if with_net else 0   # None = auto-size
    return g, fleet, deadlines, budgets, scenario, n_users, net_cap


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 999),
       opt=st.sampled_from([types.OPT_COST, types.OPT_TIME]),
       with_failures=st.booleans(),
       with_net=st.booleans())
def test_sweep_select_free_bit_identical(seed, opt, with_failures,
                                         with_net):
    """simulation.sweep's select-free engine == the reference batch=1
    path, bitwise, over random grids x opt x failures x net."""
    g, fleet, dls, buds, scenario, n_users, net_cap = _case(
        seed, with_failures, with_net)
    ref_res = simulation.sweep(g, fleet, dls, buds, opt, n_users,
                               scenario=scenario, batch=1,
                               net_cap=net_cap, select_free=False)
    swp_res = simulation.sweep(g, fleet, dls, buds, opt, n_users,
                               scenario=scenario, net_cap=net_cap,
                               select_free=True)
    assert_results_identical(ref_res, swp_res)


def test_run_sweep_matches_run_inner_unbatched():
    """engine.run_sweep == engine.run_inner outside any vmap, and its
    batch=1 degenerate case == the batch=8 case (the micro-steps only
    repack work, never change it)."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=8, n_users=3)
    params = simulation._scenario_params(
        fleet, 1500.0, 15000.0, types.OPT_COST, 3, simulation.Scenario())
    me = simulation._max_events(g.n, 3, 3100.0, 1.0)
    a = engine.run_inner(g, fleet, params, 3, me, batch=1)
    b = engine.run_sweep(g, fleet, params, 3, me, batch=8)
    c = engine.run_sweep(g, fleet, params, 3, me, batch=1)
    for name in a._fields:
        if name in HOW_COUNTERS:
            continue
        for x, y, z in zip(jax.tree_util.tree_leaves(getattr(a, name)),
                           jax.tree_util.tree_leaves(getattr(b, name)),
                           jax.tree_util.tree_leaves(getattr(c, name))):
            assert np.array_equal(np.asarray(x), np.asarray(y)), name
            assert np.array_equal(np.asarray(x), np.asarray(z)), name


def test_sweep_sharded_matches_sweep_single_device():
    """sweep_sharded on the host's single device == sweep, bitwise
    (same lane layout, no shard_map in the way)."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(5), n_jobs=6, n_users=2)
    dls, buds = [700.0, 1400.0], [6000.0, 14000.0]
    a = simulation.sweep(g, fleet, dls, buds, types.OPT_COST, 2)
    b = simulation.sweep_sharded(g, fleet, dls, buds, types.OPT_COST, 2)
    assert_results_identical(a, b)


def test_sweep_sharded_matches_under_forced_devices():
    """shard_map smoke test: with 8 forced host devices, the sharded
    sweep (padded S = 6 -> 8 lanes) is bitwise identical to the plain
    vmap sweep.  Runs in a subprocess so the main pytest process keeps
    its single CPU device."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import gridlet, resource, simulation, types
        assert len(jax.devices()) == 8
        fleet = resource.wwg_fleet()
        g = gridlet.task_farm(jax.random.PRNGKey(5), n_jobs=6, n_users=2)
        dls = [500.0, 1000.0, 2000.0]
        buds = [6000.0, 14000.0]
        a = simulation.sweep(g, fleet, dls, buds, types.OPT_COST, 2)
        b = simulation.sweep_sharded(g, fleet, dls, buds,
                                     types.OPT_COST, 2)
        skip = {"n_steps", "n_spec", "n_scans", "n_reseeds"}
        for name in a._fields:
            if name in skip:
                continue
            for x, y in zip(jax.tree_util.tree_leaves(getattr(a, name)),
                            jax.tree_util.tree_leaves(getattr(b, name))):
                assert np.array_equal(np.asarray(x), np.asarray(y)), name
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


def test_sweep_sharded_builds_once_per_static_shape():
    """Repeated sweep_sharded calls with the same shapes and statics
    reuse one program (no trace at all on the second call); another
    max_events builds exactly one more.  Answers == sweep, bitwise."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(9), n_jobs=4, n_users=1)
    dls, buds = [600.0, 1300.0], [5000.0, 12000.0]
    ref_res = simulation.sweep(g, fleet, dls, buds, types.OPT_COST, 1)
    n0 = simulation._sweep_lanes._cache_size()
    a = simulation.sweep_sharded(g, fleet, dls, buds, types.OPT_COST, 1)
    assert simulation._sweep_lanes._cache_size() == n0 + 1
    with count_traces() as traces:
        b = simulation.sweep_sharded(g, fleet, dls, buds, types.OPT_COST, 1)
        jax.block_until_ready(b)
    assert traces[0] == 0
    assert simulation._sweep_lanes._cache_size() == n0 + 1
    me = simulation._max_events(g.n, 1, 2 * max(dls) + 100.0, 1.0) + 64
    simulation.sweep_sharded(g, fleet, dls, buds, types.OPT_COST, 1,
                             max_events=me)
    assert simulation._sweep_lanes._cache_size() == n0 + 2
    assert_results_identical(ref_res, a, "first: ")
    assert_results_identical(ref_res, b, "second: ")


def test_sweep_sharded_builds_once_per_mesh_under_forced_devices():
    """With 8 forced host devices: a second call on all 8 traces
    nothing, a call on the first 4 builds its own program (the mesh is
    part of the key), and every answer == sweep, bitwise -- padding
    included (S = 6 -> 8 lanes on 8 devices, 6 -> 8 on 4)."""
    code = """
        import jax, numpy as np
        from repro.core import gridlet, resource, simulation, types
        assert len(jax.devices()) == 8
        traces = [0]
        def listener(name, _secs, **_):
            if name == "/jax/core/compile/jaxpr_trace_duration":
                traces[0] += 1
        jax.monitoring.register_event_duration_secs_listener(listener)
        fleet = resource.wwg_fleet()
        g = gridlet.task_farm(jax.random.PRNGKey(5), n_jobs=6, n_users=2)
        dls = [500.0, 1000.0, 2000.0]
        buds = [6000.0, 14000.0]
        a = simulation.sweep(g, fleet, dls, buds, types.OPT_COST, 2)
        run = lambda devs: jax.block_until_ready(simulation.sweep_sharded(
            g, fleet, dls, buds, types.OPT_COST, 2, devices=devs))
        n0 = simulation._sweep_lanes._cache_size()
        outs = [run(jax.devices())]
        assert simulation._sweep_lanes._cache_size() == n0 + 1
        traces[0] = 0
        outs.append(run(jax.devices()))
        assert traces[0] == 0, traces[0]
        assert simulation._sweep_lanes._cache_size() == n0 + 1
        outs.append(run(jax.devices()[:4]))
        assert traces[0] > 0
        assert simulation._sweep_lanes._cache_size() == n0 + 2
        skip = {"n_steps", "n_spec", "n_scans", "n_reseeds"}
        for i, b in enumerate(outs):
            for name in a._fields:
                if name in skip:
                    continue
                for x, y in zip(jax.tree_util.tree_leaves(getattr(a, name)),
                                jax.tree_util.tree_leaves(getattr(b, name))):
                    assert np.array_equal(np.asarray(x), np.asarray(y)), \
                        (i, name)
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 999), k=st.sampled_from([1, 4]))
def test_slab_live_gate_three_way(seed, k):
    """The slab kernels' scalar ``live`` gate: live=True is a bitwise
    pass-through, live=False an all-sentinel no-op -- on the XLA
    fallback, Pallas interpret and the numpy oracle alike."""
    rng = np.random.RandomState(seed)
    r, j = 8, 12
    rem = np.where(rng.rand(r, j) > 0.3, rng.rand(r, j) * 100.0, 0.0)
    rem = rem.astype(np.float32)
    mips = rng.uniform(1.0, 4.0, r).astype(np.float32)
    pes = rng.randint(1, 5, r).astype(np.int32)
    args = (jnp.asarray(rem), jnp.asarray(mips), jnp.asarray(pes))

    base = ops.event_scan_slab(*args, k)
    for live in (True, False):
        xla = ops.event_scan_slab(*args, k, live=jnp.asarray(live))
        pal = event_scan_mod.event_scan_slab(*args, k,
                                             live=jnp.asarray(live),
                                             interpret=True)
        orc = ref.event_scan_slab_ref(rem, mips, pes, k, live=live)
        if live:   # pass-through: bitwise equal to the ungated call
            assert np.array_equal(np.asarray(xla[0]), np.asarray(base[0]))
            assert np.array_equal(np.asarray(xla[1]), np.asarray(base[1]))
        else:      # no-op: every wave the (BIG, J) sentinel, everywhere
            assert np.all(np.asarray(xla[0]) >= 3.0e38)
            assert np.all(np.asarray(xla[1]) == j)
            for got in (pal, orc):
                assert np.array_equal(np.asarray(xla[0]),
                                      np.asarray(got[0]))
                assert np.array_equal(np.asarray(xla[1]),
                                      np.asarray(got[1]))
        np.testing.assert_allclose(np.asarray(xla[0]), np.asarray(pal[0]),
                                   rtol=2e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(xla[0]), np.asarray(orc[0]),
                                   rtol=2e-3, atol=1e-3)
        assert np.array_equal(np.asarray(xla[1]), np.asarray(pal[1]))
        assert np.array_equal(np.asarray(xla[1]), np.asarray(orc[1]))


def test_masked_apply_contract():
    """des.FnSource.masked_apply: fire=True == apply bitwise, fire=False
    == identity bitwise, even at a garbage event time -- the contract
    the sweep engine's unconditional supersteps rest on."""
    from repro.core import des
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(1), n_jobs=5, n_users=2)
    params = simulation._scenario_params(
        fleet, 900.0, 9000.0, types.OPT_COST, 2,
        simulation.Scenario(mtbf=300.0, mttr=50.0, seed=7))
    state = engine.init_state(g, fleet, 2, params=params)

    def bump(s, now):   # touches floats, ints and the rng key
        key, _ = jax.random.split(s.rng_key)
        return types.replace(s, t=jnp.maximum(s.t, now),
                             n_events=s.n_events + 1, rng_key=key)

    src = des.FnSource(kind=des.K_FAILURE, name="bump",
                       candidates_fn=lambda s: jnp.full((1,), types.INF),
                       apply_fn=bump)
    t = jnp.asarray(25.0, jnp.float32)
    garbage = jnp.asarray(-1.0e30, jnp.float32)
    on = src.masked_apply(state, t, jnp.asarray(True))
    want = src.apply(state, t)
    off = src.masked_apply(state, garbage, jnp.asarray(False))
    for x, y in zip(jax.tree_util.tree_leaves(on),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(off),
                    jax.tree_util.tree_leaves(state)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("model,kind_name", [("commodity", "market"),
                                             ("auction", "auction")])
def test_pricing_sources_masked_apply_noop(model, kind_name):
    """The MARKET and AUCTION sources honour the masked-apply contract
    on the REAL engine sources: fire=True == apply bitwise; fire=False
    == bitwise identity even at a garbage event time (every write is
    gated on the round being due, and the auction's PRNG split is
    selected back).  This is what lets the sweep paths run pricing
    rounds unconditionally."""
    from repro.core import des
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(2), n_jobs=6, n_users=2)
    params = simulation._scenario_params(
        fleet, 900.0, 9000.0, types.OPT_COST, 2,
        simulation.Scenario(pricing_model=model, market_period=40.0,
                            auction_period=40.0, seed=5))
    state = engine.init_state(g, fleet, 2, params=params)
    sources = engine._make_sources(fleet, params, 2,
                                   {"select_free": True})
    pos = {s.kind: i for i, s in enumerate(sources)}
    kind = des.K_MARKET if model == "commodity" else des.K_AUCTION
    src = sources[pos[kind]]
    assert src.name == kind_name

    t_due = jnp.asarray(40.0, jnp.float32)      # the round IS due
    garbage = jnp.asarray(-1.0e30, jnp.float32)
    on = src.masked_apply(state, t_due, jnp.asarray(True))
    want = src.apply(state, t_due)
    off = src.masked_apply(state, garbage, jnp.asarray(False))
    for x, y in zip(jax.tree_util.tree_leaves(on),
                    jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(off),
                    jax.tree_util.tree_leaves(state)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    # The fired round really moved the posted price and rescheduled.
    assert not np.array_equal(np.asarray(on.price), np.asarray(state.price))
    nxt = on.next_market if model == "commodity" else on.next_auction
    assert float(nxt) == 80.0


def test_run_sweep_lanes_matches_per_lane_reference():
    """engine.run_sweep_lanes (the lane-batched loop with any-lane
    cond skips) == running each lane's params through engine.run_inner
    one at a time -- heterogeneous lanes, so some iterations take the
    skip branches while others need the taken ones."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(11), n_jobs=7, n_users=3)
    tmpl = simulation._scenario_params(
        fleet, 0.0, 0.0, types.OPT_COST, 3, simulation.Scenario())
    me = simulation._max_events(g.n, 3, 4100.0, 1.0)
    dls = jnp.asarray([250.0, 900.0, 2000.0], jnp.float32)
    buds = jnp.asarray([2500.0, 9000.0, 20000.0], jnp.float32)
    p_lanes = jax.vmap(
        lambda d, b: simulation._scenario_point(tmpl, d, b, 3))(dls, buds)
    lanes = jax.jit(
        lambda p: engine.run_sweep_lanes(g, fleet, p, 3, me))(p_lanes)
    for i in range(dls.shape[0]):
        one = engine.run_inner(
            g, fleet, simulation._scenario_point(tmpl, dls[i], buds[i], 3),
            3, me, batch=1)
        lane = jax.tree_util.tree_map(lambda x: x[i], lanes)
        assert_results_identical(one, lane, tag=f"lane{i} ")


def test_fleet_price_per_mi_divided_on_host():
    """The G$/MI price is a stored fleet field, divided once in numpy,
    so no backend ever divides it inside a traced program."""
    fleet = resource.wwg_fleet()
    want = (np.asarray(fleet.cost_per_sec, np.float32)
            / np.asarray(fleet.mips_per_pe, np.float32))
    assert np.asarray(fleet.cost_per_mi).dtype == np.float32
    assert np.array_equal(np.asarray(fleet.cost_per_mi), want)


@pytest.mark.parametrize("captured", [True, False])
def test_run_sweep_lanes_closure_or_args_match_engine_run(captured):
    """Gridlets and fleet captured by the jitted lane call (constants
    XLA may fold) or passed as arguments: every lane is bitwise equal to
    its own engine.run(batch=1) -- policy and pricing lanes, whose
    dispatch and spend read the fleet's G$/MI price."""
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(5), n_jobs=6, n_users=3)
    me = simulation._max_events(g.n, 3, 2000.0, 1.0)
    scs = (simulation.Scenario(policy=types.OPT_COST),
           simulation.Scenario(policy=types.OPT_TIME),
           simulation.Scenario(policy=types.OPT_COST,
                               pricing_model="commodity",
                               market_period=60.0, market_gain=0.25))
    ps = [simulation._scenario_params(fleet, 2000.0, 9000.0,
                                      types.OPT_COST, 3, sc) for sc in scs]
    p_lanes = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)
    if captured:
        lanes = jax.jit(lambda pp: engine.run_sweep_lanes(
            g, fleet, pp, 3, me, batch=engine.DEFAULT_BATCH))(p_lanes)
    else:
        lanes = jax.jit(lambda gg, fl, pp: engine.run_sweep_lanes(
            gg, fl, pp, 3, me, batch=engine.DEFAULT_BATCH))(g, fleet,
                                                            p_lanes)
    for i, p in enumerate(ps):
        one = engine.run(g, fleet, p, 3, me, batch=1)
        lane = jax.tree_util.tree_map(lambda x: x[i], lanes)
        assert_results_identical(one, lane, tag=f"lane{i} ")
