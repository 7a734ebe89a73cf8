"""Vectorised DES engine throughput (the core's own perf table).

The 2002 toolkit ran one JVM thread per entity; the array engine's cost
is events/second at fleet scale.  Three WWG scenarios (1 / 20 / 200
users), a failure scenario, a correlated trunk-cut scenario (shared
failure domain + trace-driven injection + the retry/backoff broker)
and a large-J deep-queue scenario are timed
and written to ``benchmarks/artifacts/BENCH_engine.json`` with
steady-state events/sec, compile time, while-loop iterations and
wall-clock, so future PRs have a perf trajectory (the full schema and
the PR-over-PR table live in docs/PERFORMANCE.md).

Timing discipline: the first call is timed separately (``compile_s`` --
jit tracing + XLA compile + the first run) from the steady-state run
that follows (``wall_s``/``events_per_sec``).  Folding compilation into
the throughput number hides the real per-iteration constant, which is
what the engine work optimises.

Each scenario runs twice more: once with the k-step speculative
superstep batching that is the engine default
(``engine.DEFAULT_BATCH``) -- the timed run -- and once with
``batch=1`` to record the iteration-count baseline and assert the two
runs are bit-for-bit identical (``batched_identical``).  The 20-user
cell is additionally compared against the recorded pre-superstep engine
(tests/data/golden_pre_refactor.json): results must stay identical
while while-loop iterations keep shrinking (``iteration_ratio``).
A third untimed pass per scenario runs with the telemetry metrics ring
recording and gates ``telemetry_identical`` -- the ring is a separate
loop carry that must never feed back into the simulation.  Every cell
also carries roofline columns (``arith_intensity`` /
``pct_of_roofline`` / ``roofline_bound``): the analytic FLOP/byte
model of the associative slab solve at the cell's job-table shape
(benchmarks/roofline.bench_row) grounded against the measured wall.

Three microbench sections ride along under the ``_`` prefix (skipped
by the per-scenario renderer columns, rendered as their own tables):

* ``_rank_crossover`` -- XLA-compiled wall-clock of the three exact
  in-kernel ranking algorithms (pairwise O(J^2), bitonic O(J log^2 J),
  lexsort O(J log J)) across J, measuring the
  ``event_scan.RANK_BITONIC_MIN_J`` crossover claim;
* ``_sweep_bench`` -- the sweep engine section: steady-state wall of
  ``simulation.sweep`` through the reference batch=1 path vs the
  lane-batched select-free sweep engine (``select_free=True``, the
  default), timed as interleaved median-of-3 with ``compile_s`` split
  out per row (first call) so the ratio measures execution, not
  tracing or load transients; bitwise ``sweep_identical`` checks on
  both the coarse-poll headline grid and the paper-default-poll grid;
  and a device-count scaling row timing ``simulation.sweep_sharded``
  over the first 1 vs 2 devices of this process on a
  heterogeneous-run-length grid (short-deadline lanes grouped on one
  device stop costing while-loop iterations on the other).  It needs
  two devices: on the CPU, start the bench with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=2``;
* ``_strategy_sweep`` -- the economic-broker section: the four DBC
  strategies plus the commodity/auction pricing models and plan-ahead
  dispatch as lanes of one ``engine.run_sweep_lanes`` call, with
  CI-gated ``strategy_identical`` (every lane bitwise equal to its
  ``engine.run(batch=1)`` reference) and ``table1_ordering`` (cost-min
  spends no more than time-min; time-min finishes no later) bits.

:func:`run` enables the JAX persistent compilation cache
(:mod:`repro.compile_cache`: ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache`` in the checkout) so repeated bench runs -- and the
bench rows that share static shapes, which all reuse the single
module-level jitted ``simulation._sweep_grid`` -- skip recompilation.

Sized for the 1-core CPU container (the kernel routes through its XLA
fallback there); the same jit'd program is the TPU-target workload for
kernels.event_scan / event_scan_slab.
"""
from __future__ import annotations

import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import engine, gridlet, resource, simulation, types
from repro.kernels import event_scan as event_scan_mod

from . import roofline
from .common import art_path

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
GOLDEN_PATH = os.path.join(REPO, "tests", "data",
                           "golden_pre_refactor.json")


def _deep_fleet():
    """Few resources, deep per-resource job tables: 2 x 80-PE
    time-shared resources.  With 4 users the broker stages up to
    ``4 * 2 * 80 = 640`` concurrent jobs per resource, so the job-slot
    axis J reaches 640 -- strictly past RANK_BITONIC_MIN_J = 512, so
    Pallas lane-pads it to 1024 and selects the bitonic in-kernel rank
    on TPU; on CPU it is the widest lexsort the XLA fallback sees."""
    return resource.make_fleet([80, 80], [100.0, 120.0], [1.0, 2.0],
                               types.TIME_SHARED)


# (n_users, n_jobs_per_user, scenario, fleet_fn, deadline, budget,
# extras): the failure cell re-runs the 20-user workload with the
# failure/recovery event source live (MTBF=500, MTTR=25) so the perf
# trajectory tracks the dynamic-resource path -- including how far
# dense interference degrades the speculation horizon -- not just the
# static fleet; the 4-user cell is the large-J rank-crossover workload;
# the net cell re-runs the 20-user workload with real file payloads
# over the contention-aware fair-share links (suffix "_net": the
# NETWORK event source + link_scan kernel live in the hot path, with
# one phantom background flow per link).  ``extras`` keys: suffix,
# in_bytes/out_bytes (payloads; default 0), net (enable the network
# subsystem with an auto-sized transfer table).
SCENARIOS = (
    (1, 200, None, None, 2000.0, 22000.0, None),
    (20, 100, None, None, 2000.0, 22000.0, None),
    (200, 10, None, None, 2000.0, 22000.0, None),
    (20, 100, simulation.Scenario(mtbf=500.0, mttr=25.0, seed=1), None,
     2000.0, 22000.0, dict(suffix="_fail")),
    (4, 512, None, _deep_fleet, 2000.0, 500000.0, None),
    (20, 100, simulation.Scenario(baud_rate=28_000.0, bg_flows=1.0),
     None, 2000.0, 22000.0,
     dict(suffix="_net", net=True, in_bytes=200_000.0,
          out_bytes=100_000.0)),
    # The correlated-failure cell: the WWG fleet's first five resources
    # share one trunk (11 = R + trunk id 0 targets the whole domain) and
    # a replayable trace cuts it mid-run for 100 time units -- every
    # resource behind the trunk fails in ONE superstep, in-flight
    # gridlets refund and resubmit, and the retry/backoff broker knobs
    # are live so the perf trajectory tracks the fault-tolerant path.
    (20, 100, simulation.Scenario(
        trunk_of=[0, 0, 0, 0, 0, -1, -1, -1, -1, -1, -1],
        fault_trace=[(500.0, 11, 0), (600.0, 11, 1)],
        retry_limit=8, backoff_base=1.0, blacklist_cooldown=5.0),
     None, 2000.0, 22000.0, dict(suffix="_trunk")),
)


def scenario_case(spec):
    """One ``SCENARIOS`` row -> (cell name, gridlets, fleet,
    ``run_experiment`` keyword arguments without ``batch``) at its
    bench size; ``net_cap=None`` auto-sizes the transfer table."""
    n_users, n_jobs, scenario, fleet_fn, deadline, budget, extras = spec
    extras = extras or {}
    fleet = resource.wwg_fleet() if fleet_fn is None else fleet_fn()
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=n_jobs,
                          n_users=n_users,
                          in_bytes=extras.get("in_bytes", 0.0),
                          out_bytes=extras.get("out_bytes", 0.0))
    kw = dict(deadline=deadline, budget=budget, opt=types.OPT_COST,
              n_users=n_users, scenario=scenario,
              net_cap=None if extras.get("net") else 0)
    name = f"engine_{n_users}u_{n_jobs}j" + extras.get("suffix", "")
    return name, g, fleet, kw


def _one(fleet, g, n_users, scenario, batch, deadline, budget,
         net_cap=0, timed=True):
    kw = dict(deadline=deadline, budget=budget, opt=types.OPT_COST,
              n_users=n_users, scenario=scenario, batch=batch,
              net_cap=net_cap)
    t0 = time.perf_counter()
    r = simulation.run_experiment(g, fleet, **kw)      # compile + run
    jax.block_until_ready(r.spent)
    first = time.perf_counter() - t0
    if not timed:       # baseline pass: results only, skip the re-run
        return r, float("nan"), float("nan")
    wall = float("inf")
    for _ in range(2):  # best-of-2: damp container load noise
        t0 = time.perf_counter()
        r = simulation.run_experiment(g, fleet, **kw)  # steady state
        jax.block_until_ready(r.spent)
        wall = min(wall, time.perf_counter() - t0)
    return r, wall, max(first - wall, 0.0)


def _rank_crossover():
    """Wall-clock of the three exact ranking algorithms, XLA-compiled
    on [8, J] rows -- the measured basis of the
    ``RANK_BITONIC_MIN_J`` in-kernel crossover (docs/PERFORMANCE.md).
    The bitonic needs a power-of-two width, so J sweeps powers of 2."""
    rows = {}
    rng = np.random.RandomState(0)
    algos = {
        "pairwise_o_j2": event_scan_mod._pairwise_rank,
        "bitonic_o_jlog2j": event_scan_mod._bitonic_rank,
        "lexsort_o_jlogj": event_scan_mod._lexsort_rank,
    }
    for j in (64, 128, 256, 512, 1024):
        rem = jnp.asarray(rng.exponential(50.0, (8, j)), jnp.float32)
        tie = jnp.asarray(
            rng.permutation(8 * j).reshape(8, j), jnp.float32)
        valid = rem > 10.0
        cell = {}
        for name, fn in algos.items():
            f = jax.jit(lambda rem, tie, valid, fn=fn:
                        fn(rem, tie, valid)[0])
            jax.block_until_ready(f(rem, tie, valid))
            t0 = time.perf_counter()
            n = 50
            for _ in range(n):
                out = f(rem, tie, valid)
            jax.block_until_ready(out)
            cell[name] = (time.perf_counter() - t0) / n * 1e6  # us
        rows[f"j{j}"] = cell
    rows["crossover_j"] = event_scan_mod.RANK_BITONIC_MIN_J
    return rows


# "How" counters may pack the same events into supersteps differently
# between the reference and sweep loops; every "what" field must match
# bitwise (same convention as tests/test_sweep_engine.py).  The
# telemetry ring is observability, not a result -- it records one row
# per committed superstep, so it inherits the packing differences.
_HOW_COUNTERS = ("n_steps", "n_spec", "n_scans", "n_reseeds",
                 "telemetry")


def results_identical(a, b) -> bool:
    for name in a._fields:
        if name in _HOW_COUNTERS:
            continue
        la = jax.tree_util.tree_leaves(getattr(a, name))
        lb = jax.tree_util.tree_leaves(getattr(b, name))
        if len(la) != len(lb):
            return False
        for x, y in zip(la, lb):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                return False
    return True


# Device-scaling lane mix, chosen so run lengths differ wildly across
# the sharded axis: the deep fleet at J=640 makes per-iteration work
# expensive, the infeasible deadline (2.0) makes its 20 lanes give up
# in a handful of supersteps while the 10000.0 lanes run ~138, and the
# budget axis stays minor (non-sharded).  Sharding deadline-major puts
# all short lanes on one device, which then stops paying while-loop
# iterations for the long lanes -- the convoy effect a single vmap
# cannot avoid on any device count.
def device_scaling_grid():
    """(gridlets, fleet, deadlines, budgets, n_users) of the
    device-scaling sweep: deep fleet, 2 deadlines x 20 budgets."""
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=256, n_users=4)
    return (g, _deep_fleet(), jnp.asarray([2.0, 10000.0]),
            jnp.linspace(150000.0, 500000.0, 20), 4)


def _device_scaling():
    """Time ``sweep_sharded`` over the first 1 and 2 devices of this
    process (one process holds every device; on the CPU it starts with
    ``--xla_force_host_platform_device_count=2``).  One steady run per
    device count -- each is a minute-scale program on the CPU, far
    above timer noise."""
    devices = jax.devices()
    if len(devices) < 2:
        raise RuntimeError(
            f"device scaling needs 2 devices, found {len(devices)}; on "
            "the CPU set XLA_FLAGS=--xla_force_host_platform_device_count=2")
    g, fleet, dls, buds, n_users = device_scaling_grid()
    rows, res = {}, {}
    for n in (1, 2):
        def call():
            r = simulation.sweep_sharded(g, fleet, dls, buds,
                                         types.OPT_COST, n_users,
                                         devices=devices[:n])
            jax.block_until_ready(r.spent)
            return r
        t0 = time.perf_counter()
        call()
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        res[n] = call()
        wall = time.perf_counter() - t0
        rows[f"dev{n}"] = {"devices": n, "wall_s": wall,
                           "compile_s": max(first - wall, 0.0),
                           "n_done": float(jnp.sum(res[n].n_done)),
                           "spent": float(jnp.sum(res[n].spent))}
    rows["device_speedup"] = rows["dev1"]["wall_s"] / rows["dev2"]["wall_s"]
    rows["device_identical"] = results_identical(res[1], res[2])
    return rows


def sweep_grid():
    """(gridlets, fleet, deadlines, budgets, coarse-poll scenario,
    n_users) of the sweep section: 20 users x 25 jobs on the WWG fleet,
    a 2 x 2 deadline x budget grid."""
    return (gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=25,
                              n_users=20),
            resource.wwg_fleet(), jnp.asarray([1500.0, 2000.0]),
            jnp.asarray([15000.0, 22000.0]),
            simulation.Scenario(sched_min_period=10.0, sched_frac=0.05),
            20)


def _sweep_bench():
    """The sweep engine section: the reference batch=1 grid
    (``select_free=False`` -- under vmap its conds lower to selects, so
    both branches execute every superstep) vs the lane-batched sweep
    engine (``select_free=True``: the scenario lanes ride INSIDE the
    while loop, so the reseed sort / broker poll / rare applies run
    under real any-lane conds and the speculation loop exits early).

    Timing discipline: one untimed first call per path (``compile_s``),
    then three timed runs per path, INTERLEAVED (ref, sweep, ref,
    sweep, ...) with the median reported -- on a shared 1-core
    container a best-of or back-to-back scheme lets a load transient
    land entirely on one path and swing the ratio ~25% either way.

    The headline grid uses a coarse broker poll
    (``Scenario(sched_min_period=10, sched_frac=0.05)``): the paper's
    default (re-poll every 1 s of simulated time) makes nearly half the
    reference supersteps pure polls, which caps how deep ANY batching
    engine can speculate; scenarios that poll at realistic rates are
    what the sweep engine is for (see docs/PERFORMANCE.md, "Profiling
    checklist").  The paper-default ratio is recorded alongside as
    ``batch_speedup_paper_polls`` -- identity-checked the same way.

    Also: a bitwise identity check over every "what" field per
    scenario; a single-device ``sweep_sharded`` identity check on the
    same grid; and the 1-vs-2-device scaling rows."""
    g, fleet, deadlines, budgets, coarse, n_users = sweep_grid()
    out = {"grid": "20u/25j, 2x2 deadline x budget, "
                   "sched_min_period=10 sched_frac=0.05"}

    def measure(scen):
        kws = {"ref": dict(batch=1, select_free=False),
               "sweep": dict(select_free=True)}
        res, walls, first = {}, {k: [] for k in kws}, {}
        for tag, kw in kws.items():
            t0 = time.perf_counter()
            r = simulation.sweep(g, fleet, deadlines, budgets,
                                 types.OPT_COST, n_users, scenario=scen,
                                 **kw)
            jax.block_until_ready(r.spent)
            first[tag] = time.perf_counter() - t0
            res[tag] = r
        for _ in range(3):
            for tag, kw in kws.items():
                t0 = time.perf_counter()
                r = simulation.sweep(g, fleet, deadlines, budgets,
                                     types.OPT_COST, n_users, scenario=scen,
                                     **kw)
                jax.block_until_ready(r.spent)
                walls[tag].append(time.perf_counter() - t0)
        med = {t: sorted(w)[1] for t, w in walls.items()}
        return res, med, first

    res, med, first = measure(coarse)
    for tag in ("ref", "sweep"):
        out[f"wall_s_{tag}"] = med[tag]
        out[f"compile_s_{tag}"] = max(first[tag] - med[tag], 0.0)
        out[f"supersteps_{tag}"] = int(np.asarray(res[tag].n_steps).sum())
    out["batch"] = engine.DEFAULT_BATCH
    out["batch_speedup"] = out["wall_s_ref"] / out["wall_s_sweep"]
    out["sweep_identical"] = results_identical(res["ref"], res["sweep"])
    res_p, med_p, _ = measure(None)
    out["batch_speedup_paper_polls"] = med_p["ref"] / med_p["sweep"]
    out["sweep_identical_paper_polls"] = results_identical(
        res_p["ref"], res_p["sweep"])
    sh = simulation.sweep_sharded(g, fleet, deadlines, budgets,
                                  types.OPT_COST, n_users, scenario=coarse,
                                  devices=jax.devices()[:1])
    out["sharded_identical"] = results_identical(res["sweep"], sh)
    out["device_scaling"] = _device_scaling()
    return out


STRATEGY_DEADLINE, STRATEGY_BUDGET = 2000.0, 22000.0


def strategy_lanes():
    """(gridlets, fleet, n_users, max_events, lane names, stacked
    params) of the strategy section's seven policy/pricing lanes."""
    fleet = resource.wwg_fleet()
    n_users = 20
    g = gridlet.task_farm(jax.random.PRNGKey(3), n_jobs=25,
                          n_users=n_users)
    max_events = simulation._max_events(g.n, n_users, STRATEGY_DEADLINE,
                                        1.0)
    lanes_sc = (
        ("cost", simulation.Scenario(policy=types.OPT_COST)),
        ("time", simulation.Scenario(policy=types.OPT_TIME)),
        ("cost_time", simulation.Scenario(policy=types.OPT_COST_TIME)),
        ("none", simulation.Scenario(policy=types.OPT_NONE)),
        ("cost_commodity", simulation.Scenario(
            policy=types.OPT_COST, pricing_model="commodity",
            market_period=60.0, market_gain=0.25)),
        ("cost_auction", simulation.Scenario(
            policy=types.OPT_COST, pricing_model="auction",
            auction_period=60.0, seed=5)),
        ("cost_plan", simulation.Scenario(policy=types.OPT_COST,
                                          plan_ahead=True)),
    )
    ps = [simulation._scenario_params(fleet, STRATEGY_DEADLINE,
                                      STRATEGY_BUDGET, types.OPT_COST,
                                      n_users, sc)
          for _, sc in lanes_sc]
    p_lanes = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)
    return g, fleet, n_users, max_events, [n for n, _ in lanes_sc], p_lanes


def _strategy_sweep():
    """The economic-broker section: every DBC strategy and pricing
    model as a ``Scenario`` lane of ONE ``engine.run_sweep_lanes``
    call -- the Table-1 experiment (strategy x deadline/budget) on the
    lane-batched engine.  Seven lanes: the four broker optimisations
    under static pricing, then the cost optimiser under commodity
    repricing, sealed-bid auctions and plan-ahead (cs/0203020)
    dispatch.

    Two gate bits ride into CI like the sweep gates:

    * ``strategy_identical`` -- every lane is bitwise identical (all
      "what" fields) to its own ``engine.run(batch=1)`` reference, so
      the policy/pricing axis rides the select-free lane machinery
      without changing a single event;
    * ``table1_ordering`` -- the paper's qualitative result holds:
      cost-minimisation spends no more than time-minimisation, and
      time-minimisation finishes no later than cost-minimisation.
    """
    g, fleet, n_users, max_events, names, p_lanes = strategy_lanes()
    f = jax.jit(lambda pp: engine.run_sweep_lanes(
        g, fleet, pp, n_users, max_events, batch=engine.DEFAULT_BATCH))
    t0 = time.perf_counter()
    r = f(p_lanes)
    jax.block_until_ready(r.spent)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    r = f(p_lanes)
    jax.block_until_ready(r.spent)
    wall = time.perf_counter() - t0
    out = {"grid": f"20u/25j wwg, 7 policy/pricing lanes, "
                   f"deadline={STRATEGY_DEADLINE:.0f} "
                   f"budget={STRATEGY_BUDGET:.0f}",
           "wall_s": wall, "compile_s": max(first - wall, 0.0),
           "batch": engine.DEFAULT_BATCH, "lanes": {}}
    identical = True
    for i, name in enumerate(names):
        ref = engine.run(
            g, fleet, jax.tree_util.tree_map(lambda x: x[i], p_lanes),
            n_users, max_events, batch=1)
        lane = jax.tree_util.tree_map(lambda a: a[i], r)
        identical = identical and results_identical(ref, lane)
        identical = identical and (int(np.asarray(ref.n_steps)) +
                                   int(np.asarray(ref.n_spec))
                                   < max_events)
        out["lanes"][name] = {
            "n_done": int((np.asarray(lane.gridlets.status)
                           == types.DONE).sum()),
            "finish_t": float(np.asarray(lane.term_time).max()),
            "spent": float(np.asarray(lane.spent).sum()),
        }
    out["strategy_identical"] = bool(identical)
    rows = out["lanes"]
    out["table1_ordering"] = bool(
        rows["cost"]["spent"] <= rows["time"]["spent"] and
        rows["time"]["finish_t"] <= rows["cost"]["finish_t"])
    return out


def run():
    enable_compilation_cache()
    try:
        golden = json.load(open(GOLDEN_PATH))
    except OSError:
        golden = {}
    report, out = {}, []
    for spec in SCENARIOS:
        n_users, n_jobs, scenario, fleet_fn, deadline, budget, extras = spec
        extras = extras or {}
        name, g, fleet, kw = scenario_case(spec)
        net_cap = kw["net_cap"]
        r, wall, compile_s = _one(fleet, g, n_users, scenario,
                                  engine.DEFAULT_BATCH, deadline, budget,
                                  net_cap=net_cap)
        r1, _, _ = _one(fleet, g, n_users, scenario, 1, deadline,
                        budget, net_cap=net_cap, timed=False)
        # Telemetry identity gate: the same run with the metrics ring
        # recording must be bitwise identical on every "what" field
        # (the ring is a separate loop carry that must never feed back
        # into the simulation -- see repro/core/telemetry.py).
        r_tel = simulation.run_experiment(
            g, fleet, deadline=deadline, budget=budget,
            opt=types.OPT_COST, n_users=n_users, scenario=scenario,
            batch=engine.DEFAULT_BATCH, net_cap=net_cap, telemetry=1024)
        events = int(np.asarray(r.n_events))
        steps = int(np.asarray(r.n_steps))
        steps_k1 = int(np.asarray(r1.n_steps))
        cell = {
            "n_users": n_users,
            "n_jobs_per_user": n_jobs,
            "batch": engine.DEFAULT_BATCH,
            "wall_s": wall,
            "compile_s": compile_s,
            "events": events,
            "supersteps": steps,
            "spec_supersteps": int(np.asarray(r.n_spec)),
            "supersteps_k1": steps_k1,
            "batch_iteration_ratio": steps_k1 / max(steps, 1),
            "batched_identical": bool(
                np.array_equal(np.asarray(r.n_done),
                               np.asarray(r1.n_done)) and
                np.array_equal(np.asarray(r.spent),
                               np.asarray(r1.spent)) and
                np.array_equal(np.asarray(r.term_time),
                               np.asarray(r1.term_time)) and
                int(np.asarray(r.n_events)) == int(np.asarray(r1.n_events))),
            "events_per_sec": events / max(wall, 1e-9),
            "events_per_superstep": events / max(steps, 1),
            "scan_reseeds": int(np.asarray(r.n_reseeds)),
            "slab_hit_rate": 1.0 - (int(np.asarray(r.n_reseeds)) /
                                    max(int(np.asarray(r.n_scans)), 1)),
            # Mean speculative micro-steps riding each committed
            # superstep, and the dependent-step depth of the
            # associative-scan slab solve (log2 tree over k waves vs
            # the old k sequential fori iterations).
            "slab_depth_mean": int(np.asarray(r.n_spec)) / max(steps, 1),
            "scan_depth": int(math.ceil(math.log2(
                engine.DEFAULT_BATCH))) + 1,
            "n_done": float(np.asarray(r.n_done).sum()),
            "spent": float(np.asarray(r.spent).sum()),
            "overflow": int(np.asarray(r.overflow)),
            "truncated": bool(np.asarray(r.truncated)),
            "telemetry_identical": bool(
                results_identical(r, r_tel)
                and r_tel.telemetry is not None
                and int(np.asarray(r_tel.telemetry.n)) > 0),
        }
        # Roofline grounding: analytic arithmetic intensity of the
        # associative slab solve at this cell's [r_pad, J] shape, and
        # the measured throughput as a fraction of the intensity-capped
        # ceiling (benchmarks/roofline.bench_row; chip model is the TPU
        # target -- on the CPU CI host the percentage is a tiny
        # relative-regression signal, not a utilisation claim).
        r_pad = -(-fleet.r // engine.BLOCK_R) * engine.BLOCK_R
        j_cap = int(simulation.safe_max_jobs(
            g, engine.default_params(deadline, budget, types.OPT_COST,
                                     n_users, fleet.r), fleet))
        cell.update(roofline.bench_row(
            r_pad, j_cap, engine.DEFAULT_BATCH,
            int(np.asarray(r.n_scans)), wall))
        if extras.get("suffix") == "_fail":
            cell["scenario"] = {"mtbf": float(np.asarray(scenario.mtbf)),
                                "mttr": float(np.asarray(scenario.mttr)),
                                "seed": scenario.seed}
            cell["n_failed"] = int(np.asarray(r.n_failed))
            cell["n_resubmits"] = int(np.asarray(r.n_resubmits))
            cell["downtime_total"] = float(np.asarray(r.downtime).sum())
        if extras.get("suffix") == "_trunk":
            cell["scenario"] = {
                "trunk_members": int(np.sum(
                    np.asarray(scenario.trunk_of) == 0)),
                "fault_trace": [list(row) for row
                                in scenario.fault_trace],
                "retry_limit": scenario.retry_limit,
                "backoff_base": scenario.backoff_base,
                "blacklist_cooldown": scenario.blacklist_cooldown,
            }
            cell["n_failed"] = int(np.asarray(r.n_failed))
            cell["n_resubmits"] = int(np.asarray(r.n_resubmits))
            cell["downtime_total"] = float(np.asarray(r.downtime).sum())
        if extras.get("net"):
            cell["scenario"] = {
                "baud_rate": float(np.asarray(scenario.baud_rate)),
                "bg_flows": float(np.asarray(scenario.bg_flows)),
                "in_bytes": extras["in_bytes"],
                "out_bytes": extras["out_bytes"],
            }
            cell["net_cap"] = int(simulation.safe_net_cap(
                g, engine.default_params(deadline, budget,
                                         types.OPT_COST, n_users,
                                         fleet.r), fleet, n_users))
        if fleet_fn is not None:
            cell["fleet"] = "deep_2x80pe"
            cell["j_cap"] = int(simulation.safe_max_jobs(
                g, engine.default_params(deadline, budget,
                                         types.OPT_COST, n_users,
                                         fleet.r), fleet))
        base = None if (scenario is not None or fleet_fn is not None) \
            else golden.get(f"{n_users}u_{n_jobs}j")
        if base is not None:
            cell["pre_superstep_iterations"] = base["iterations"]
            cell["iteration_ratio"] = base["iterations"] / max(steps, 1)
            cell["result_identical"] = bool(
                np.allclose(np.asarray(r.n_done), base["n_done"]) and
                np.allclose(np.asarray(r.spent), base["spent"],
                            rtol=1e-5) and
                np.allclose(np.asarray(r.term_time), base["term_time"],
                            rtol=1e-5))
        report[name] = cell
        derived = (f"events/s~{cell['events_per_sec']:.0f} "
                   f"(compile {compile_s:.1f}s) "
                   f"steps={steps} (k1={steps_k1}, "
                   f"{cell['batch_iteration_ratio']:.2f}x) "
                   f"done={cell['n_done']:.0f} "
                   f"identical={cell['batched_identical']} "
                   f"tel={cell['telemetry_identical']} "
                   f"AI={cell['arith_intensity']:.2f}")
        if "iteration_ratio" in cell:
            derived += f" iters_vs_pre={cell['iteration_ratio']:.2f}x"
        if "n_resubmits" in cell:
            derived += (f" failed={cell['n_failed']} "
                        f"resub={cell['n_resubmits']}")
        out.append((name, wall * 1e6, derived))

    report["_rank_crossover"] = _rank_crossover()
    report["_sweep_bench"] = _sweep_bench()
    report["_strategy_sweep"] = _strategy_sweep()
    out.append(("rank_crossover", 0.0,
                " ".join(f"{k}:p{v['pairwise_o_j2']:.0f}us/"
                         f"b{v['bitonic_o_jlog2j']:.0f}us"
                         for k, v in report["_rank_crossover"].items()
                         if k.startswith("j"))))
    sb = report["_sweep_bench"]
    ds = sb.get("device_scaling", {})
    out.append(("sweep_bench", sb["wall_s_ref"] * 1e6,
                f"select-free speedup={sb['batch_speedup']:.2f}x "
                f"identical={sb['sweep_identical']} "
                f"sharded={sb['sharded_identical']} "
                f"2dev/1dev={ds.get('device_speedup', float('nan')):.2f}x"))
    ss = report["_strategy_sweep"]
    out.append(("strategy_sweep", ss["wall_s"] * 1e6,
                f"7 lanes identical={ss['strategy_identical']} "
                f"table1={ss['table1_ordering']} "
                f"cost_spent={ss['lanes']['cost']['spent']:.0f} "
                f"time_t={ss['lanes']['time']['finish_t']:.0f}"))

    with open(art_path("BENCH_engine.json"), "w") as f:
        json.dump(report, f, indent=1)
    return out
