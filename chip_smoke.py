"""Smoke run of the simulator's main path on one TPU chip.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded sweep only

Phases (one JSON line each; any failed check raises and the script
exits non-zero without printing a result):

* ``kernels`` -- ``ops.event_scan`` (with and without the rank output),
  ``ops.link_scan`` and ``ops.event_frontier`` at the widths the engine
  runs them, against the numpy oracles of ``repro.kernels.ref`` and the
  XLA implementations, at the kernel tests' tolerances;
* ``engine`` -- ``simulation.run_experiment`` on every
  ``engine_bench.SCENARIOS`` cell at its bench size: no slot overflow,
  no truncation, ``batch=DEFAULT_BATCH`` bitwise equal to ``batch=1``,
  telemetry on bitwise equal to telemetry off, and the compiled engine
  program holds ``tpu_custom_call`` (the Pallas route ran, not an XLA
  fallback or the interpreter);
* ``paper`` -- the Table 1 trace event for event (both allocation
  policies) and the quickstart figures (182/200 done, 11993 G$);
* ``sweeps`` -- ``simulation.sweep`` on the sweep-bench grid and the
  seven strategy lanes of ``engine.run_sweep_lanes``, every lane bitwise
  equal to its own ``engine.run(batch=1)``;
* ``sharded`` (``--chips 4``) -- ``simulation.sweep_sharded`` over four
  devices on the device-scaling grid, bitwise equal to the one-device
  ``sweep``, with the result's shards on four distinct devices.

Times in the output are informational: this script makes no speed
claim.  The last line is ``{"ok": true, "device": {...}}``.  The phase
functions take their sizes as arguments so tests can run them small on
the CPU; only :func:`main` requires the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import engine_bench, table1  # noqa: E402
from repro.compile_cache import enable_compilation_cache  # noqa: E402
from repro.core import (engine, gridlet, resource, simulation,  # noqa: E402
                        types)
from repro.kernels import event_scan as event_scan_mod  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

# Widths the engine hands the kernels in the bench cells: [R_pad, J]
# job-slot tables (WWG fleet R_pad=16 at J=32/640/2000, the deep fleet
# R_pad=8 at J=640), the _net cell's [L, T] transfer table, and the
# event-frontier segment layouts of the 20-user, deep-fleet and _net
# cells.
EVENT_WIDTHS = ((16, 32), (16, 640), (16, 2000), (8, 640))
LINK_WIDTHS = ((16, 640),)
FRONTIER_SIZES = ((16, 11, 11, 1, 0, 1, 1, 0, 2000, 2000, 11, 1),
                  (8, 2, 2, 1, 0, 1, 1, 0, 2048, 2048, 2, 1),
                  (16, 11, 11, 1, 0, 1, 1, 2016, 2000, 2000, 11, 1))

class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _close(got, want, what, rtol, atol=0.0):
    try:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=rtol, atol=atol)
    except AssertionError as e:
        raise SmokeFailure(f"{what}: {e}") from None


def _equal(got, want, what):
    check(np.array_equal(np.asarray(got), np.asarray(want)), what)


def phase_kernels(event_widths=EVENT_WIDTHS, link_widths=LINK_WIDTHS,
                  frontier_sizes=FRONTIER_SIZES, seed=0) -> dict:
    """Every engine kernel through ``ops`` (the route the engine takes
    on this backend) vs the XLA implementation and the numpy oracle."""
    rng = np.random.RandomState(seed)
    calls = 0
    t0 = time.perf_counter()
    for r, j in event_widths:
        rem = rng.exponential(50.0, (r, j)).astype(np.float32)
        rem[rng.rand(r, j) < 0.4] = 0.0
        mips = rng.uniform(1.0, 500.0, (r,)).astype(np.float32)
        pes = rng.randint(1, 9, (r,)).astype(np.int32)
        tie = rng.permutation(r * j).reshape(r, j).astype(np.float32)
        pol = rng.randint(0, 2, (r,)).astype(np.int32)
        args = (jnp.asarray(rem), jnp.asarray(mips), jnp.asarray(pes))
        kw = dict(tie=jnp.asarray(tie), policy=jnp.asarray(pol))
        want_x = event_scan_mod.event_scan_xla(*args, **kw, with_rank=True)
        want_o = ref.event_scan_ref(rem, mips, pes, tie=tie, policy=pol,
                                    with_rank=True)
        valid = rem > 0
        for with_rank in (False, True):
            got = ops.event_scan(*args, **kw, with_rank=with_rank)
            calls += 1
            what = f"event_scan[{r},{j}] with_rank={with_rank}"
            for want, name in ((want_x, "xla"), (want_o, "oracle")):
                _close(got[0], want[0], f"{what} rate vs {name}", 1e-4,
                       1e-4)
                _close(got[1], want[1], f"{what} t_min vs {name}", 1e-4)
                _equal(got[3], want[3], f"{what} occupancy vs {name}")
                if with_rank:
                    _equal(np.asarray(got[4])[valid],
                           np.asarray(want[4])[valid],
                           f"{what} rank vs {name}")
            _equal(got[2], want_x[2], f"{what} argmin vs xla")
    for l, t in link_widths:
        rem = rng.exponential(1e5, (l, t)).astype(np.float32)
        rem[rng.rand(l, t) < 0.4] = 0.0
        baud = rng.uniform(100.0, 1e4, (l,)).astype(np.float32)
        baud[0], baud[3 % l] = 0.0, np.inf        # dead, uncontended
        bg = rng.choice([0.0, 1.0, 2.5], (l,)).astype(np.float32)
        tie = rng.permutation(l * t).reshape(l, t).astype(np.float32)
        args = (jnp.asarray(rem), jnp.asarray(baud))
        kw = dict(bg=jnp.asarray(bg), tie=jnp.asarray(tie))
        got = ops.link_scan(*args, **kw)
        calls += 1
        want_x = event_scan_mod.link_scan_xla(*args, **kw)
        want_o = ref.link_scan_ref(rem, baud, bg=bg, tie=tie)
        what = f"link_scan[{l},{t}]"
        for want, name in ((want_x, "xla"), (want_o, "oracle")):
            _close(got[0], want[0], f"{what} rate vs {name}", 1e-4, 1e-4)
            _close(got[1], want[1], f"{what} t_min vs {name}", 1e-4)
            _equal(got[3], want[3], f"{what} occupancy vs {name}")
        _equal(got[2], want_x[2], f"{what} argmin vs xla")
    for sizes in frontier_sizes:
        c = sum(sizes)
        cand = np.where(rng.rand(c) < 0.6, np.inf,
                        rng.uniform(0.0, 500.0, c)).astype(np.float32)
        cuts = (rng.rand(c) < 0.5).astype(np.float32)
        got = ops.event_frontier(jnp.asarray(cand), sizes,
                                 cuts=jnp.asarray(cuts))
        calls += 1
        want_o = ref.event_frontier_ref(cand, sizes, cuts=cuts)
        for i, (a, b) in enumerate(zip(got, want_o)):
            _equal(a, b, f"event_frontier{sizes} output {i} vs oracle")
    return {"phase": "kernels", "calls": calls,
            "wall_s_informational": time.perf_counter() - t0}


def phase_engine(spec) -> dict:
    """One bench cell through ``run_experiment``: batched, batch=1 and
    telemetry-on runs, bitwise equal on every result field."""
    name, g, fleet, kw = engine_bench.scenario_case(spec)
    t0 = time.perf_counter()
    r = simulation.run_experiment(g, fleet, batch=engine.DEFAULT_BATCH,
                                  **kw)
    jax.block_until_ready(r.spent)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    r = simulation.run_experiment(g, fleet, batch=engine.DEFAULT_BATCH,
                                  **kw)
    jax.block_until_ready(r.spent)
    wall_s = time.perf_counter() - t0
    r1 = simulation.run_experiment(g, fleet, batch=1, **kw)
    r_tel = simulation.run_experiment(g, fleet, batch=engine.DEFAULT_BATCH,
                                      telemetry=1024, **kw)
    check(int(r.overflow) == 0, f"{name}: slot overflow {int(r.overflow)}")
    check(not bool(r.truncated), f"{name}: truncated at max_events")
    check(engine_bench.results_identical(r, r1),
          f"{name}: batch={engine.DEFAULT_BATCH} differs from batch=1")
    check(engine_bench.results_identical(r, r_tel),
          f"{name}: telemetry on differs from telemetry off")
    check(int(r_tel.telemetry.n) > 0, f"{name}: telemetry ring empty")
    # the program run_experiment ran above (a compile-cache hit)
    params, max_events, max_jobs, net_cap = simulation.experiment_args(
        g, fleet, **kw)
    program = engine._run_jit.lower(
        g, fleet, params, n_users=kw["n_users"], max_events=max_events,
        max_jobs=max_jobs, batch=engine.DEFAULT_BATCH, net_cap=net_cap,
        telemetry=None).compile().as_text()
    return {"phase": "engine", "cell": name, "j_cap": max_jobs,
            "n_done": float(jnp.sum(r.n_done)),
            "spent": float(jnp.sum(r.spent)),
            "events": int(r.n_events), "supersteps": int(r.n_steps),
            "supersteps_k1": int(r1.n_steps),
            "pallas_calls": program.count("tpu_custom_call"),
            "first_call_s_informational": first_s,
            "wall_s_informational": wall_s}


def phase_paper() -> dict:
    """Table 1 trace (both policies, batch 1 and batched) and the
    quickstart's figures."""
    t0 = time.perf_counter()
    for policy, want in table1.TRACES.items():
        for batch in (1, engine.DEFAULT_BATCH):
            res = engine.run_direct(
                gridlet.make_batch(table1.LENGTHS),
                resource.table1_resource(policy), 0, table1.ARRIVALS,
                max_events=64, batch=batch)
            tt, kind, who = (np.asarray(x) for x in res.trace)
            m = kind >= 0
            got = list(zip(tt[m].tolist(), kind[m].tolist(),
                           who[m].tolist()))
            check(got == want, f"Table 1 trace policy={policy} "
                               f"batch={batch}: {got}")
    # The quickstart's published output was drawn with the
    # non-partitionable threefry (JAX's default before 0.5).
    with jax.threefry_partitionable(False):
        farm = gridlet.task_farm(jax.random.PRNGKey(7), n_jobs=200)
    res = simulation.run_experiment(farm, resource.wwg_fleet(),
                                    deadline=600.0, budget=12000.0,
                                    opt=types.OPT_COST)
    n_done, spent = int(res.n_done[0]), float(res.spent[0])
    check(n_done == 182, f"quickstart n_done {n_done} != 182")
    check(round(spent) == 11993, f"quickstart spent {spent} != 11993")
    return {"phase": "paper", "table1": "match", "quickstart_done": n_done,
            "quickstart_spent": spent,
            "wall_s_informational": time.perf_counter() - t0}


def _lane(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def phase_sweeps(sweep_grid=None, strategy=None) -> dict:
    """The select-free sweep grid and the strategy lanes, every lane
    against its own ``engine.run(batch=1)``."""
    g, fleet, dls, buds, scen, n_users = \
        sweep_grid or engine_bench.sweep_grid()
    t0 = time.perf_counter()
    sw = simulation.sweep(g, fleet, dls, buds, types.OPT_COST, n_users,
                          scenario=scen, select_free=True)
    template, max_events, max_jobs, _, _ = simulation._sweep_statics(
        g, fleet, jnp.asarray(dls), types.OPT_COST, n_users, None, scen,
        None, 0, True)
    for i, d in enumerate(np.asarray(dls)):
        for j, b in enumerate(np.asarray(buds)):
            p = simulation._scenario_point(template, d, b, n_users)
            ref_run = simulation.summarize(
                engine.run(g, fleet, p, n_users, max_events, max_jobs,
                           batch=1), p, n_users, fleet.r, max_events)
            check(engine_bench.results_identical(
                ref_run, _lane(_lane(sw, i), j)),
                f"sweep lane ({d}, {b}) differs from engine.run(batch=1)")
    g, fleet, n_users, max_events, names, p_lanes = \
        strategy or engine_bench.strategy_lanes()
    # gridlets and fleet captured as constants, as a user's lambda would
    lanes = jax.jit(lambda pp: engine.run_sweep_lanes(
        g, fleet, pp, n_users, max_events,
        batch=engine.DEFAULT_BATCH))(p_lanes)
    for i, name in enumerate(names):
        ref_run = engine.run(g, fleet, _lane(p_lanes, i), n_users,
                             max_events, batch=1)
        check(int(ref_run.n_steps) + int(ref_run.n_spec) < max_events,
              f"strategy lane {name}: reference truncated")
        check(engine_bench.results_identical(ref_run, _lane(lanes, i)),
              f"strategy lane {name} differs from engine.run(batch=1)")
    return {"phase": "sweeps", "sweep_lanes": int(np.size(dls) *
                                                  np.size(buds)),
            "sweep_n_done": float(jnp.sum(sw.n_done)),
            "strategy_lanes": len(names),
            "strategy_spent": float(jnp.sum(lanes.spent)),
            "wall_s_informational": time.perf_counter() - t0}


def phase_sharded(devices, grid=None) -> dict:
    """``sweep_sharded`` over ``devices`` vs the one-device ``sweep``:
    bitwise equal, result split over every device."""
    g, fleet, dls, buds, n_users = grid or engine_bench.device_scaling_grid()
    t0 = time.perf_counter()
    one = simulation.sweep(g, fleet, dls, buds, types.OPT_COST, n_users)
    jax.block_until_ready(one.spent)
    one_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sh = simulation.sweep_sharded(g, fleet, dls, buds, types.OPT_COST,
                                  n_users, devices=devices)
    jax.block_until_ready(sh.spent)
    sh_s = time.perf_counter() - t0
    check(engine_bench.results_identical(one, sh),
          "sweep_sharded differs from the one-device sweep")
    shards = sh.spent.addressable_shards
    on = {s.device.id for s in shards}
    check(on == {d.id for d in devices},
          f"result shards on devices {sorted(on)}, expected "
          f"{sorted(d.id for d in devices)}")
    check(sum(s.data.size for s in shards) == sh.spent.size,
          "result shards overlap (replicated, not split)")
    return {"phase": "sharded", "devices": sorted(on),
            "lanes": int(sh.spent.shape[0] * sh.spent.shape[1]),
            "n_done": float(jnp.sum(sh.n_done)),
            "spent": float(jnp.sum(sh.spent)),
            "one_device_s_informational": one_s,
            "sharded_s_informational": sh_s}


def _emit(rec):
    print(json.dumps(rec), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev['platform']})",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: {args.chips} chips requested, "
              f"{dev['count']} found", file=sys.stderr)
        return 2
    enable_compilation_cache()
    _emit({"phase": "device", **dev})
    if args.chips == 4:
        _emit(phase_sharded(jax.devices()[:4]))
    else:
        _emit(phase_kernels())
        for spec in engine_bench.SCENARIOS:
            rec = phase_engine(spec)
            check(rec["pallas_calls"] > 0,
                  f"{rec['cell']}: no tpu_custom_call in the engine")
            _emit(rec)
        _emit(phase_paper())
        _emit(phase_sweeps())
    _emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
