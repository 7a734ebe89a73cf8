"""Contended-links demo: file staging over fair-share wide-area links.

Drives the engine's contention-aware network subsystem end-to-end (the
Nimrod-G concern the analytic bytes/baud model cannot express): a
3-resource grid runs a 30-job task farm whose Gridlets carry real input
and output files, first over analytic links (every transfer gets the
whole link to itself) and then over fair-share links (``net_cap``:
concurrent stagings and result returns on the same resource link split
its baud rate equally, with one phantom background flow of non-grid
traffic per link).  Contention stretches the transfer phase, so the
same broker schedule finishes later -- and a bandwidth-starved link
changes which resources are worth buying.

Also prints the physics on a minimal two-transfer example (two 128-byte
stagings over a 16 B/unit link arrive at t=16, not t=8), then asserts
the engine's identity contracts: batched == single-step on the
contended run, and infinite-baud fair-share links == the analytic path
superstep-for-superstep.

  PYTHONPATH=src python examples/network_contention.py [baud]

Expected output with the default baud 24000 (deterministic; asserted
below, and smoke-run by the CI docs job):

  two 128 B stagings over a 16 B/unit link: arrivals [16. 16.] (analytic: [8. 8.])
  ...
  analytic links:    completed 30/30  finished at t=369.1
  fair-share links:  completed 30/30  finished at t=593.4

The contended farm completes the same work later: transfer time is now
part of the simulated timeline, not a per-transfer constant.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import engine, gridlet, resource, simulation, types


def main():
    baud = float(sys.argv[1]) if len(sys.argv) > 1 else 24_000.0

    # -- the physics, minimally: two transfers halve each other -------
    tiny_fleet = resource.make_fleet([2], 1.0, 1.0, types.TIME_SHARED,
                                     baud_rate=16.0)
    tiny = gridlet.make_batch([8.0, 8.0], in_bytes=128.0)
    shared = engine.run_direct(tiny, tiny_fleet, 0, 0.0, max_events=64,
                               net_cap=2)
    alone = engine.run_direct(tiny, tiny_fleet, 0, 0.0, max_events=64)
    print("two 128 B stagings over a 16 B/unit link: arrivals "
          f"{np.asarray(shared.gridlets.start)} "
          f"(analytic: {np.asarray(alone.gridlets.start)})")
    np.testing.assert_allclose(np.asarray(shared.gridlets.start), 16.0)
    np.testing.assert_allclose(np.asarray(alone.gridlets.start), 8.0)

    # -- a broker-driven farm with real file payloads -----------------
    fleet = resource.make_fleet(
        num_pe=[4, 2, 2], mips_per_pe=[500.0, 400.0, 380.0],
        cost_per_sec=[8.0, 4.0, 2.0], policy=types.TIME_SHARED,
        baud_rate=baud)
    farm = gridlet.task_farm(jax.random.PRNGKey(7), n_jobs=30,
                             base_mi=10_000.0, in_bytes=300_000.0,
                             out_bytes=150_000.0)
    sc = simulation.Scenario(bg_flows=1.0)    # standing non-grid flow
    kw = dict(deadline=900.0, budget=12_000.0, opt=types.OPT_COST)

    analytic = simulation.run_experiment(farm, fleet, **kw, scenario=sc)
    contended = simulation.run_experiment(farm, fleet, **kw, scenario=sc,
                                          net_cap=None)   # auto-sized

    print(f"\n30-gridlet farm, 3 resources, {baud:.0f} B/unit links, "
          "300 kB in / 150 kB out per gridlet, 1 background flow")
    for name, res in (("analytic links:  ", analytic),
                      ("fair-share links:", contended)):
        print(f"  {name} completed {int(res.n_done[0])}/30  "
              f"finished at t={float(res.term_time[0]):.1f}")

    # -- identity contracts -------------------------------------------
    assert int(analytic.overflow) == 0 and int(contended.overflow) == 0
    assert not bool(contended.truncated)
    # contention can only stretch a transfer, never shrink it
    assert float(contended.term_time[0]) >= float(analytic.term_time[0])

    single = simulation.run_experiment(farm, fleet, **kw, scenario=sc,
                                       net_cap=None, batch=1)
    for f in ("n_done", "spent", "term_time", "n_events"):
        assert np.array_equal(np.asarray(getattr(single, f)),
                              np.asarray(getattr(contended, f))), f
    assert int(single.n_steps) == \
        int(contended.n_steps) + int(contended.n_spec)
    print("batched engine bit-identical to single-step on the "
          f"contended run: OK ({int(single.n_steps)} -> "
          f"{int(contended.n_steps)} iterations)")

    # infinite links: the subsystem tables nothing and the run is
    # identical to the analytic engine, superstep for superstep
    inf_fleet = resource.make_fleet(
        num_pe=[4, 2, 2], mips_per_pe=[500.0, 400.0, 380.0],
        cost_per_sec=[8.0, 4.0, 2.0], policy=types.TIME_SHARED,
        baud_rate=jnp.inf)
    a = simulation.run_experiment(farm, inf_fleet, **kw)
    b = simulation.run_experiment(farm, inf_fleet, **kw, net_cap=None)
    for f in ("n_done", "spent", "term_time", "n_events", "n_steps",
              "n_spec"):
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f
    print("infinite-baud fair-share links bit-identical to the "
          "analytic path: OK")

    if len(sys.argv) == 1:     # deterministic default (header block)
        assert int(contended.n_done[0]) == 30
        assert float(contended.term_time[0]) >= \
            float(analytic.term_time[0])


if __name__ == "__main__":
    enable_compilation_cache()
    main()
