"""Failure/recovery demo: resources fail mid-run, the broker resubmits.

Drives the engine's pluggable FAILURE/RECOVERY event sources end-to-end
(the paper's "resources are dynamic" scenario): a 3-resource grid runs a
40-job task farm while every resource fails with MTBF = 150 time units
and repairs with MTTR = 15.  When a resource goes down its in-flight
Gridlets move to the FAILED state and their committed cost is refunded;
the economic broker re-plans and re-dispatches them (billing only the
new dispatch), so the farm still completes -- just later and, when the
cheap resource was down at the wrong moment, at a different cost.

Prints per-resource downtime and the resubmission count, then checks the
no-double-billing invariant: total spend == the committed cost of the
Gridlets that completed.  Both runs use the engine's default k-step
superstep batching; the failure run is additionally re-executed with
``batch=1`` to assert the speculative path is bit-for-bit identical
under dense interference (the horizon degrades, the results don't).

A third run demonstrates *planned* downtime: a maintenance window
(``reservation.maintenance`` -- sugar over the advance-reservation
source that holds every PE of a resource) takes the cheapest resource
offline for [100, 160).  Unlike a failure, nothing is killed or
refunded: admission just stops, and queued work resumes when the window
closes.

  PYTHONPATH=src python examples/failure_recovery.py [seed]

Expected output with the default seed 0 (deterministic; asserted below,
and smoke-run by the CI docs job):

  baseline (no failures):
    completed 40/40  spent 2301 G$  finished at t=528.2
  with failures:
    completed 40/40  spent 2879 G$  finished at t=555.9
    gridlets hit by failures: 12, resubmitted: 12
  with R2 maintenance [100, 160):
    completed 40/40  spent 5177 G$  finished at t=232.5
    gridlets hit by failures: 0, resubmitted: 0

Failures push the finish past the baseline's t=528.2 and the re-planned
dispatches land on costlier resources -- same completions, higher spend.
Maintenance kills nothing, but with the cheap R2 dark mid-run the
cost-optimising broker buys the expensive fast resources instead:
double the spend, half the makespan -- planned downtime trades G$ for
time where a failure trades both.
"""
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import gridlet, reservation, resource, simulation, types


def main():
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 0

    fleet = resource.make_fleet(
        num_pe=[4, 2, 2], mips_per_pe=[500.0, 400.0, 380.0],
        cost_per_sec=[8.0, 4.0, 2.0], policy=types.TIME_SHARED,
        baud_rate=jnp.inf)
    farm = gridlet.task_farm(jax.random.PRNGKey(7), n_jobs=40,
                             base_mi=10_000.0)

    baseline = simulation.run_experiment(
        farm, fleet, deadline=600.0, budget=12000.0, opt=types.OPT_COST)
    faulty = simulation.run_experiment(
        farm, fleet, deadline=600.0, budget=12000.0, opt=types.OPT_COST,
        scenario=simulation.Scenario(mtbf=150.0, mttr=15.0, seed=seed))
    # Planned downtime: the cheapest resource (R2) goes dark over
    # [100, 160) -- a maintenance window blocking all of its PEs.
    maint = simulation.run_experiment(
        farm, fleet, deadline=600.0, budget=12000.0, opt=types.OPT_COST,
        scenario=simulation.Scenario(
            reservations=reservation.maintenance(fleet.num_pe,
                                                 [(2, 100.0, 160.0)])))

    print("40-gridlet task farm, 3 resources, MTBF=150 MTTR=15 "
          f"(seed {seed})\n")
    print("resource  PEs  G$/s   downtime")
    downtime = np.asarray(faulty.downtime)
    for r in range(fleet.r):
        print(f"R{r:<8d} {int(fleet.num_pe[r]):3d} "
              f"{float(fleet.cost_per_sec[r]):5.1f} {downtime[r]:9.1f}")

    for name, res in (("baseline (no failures)", baseline),
                      ("with failures", faulty),
                      ("with R2 maintenance [100, 160)", maint)):
        print(f"\n{name}:")
        print(f"  completed {int(res.n_done[0])}/40  "
              f"spent {float(res.spent[0]):.0f} G$  "
              f"finished at t={float(res.term_time[0]):.1f}")
        print(f"  gridlets hit by failures: {int(res.n_failed)}, "
              f"resubmitted: {int(res.n_resubmits)}")

    # no double billing: spend equals committed cost of completed jobs
    status = np.asarray(faulty.gridlets.status)
    cost_done = float(np.asarray(faulty.gridlets.cost)
                      [status == types.DONE].sum())
    assert abs(float(faulty.spent[0]) - cost_done) < 1e-3 * max(cost_done,
                                                                1.0)
    # every failed gridlet was resubmitted, or (if the broker had
    # already deactivated) refunded: abandoned FAILED gridlets carry no
    # committed cost.
    assert int(faulty.n_failed) > 0
    assert np.all(np.asarray(faulty.gridlets.cost)
                  [status == types.FAILED] == 0.0)
    print("\nevery failed gridlet resubmitted or refunded: OK")

    # k-step speculation must be bit-identical to the single-step
    # engine even with failures cutting the horizon mid-run.
    single = simulation.run_experiment(
        farm, fleet, deadline=600.0, budget=12000.0, opt=types.OPT_COST,
        scenario=simulation.Scenario(mtbf=150.0, mttr=15.0, seed=seed),
        batch=1)
    for f in ("n_done", "spent", "term_time", "n_events", "n_failed",
              "n_resubmits"):
        assert np.array_equal(np.asarray(getattr(single, f)),
                              np.asarray(getattr(faulty, f))), f
    assert int(single.n_steps) == int(faulty.n_steps) + int(faulty.n_spec)
    print(f"batched engine bit-identical to single-step: OK "
          f"({int(single.n_steps)} -> {int(faulty.n_steps)} iterations)")
    # maintenance is planned downtime: nothing killed, nothing
    # refunded -- but steering the broker off the cheap resource
    # mid-run costs real G$ (it buys the fast expensive ones instead)
    assert int(maint.n_failed) == 0 and int(maint.n_resubmits) == 0
    assert int(maint.n_done[0]) == 40
    assert float(maint.spent[0]) > float(baseline.spent[0])
    if seed == 0:              # deterministic default (header block)
        assert int(faulty.n_done[0]) == 40
        assert int(faulty.n_failed) == 12 and int(faulty.n_resubmits) == 12


if __name__ == "__main__":
    enable_compilation_cache()
    main()
