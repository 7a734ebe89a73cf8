"""Quickstart: the paper's section-4.1 recipe in ~30 lines.

Creates the WWG testbed fleet (Table 2), a 200-job task-farming
application (section 5.2), runs the Nimrod-G-like economic broker with
DBC cost-optimisation (k-step superstep batching on, the engine
default), and prints the per-resource allocation -- the repeatable,
controllable experiment the paper was built for.

  PYTHONPATH=src python examples/quickstart.py [deadline] [budget]

Expected output with the default arguments (deterministic; asserted
below, and smoke-run by the CI docs job):

  fleet: 11 resources, 68 PEs, T_min=76 T_max=5555 C_min=5511 C_max=32530
  ...
  R8          2   1.0    380     38   <- cheapest G$/MI
  ...
  completed 182/200  spent 11993/12000 G$  terminated at t=548/600

The broker drains the cheap resources (R2-R4, R8) and leaves the
expensive ones idle; 18 Gridlets stay undispatched when the remaining
budget no longer covers the cheapest possible job.
"""
import sys

import jax
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import economy, gridlet, resource, simulation, types


def main():
    deadline = float(sys.argv[1]) if len(sys.argv) > 1 else 600.0
    budget = float(sys.argv[2]) if len(sys.argv) > 2 else 12000.0

    fleet = resource.wwg_fleet()
    # the expected output above was recorded with the
    # non-partitionable threefry, JAX's default PRNG before 0.5
    with jax.threefry_partitionable(False):
        farm = gridlet.task_farm(jax.random.PRNGKey(7), n_jobs=200)
    total_mi = float(farm.length_mi.sum())

    print(f"fleet: {fleet.r} resources, "
          f"{int(fleet.num_pe.sum())} PEs, "
          f"T_min={float(economy.t_min(fleet, total_mi)):.0f} "
          f"T_max={float(economy.t_max(fleet, total_mi)):.0f} "
          f"C_min={float(economy.c_min(fleet, total_mi)):.0f} "
          f"C_max={float(economy.c_max(fleet, total_mi)):.0f}")
    print(f"experiment: 200 Gridlets, deadline={deadline:.0f}, "
          f"budget={budget:.0f} G$, cost-optimisation\n")

    res = simulation.run_experiment(farm, fleet, deadline=deadline,
                                    budget=budget, opt=types.OPT_COST)

    per = np.asarray(res.per_resource_done[0], int)
    cost_mi = np.asarray(fleet.cost_per_mi)
    print("resource  PEs  G$/s   MIPS  gridlets")
    for r in range(fleet.r):
        print(f"R{r:<8d} {int(fleet.num_pe[r]):3d} "
              f"{float(fleet.cost_per_sec[r]):5.1f} "
              f"{float(fleet.mips_per_pe[r]):6.0f} {per[r]:6d}"
              + ("   <- cheapest G$/MI" if r == cost_mi.argmin() else ""))
    print(f"\ncompleted {int(res.n_done[0])}/200  "
          f"spent {float(res.spent[0]):.0f}/{budget:.0f} G$  "
          f"terminated at t={float(res.term_time[0]):.0f}/{deadline:.0f}")

    # Real smoke assertions (CI runs this file): the run is healthy and
    # the k-step batched engine actually engaged.
    assert int(res.overflow) == 0 and not bool(res.truncated)
    assert float(res.spent[0]) <= budget + 1e-3
    if len(sys.argv) == 1:     # deterministic defaults (header block)
        assert int(res.n_done[0]) == 182
        assert per[cost_mi.argmin()] == 38
        assert round(float(res.spent[0])) == 11993
        # a real workload must actually exercise the k-step batched path
        # (degenerate CLI args -- zero budget etc. -- legitimately don't)
        assert int(res.n_spec) > 0, "superstep speculation never engaged"


if __name__ == "__main__":
    enable_compilation_cache()
    main()
