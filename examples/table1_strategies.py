"""DBC strategy comparison on the lane-batched sweep engine (the
paper's headline Nimrod-G experiment, Table-1 style).

One `engine.run_sweep_lanes` call runs every broker strategy -- cost-,
time-, cost-time- and un-optimised dispatch, each a `Scenario(policy=)`
lane -- over the same WWG task farm and deadline/budget, then a second
lane stack adds the economy axis: commodity-market repricing, sealed-bid
auction rounds and plan-ahead (cs/0203020) dispatch.  Every lane is
asserted bitwise identical to its own `engine.run(batch=1)` reference,
so the strategy axis rides the device-parallel sweep machinery without
changing a single event.

The printed table reproduces the paper's qualitative ordering:
cost-minimisation spends the least, time-minimisation finishes
earliest, and cost-time matches time's finish inside equal-cost groups
while spending like cost.

  PYTHONPATH=src python examples/table1_strategies.py

Expected output with JAX 0.9's default PRNG (deterministic; the
ordering is asserted below, and the CI docs job smoke-runs it):

  strategy x (deadline=1200, budget=30000), 40 jobs on the WWG fleet
    cost       done 40/40  t=  964.9  spent 11187
    time       done 40/40  t=  383.7  spent 25497
    cost-time  done 40/40  t=  964.9  spent 11187
    none       done 38/40  t=  920.3  spent 29955
  ordering OK: cost spends least, time finishes first
  ...
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import engine, gridlet, resource, simulation, types

STRATEGIES = (("cost", types.OPT_COST), ("time", types.OPT_TIME),
              ("cost-time", types.OPT_COST_TIME), ("none", types.OPT_NONE))

DEADLINE, BUDGET = 1200.0, 30_000.0
N_USERS, N_JOBS, MAX_EVENTS = 1, 40, 8192


def lane_params(fleet, scenarios):
    """Stack per-scenario SimParams into one lane-batched pytree."""
    ps = [simulation._scenario_params(fleet, DEADLINE, BUDGET,
                                      types.OPT_COST, N_USERS, sc)
          for sc in scenarios]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)


def run_lanes(g, fleet, scenarios):
    """One lane-batched engine call + the per-lane bitwise check."""
    p_lanes = lane_params(fleet, scenarios)
    lanes = jax.jit(lambda pp: engine.run_sweep_lanes(
        g, fleet, pp, N_USERS, MAX_EVENTS, batch=8))(p_lanes)
    for i, sc in enumerate(scenarios):
        ref = engine.run(g, fleet,
                         jax.tree_util.tree_map(lambda x: x[i], p_lanes),
                         N_USERS, MAX_EVENTS, batch=1)
        assert int(ref.n_steps) + int(ref.n_spec) < MAX_EVENTS
        for f in ("spent", "term_time", "n_events", "overflow"):
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(lanes, f)[i])), \
                f"lane {i} diverges at {f}"
        for j in range(3):
            assert np.array_equal(np.asarray(ref.trace[j]),
                                  np.asarray(lanes.trace[j][i])), \
                f"lane {i} diverges at trace[{j}]"
    return lanes


def report(lanes, names, g):
    out = {}
    for i, name in enumerate(names):
        done = int((np.asarray(lanes.gridlets.status[i])
                    == types.DONE).sum())
        t = float(lanes.term_time[i][0])
        spent = float(lanes.spent[i][0])
        print(f"    {name:<10} done {done}/{g.n}  t={t:7.1f}  "
              f"spent {spent:5.0f}")
        out[name] = (done, t, spent)
    return out


def main():
    fleet = resource.wwg_fleet()
    g = gridlet.task_farm(jax.random.PRNGKey(9), n_jobs=N_JOBS,
                          n_users=N_USERS, base_mi=50_000.0)

    # -- the strategy axis: one lane per DBC policy -------------------
    print(f"  strategy x (deadline={DEADLINE:.0f}, "
          f"budget={BUDGET:.0f}), {N_JOBS} jobs on the WWG fleet")
    scs = [simulation.Scenario(policy=opt) for _, opt in STRATEGIES]
    lanes = run_lanes(g, fleet, scs)
    rows = report(lanes, [n for n, _ in STRATEGIES], g)

    # Table-1 qualitative ordering: every DBC strategy finishes the
    # farm (the unoptimised broker may exhaust its budget first --
    # that is the point of optimising), cost-min buys the cheapest
    # grid, time-min the fastest finish.
    for name in ("cost", "time", "cost-time"):
        assert rows[name][0] == N_JOBS, f"{name} left jobs undone"
    assert rows["cost"][2] < rows["time"][2], "cost-min must spend less"
    assert rows["time"][1] < rows["cost"][1], "time-min must finish first"
    assert rows["cost-time"][2] <= rows["none"][2]
    print("  ordering OK: cost spends least, time finishes first\n")

    # -- the economy axis: pricing models + plan-ahead, same engine ---
    print("  economy axis (cost-optimising broker):")
    econ_names = ["static", "commodity", "auction", "plan-ahead"]
    econ_scs = [
        simulation.Scenario(policy=types.OPT_COST),
        simulation.Scenario(policy=types.OPT_COST,
                            pricing_model="commodity",
                            market_period=60.0, market_gain=0.5),
        simulation.Scenario(policy=types.OPT_COST,
                            pricing_model="auction",
                            auction_period=60.0, seed=12),
        simulation.Scenario(policy=types.OPT_COST, plan_ahead=True),
    ]
    econ = run_lanes(g, fleet, econ_scs)
    erows = report(econ, econ_names, g)
    assert all(done == N_JOBS for done, _, _ in erows.values())
    # Sealed-bid rounds are deterministic given the seed: replaying the
    # auction lane reproduces it bitwise.
    again = run_lanes(g, fleet, [econ_scs[2]])
    assert np.array_equal(np.asarray(again.spent[0]),
                          np.asarray(econ.spent[2]))
    print("  auction replay bitwise-deterministic: OK")
    print("  every lane bit-identical to its engine.run(batch=1) "
          "reference: OK")


if __name__ == "__main__":
    enable_compilation_cache()
    main()
