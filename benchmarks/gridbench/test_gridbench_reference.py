"""The plain reference (``reference/sim.py``) against data that does not
come from today's engine -- the paper's Table 1 schedule and the
results recorded from the engine before its superstep rewrite -- and
against the engine itself at a tiny size of each configuration."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.gridbench import check, traffic  # noqa: E402
from benchmarks.gridbench.reference import sim  # noqa: E402

DATA = os.path.join(ROOT, "tests", "data")
TABLE1_FLEET = [["R0", 2, 1.0, "time_shared", 1.0, 0.0]]


def _wwg():
    return traffic.load("configs", "gridsim_wwg_1user")["fleet"]


@pytest.mark.parametrize("policy,starts,finishes", [
    ("time_shared", [0.0, 4.0, 7.0], [10.0, 14.0, 18.0]),
    ("space_shared", [0.0, 4.0, 10.0], [10.0, 12.5, 19.5]),
])
def test_table1_schedule(policy, starts, finishes):
    """Paper Table 1 / Figs 9 and 12: three gridlets of 10, 8.5 and 9.5
    MI arrive at t = 0, 4, 7 on one resource of two 1-MIPS PEs."""
    fleet = sim.Fleet([[r[0], r[1], r[2], policy, r[4]]
                       for r in TABLE1_FLEET])
    out = sim.simulate([10.0, 8.5, 9.5], [0, 0, 0], 1, fleet, -1.0, 0.0,
                       64, route=[0, 0, 0], arrive=[0.0, 4.0, 7.0])
    np.testing.assert_array_equal(out["start"], starts)
    np.testing.assert_array_equal(out["finish"], finishes)
    assert out["n_events"] == 9          # 3 arrivals, completions, returns


@pytest.mark.parametrize("farm,golden,users", [
    ("seed3_200x1", "1u_200j", 1),
    ("seed3_100x20", "20u_100j", 20),
])
def test_matches_results_recorded_before_the_superstep_engine(
        farm, golden, users):
    with open(os.path.join(DATA, "golden_gridlets.json")) as f:
        lengths = np.asarray(json.load(f)[farm]["length_mi"], np.float32)
    with open(os.path.join(DATA, "golden_pre_refactor.json")) as f:
        want = json.load(f)[golden]
    user = np.repeat(np.arange(users), lengths.size // users)
    out = sim.simulate(lengths, user, users, sim.Fleet(_wwg()), 2000.0,
                       22000.0, 100000)
    np.testing.assert_array_equal(out["n_done"], want["n_done"])
    np.testing.assert_allclose(out["spent"], want["spent"], rtol=1e-5)
    np.testing.assert_allclose(out["term_time"], want["term_time"],
                               rtol=1e-5)


def _tiny(name, users, per_user, deadlines, budgets):
    cfg = dict(traffic.load("configs", name))
    cfg.update(users=users, gridlets_per_user=per_user,
               deadlines=deadlines, budgets=budgets)
    return cfg


@pytest.mark.parametrize("cfg", [
    _tiny("gridsim_wwg_1user", 1, 40, [100.0, 600.0], [1500.0, 4000.0]),
    _tiny("gridsim_wwg_20users", 3, 30, [300.0, 1000.0], [900.0, 3000.0]),
], ids=["wwg_1user", "wwg_20users"])
def test_engine_agrees_with_reference_at_a_tiny_size(cfg):
    """The engine's lane sweep over a tiny grid of each configuration,
    lane by lane against the reference, within the cells' limits."""
    import jax
    from repro.core import simulation, types
    fleet = traffic.make_fleet(cfg)
    g = traffic.make_gridlets(cfg)
    res = simulation.sweep(g, fleet, cfg["deadlines"], cfg["budgets"],
                           opt=types.OPT_COST, n_users=cfg["users"])
    jax.block_until_ready(res)
    lengths = np.asarray(g.length_mi, np.float32)
    users = np.asarray(g.user)
    full = traffic.load("configs", "gridsim_wwg_1user")["checks"]
    for i, d in enumerate(cfg["deadlines"]):
        for j, b in enumerate(cfg["budgets"]):
            prog = dict(n_done=res.n_done[i, j], spent=res.spent[i, j],
                        term_time=res.term_time[i, j],
                        status=res.gridlets.status[i, j],
                        resource=res.gridlets.resource[i, j],
                        finish=res.gridlets.finish[i, j],
                        n_events=res.n_events[i, j],
                        overflow=res.overflow[i, j],
                        truncated=res.truncated[i, j])
            ref = check.reference_answer(cfg, lengths, users, d, b,
                                         traffic.max_events(cfg))
            gaps = check.gaps(prog, ref, d, b, cfg["gridlets_per_user"])
            assert gaps["events_gap"] == 0 and gaps["done_gap"] == 0, gaps
            assert all(gaps[k] <= full[k] for k in check.NUMBERS), gaps
