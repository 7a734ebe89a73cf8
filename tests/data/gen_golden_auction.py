"""Regenerate tests/data/golden_auction.json — the pinned event trace
for a small sealed-bid auction scenario (dynamic-pricing suite).

Run from the repo root against a known-good engine revision:

    PYTHONPATH=src python tests/data/gen_golden_auction.py

The golden is the batch=1 reference run (the canonical event order);
tests assert both batch=1 and the default batch reproduce it bitwise.
The scenario is sized so several K_AUCTION rounds land inside the
64-slot trace ring, interleaved with completions and broker polls.
"""
import json
import os
import sys

import jax
import numpy as np

from repro.core import des, engine, resource, simulation, types

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from _golden_farm import golden_farm  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "golden_auction.json")


def build_case():
    fleet = resource.make_fleet([2, 4], [300.0, 500.0], [2.0, 5.0],
                                [types.TIME_SHARED, types.SPACE_SHARED])
    g = golden_farm("seed6_10x2")
    sc = simulation.Scenario(pricing_model="auction", auction_period=15.0,
                             seed=8)
    params = simulation._scenario_params(fleet, 400.0, 20_000.0,
                                         types.OPT_COST, 2, sc)
    max_jobs = simulation.safe_max_jobs(g, params, fleet)
    return g, fleet, params, max_jobs


def main():
    g, fleet, params, max_jobs = build_case()
    with jax.threefry_partitionable(False):   # the recorded bid stream
        r = engine.run(g, fleet, params, 2, 4096, max_jobs=max_jobs,
                       batch=1)
    tt, kind, who = (np.asarray(x) for x in r.trace)
    m = kind >= 0
    n_auction = int((kind[m] == des.K_AUCTION).sum())
    assert n_auction >= 3, f"only {n_auction} auction rounds in trace"
    golden = {
        "_scenario": "golden_auction (2 res, task_farm seed 6, 10 jobs "
                     "x 2 users, auction_period=15, auction seed 8, "
                     "OPT_COST, batch=1)",
        "n_done": int((np.asarray(r.gridlets.status)
                       == types.DONE).sum()),
        "returned": np.asarray(r.gridlets.returned).tolist(),
        "spent": np.asarray(r.spent).tolist(),
        "term_time": np.asarray(r.term_time).tolist(),
        "n_events": int(np.asarray(r.n_events)),
        "overflow": int(np.asarray(r.overflow)),
        "trace_t": tt[m].tolist(),
        "trace_kind": kind[m].astype(int).tolist(),
        "trace_who": who[m].astype(int).tolist(),
    }
    with open(OUT, "w") as f:
        json.dump(golden, f, indent=1)
    print(f"wrote {OUT}: {int(m.sum())} trace events "
          f"({n_auction} auction rounds), n_events={golden['n_events']}")


if __name__ == "__main__":
    main()
