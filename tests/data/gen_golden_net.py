"""Regenerate tests/data/golden_net_20u.json — the pinned event trace
for the contended ``engine_20u_100j_net`` BENCH row.

Run from the repo root against a known-good engine revision:

    PYTHONPATH=src python tests/data/gen_golden_net.py

The golden is the batch=1 reference run (the canonical event order);
tests assert both batch=1 and the default batch reproduce it bitwise.
"""
import json
import os
import sys

import numpy as np

from repro.core import engine, resource, simulation, types

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from _golden_farm import golden_farm  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "golden_net_20u.json")


def main():
    fleet = resource.wwg_fleet()
    g = golden_farm("seed3_100x20", in_bytes=200_000.0,
                    out_bytes=100_000.0)
    sc = simulation.Scenario(baud_rate=28_000.0, bg_flows=1.0)
    params = simulation._scenario_params(fleet, 2000.0, 22000.0,
                                         types.OPT_COST, 20, sc)
    net_cap = simulation.safe_net_cap(g, params, fleet, 20)
    max_jobs = simulation.safe_max_jobs(g, params, fleet)
    r = engine.run(g, fleet, params, 20, 16384, max_jobs=max_jobs,
                   batch=1, net_cap=net_cap)
    tt, kind, who = (np.asarray(x) for x in r.trace)
    m = kind >= 0
    golden = {
        "_scenario": "engine_20u_100j_net (wwg_fleet, task_farm seed 3, "
                     "baud=28000, bg=1, in=200k out=100k, batch=1)",
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
    print(f"wrote {OUT}: {int(m.sum())} trace events, "
          f"n_events={golden['n_events']}")


if __name__ == "__main__":
    main()
