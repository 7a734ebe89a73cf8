"""The control of the check: the reference itself, put in the program's
place and computed in bfloat16, the precision below the float32 the
configurations state.  It has to fail the comparison that decides
``correct``; its numbers are the upper readings the limits are set
below (``PERF.md``).

    python3 benchmarks/gridbench/control.py --workload wwg_1user.points \
        --seeds 1 2 3

Prints one JSON line per seed with each number of the check.  It runs on
the host alone (numpy), so its numbers are the same on any machine.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

from benchmarks.gridbench import check, harness, traffic  # noqa: E402


def control_numbers(cfg, tr, seed: int):
    """The check's numbers with the bfloat16 reference as the answers,
    over the sample a run of the cell compares: the answer of most
    events and the rest drawn from the seed, on the run's inputs."""
    import ml_dtypes
    rng = np.random.default_rng(traffic.host_rng(seed).integers(2 ** 63))
    g = traffic.make_gridlets(cfg)
    lengths = np.asarray(g.length_mi, np.float32)
    users = np.asarray(g.user)
    pts = [(d, b) for d in traffic._pick(cfg["deadlines"], tr["deadlines"])
           for b in traffic._pick(cfg["budgets"], tr["budgets"])]
    steps = traffic.max_events(cfg)
    refs = [check.reference_answer(cfg, lengths, users, d, b, steps)
            for d, b in pts]
    longest = int(np.argmax([r["n_events"] for r in refs]))
    rest = [j for j in rng.permutation(len(pts)) if j != longest]
    chosen = [longest] + rest[:max(int(tr["check_answers"]) - 1, 0)]
    per = []
    for j in chosen:
        d, b = pts[j]
        low = check.reference_answer(cfg, lengths, users, d, b, steps,
                                     dtype=ml_dtypes.bfloat16)
        low["overflow"], low["truncated"] = 0, False
        per.append(check.gaps(low, refs[j], d, b,
                              cfg["gridlets_per_user"]))
    return {k: max(a[k] for a in per) for k in check.NUMBERS}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    cell = harness.cell_of(harness.spec(), a.workload)
    cfg = traffic.load("configs", cell["config"])
    tr = traffic.load("traffic", cell["traffic"])
    for s in a.seeds:
        print(json.dumps({"workload": a.workload, "seed": s,
                          **control_numbers(cfg, tr, s)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
