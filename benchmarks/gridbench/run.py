"""GridSim's chip benchmark: one run of one cell.

    python3 benchmarks/gridbench/run.py --workload wwg_20users.points \
        --seed 7 --seconds 30 --trace 0

Cells, metrics and bounds are in ``BENCHMARK.json`` at the root of the
checkout.  Prints the result as one JSON object on the last line of
standard output, and each number the check compared beside its limit as
the last lines of standard error.  Exits 3, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmarks.gridbench import harness
    try:
        line = harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                           T_PROCESS)
    except harness.NoChip as e:
        print(f"gridbench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
