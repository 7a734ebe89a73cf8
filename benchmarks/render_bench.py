"""Render ``BENCH_engine.json`` as a GitHub-flavoured markdown table.

Used by CI to surface the engine perf trajectory in the Actions job
summary (``$GITHUB_STEP_SUMMARY``) so events/sec or batching regressions
are visible directly in the PR checks:

  # committed artifact only
  python benchmarks/render_bench.py benchmarks/artifacts/BENCH_engine.json

  # fresh run vs the committed artifact (delta columns)
  python benchmarks/render_bench.py fresh.json --baseline committed.json

Pure stdlib; schema documented in docs/PERFORMANCE.md.
"""
from __future__ import annotations

import argparse
import json


def _fmt(v, nd=0):
    if v is None:
        return "--"
    return f"{v:.{nd}f}"


def _delta(new, old):
    """Signed percentage delta; positive = new is larger."""
    if new is None or old in (None, 0):
        return "--"
    pct = 100.0 * (new - old) / old
    return f"{pct:+.1f}%"


def render(report: dict, baseline: dict | None = None) -> str:
    cols = ["scenario", "events/sec", "compile s", "while-loop iters",
            "events/superstep", "events", "identical", "telemetry",
            "AI FLOP/B", "% roofline"]
    if baseline is not None:
        cols += ["Δ events/sec", "Δ events/superstep"]
    lines = ["| " + " | ".join(cols) + " |",
             "|" + "---|" * len(cols)]
    for name, cell in sorted(report.items()):
        if name.startswith("_"):
            continue            # microbench sections rendered below
        eps = cell.get("events_per_sec")
        epb = cell.get("events_per_superstep")
        ident = cell.get("batched_identical",
                         cell.get("result_identical"))
        tel = cell.get("telemetry_identical")
        pct = cell.get("pct_of_roofline")
        row = [name, _fmt(eps), _fmt(cell.get("compile_s"), 1),
               _fmt(cell.get("supersteps")),
               _fmt(epb, 2), _fmt(cell.get("events")),
               "--" if ident is None else ("yes" if ident else "**NO**"),
               "--" if tel is None else ("yes" if tel else "**NO**"),
               _fmt(cell.get("arith_intensity"), 2),
               "--" if pct is None else
               f"{pct:.2g} ({cell.get('roofline_bound', '?')}-bound)"]
        if baseline is not None:
            base = baseline.get(name, {})
            row += [_delta(eps, base.get("events_per_sec")),
                    _delta(epb, base.get("events_per_superstep"))]
        lines.append("| " + " | ".join(row) + " |")
    if baseline is not None:
        lines.append("")
        lines.append("Δ columns compare against the committed artifact "
                     "(wall-clock varies with runner load; "
                     "events/superstep is deterministic).")
    rc = report.get("_rank_crossover")
    if rc:
        lines += ["", "#### In-kernel rank crossover (us per call, "
                  "[8, J] rows, XLA CPU; crossover constant J = "
                  f"{rc.get('crossover_j')})", ""]
        lines += ["| J | pairwise O(J^2) | bitonic O(J log^2 J) | "
                  "lexsort O(J log J) |", "|---|---|---|---|"]
        for k, v in sorted(rc.items(),
                           key=lambda kv: (len(kv[0]), kv[0])):
            if not k.startswith("j"):
                continue
            lines.append(
                f"| {k[1:]} | {_fmt(v.get('pairwise_o_j2'), 1)} | "
                f"{_fmt(v.get('bitonic_o_jlog2j'), 1)} | "
                f"{_fmt(v.get('lexsort_o_jlogj'), 1)} |")
    sb = report.get("_sweep_bench")
    if sb:
        lines += ["", f"#### Sweep engine ({sb.get('grid', 'grid')})",
                  "", "| path | steady wall s | compile s | supersteps |",
                  "|---|---|---|---|"]
        lines.append(
            f"| reference (batch=1, conds->selects) | "
            f"{_fmt(sb.get('wall_s_ref'), 2)} | "
            f"{_fmt(sb.get('compile_s_ref'), 1)} | "
            f"{_fmt(sb.get('supersteps_ref'))} |")
        lines.append(
            f"| select-free sweep (batch={sb.get('batch')}) | "
            f"{_fmt(sb.get('wall_s_sweep'), 2)} | "
            f"{_fmt(sb.get('compile_s_sweep'), 1)} | "
            f"{_fmt(sb.get('supersteps_sweep'))} |")
        lines += ["",
                  f"speedup **{sb.get('batch_speedup', 0):.2f}x** | "
                  f"bitwise identical: "
                  f"{'yes' if sb.get('sweep_identical') else '**NO**'} | "
                  f"sharded identical: "
                  f"{'yes' if sb.get('sharded_identical') else '**NO**'}"]
        if "batch_speedup_paper_polls" in sb:
            ident_p = sb.get("sweep_identical_paper_polls")
            lines += ["",
                      "paper-default poll rate (1 s re-poll floor): "
                      f"**{sb['batch_speedup_paper_polls']:.2f}x** | "
                      "bitwise identical: "
                      f"{'yes' if ident_p else '**NO**'}"]
        ds = sb.get("device_scaling") or {}
        if "device_speedup" in ds:
            lines += ["", "#### Device scaling (sweep_sharded, "
                      "heterogeneous-run-length lanes)",
                      "", "| devices | steady wall s | compile s |",
                      "|---|---|---|"]
            for key in ("dev1", "dev2"):
                cell = ds.get(key, {})
                lines.append(f"| {cell.get('devices', key[3:])} | "
                             f"{_fmt(cell.get('wall_s'), 2)} | "
                             f"{_fmt(cell.get('compile_s'), 1)} |")
            lines += ["",
                      f"2-device speedup "
                      f"**{ds['device_speedup']:.2f}x** | identical "
                      "across device counts: "
                      f"{'yes' if ds.get('device_identical') else '**NO**'}"]
    return "\n".join(lines)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("artifact", help="BENCH_engine.json to render")
    p.add_argument("--baseline", default=None,
                   help="optional baseline BENCH_engine.json for deltas")
    p.add_argument("--title", default="Engine throughput "
                   "(benchmarks/artifacts/BENCH_engine.json)")
    args = p.parse_args()
    report = json.load(open(args.artifact))
    baseline = json.load(open(args.baseline)) if args.baseline else None
    print(f"### {args.title}\n")
    print(render(report, baseline))


if __name__ == "__main__":
    main()
