"""Telemetry demo: per-resource utilisation curves from the metrics ring.

The paper's headline deliverables are *time series* -- Figs 9/12 plot
per-resource utilisation and spend over the run -- but the engine's
result is end-of-run scalars.  This demo drives a contended 20-user
farm with the speculation-safe telemetry ring enabled
(``run_experiment(..., telemetry=cap)``), exports the ring as a
structured JSONL event trace plus a Chrome ``trace_event`` file
(loadable in Perfetto / chrome://tracing), and prints the paper-style
time-weighted per-resource utilisation figures.

Then it *audits the ring against the engine's own counters* -- the
telemetry series is not decorative, it must integrate back to the
simulation's ground truth:

* the per-row event counts sum to ``n_events``;
* the last spend sample equals the engine's final committed spend;
* the utilisation series, left-Riemann-integrated as
  ``sum_r min(running_r, P_r) * MIPS_r dt``, recovers the total MI the
  farm actually executed (the engine advances work at constant Fig 8
  rates between events, so the piecewise-constant integral is exact on
  this load-free, failure-free fleet).

  PYTHONPATH=src python examples/utilisation_trace.py [out_dir]

Deterministic; asserted below and smoke-run by the CI docs job (which
uploads the exported trace as an Actions artifact).
"""
import os
import sys

import jax
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import gridlet, resource, simulation, telemetry, types


def main():
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/telemetry_trace"
    os.makedirs(out_dir, exist_ok=True)

    # A deliberately contended grid: 20 users x 10 jobs over 3 small
    # time-shared resources, so queues form and utilisation saturates.
    # Load-free fleet (no calendar load, no failures, analytic links):
    # between events every resource executes exactly
    # min(running, P) * MIPS instructions per time unit, which is what
    # makes the utilisation integral below exact rather than approximate.
    fleet = resource.make_fleet(
        num_pe=[4, 2, 2], mips_per_pe=[200.0, 150.0, 100.0],
        cost_per_sec=[9.0, 5.0, 3.0], policy=types.TIME_SHARED)
    n_users = 20
    farm = gridlet.task_farm(jax.random.PRNGKey(7), n_jobs=10,
                             n_users=n_users, base_mi=2000.0)
    res = simulation.run_experiment(
        farm, fleet, deadline=600.0, budget=1e6, opt=types.OPT_COST,
        n_users=n_users, telemetry=2048)

    tel = res.telemetry
    assert tel is not None and not telemetry.truncated(tel), \
        "ring truncated: raise the telemetry capacity"
    rows = telemetry.rows(tel)
    n_done = int(np.asarray(res.n_done).sum())
    print(f"completed {n_done}/{farm.n} gridlets in "
          f"{len(rows)} recorded supersteps")

    # -- export: structured JSONL + Chrome trace_event ----------------
    jsonl = os.path.join(out_dir, "trace.jsonl")
    chrome = os.path.join(out_dir, "trace_chrome.json")
    print(f"wrote {telemetry.to_jsonl(tel, jsonl)} rows to {jsonl}")
    print(f"wrote {telemetry.to_chrome_trace(tel, chrome)} trace events "
          f"to {chrome}")

    # -- the paper's utilisation figures ------------------------------
    t, util = telemetry.utilisation(tel)
    dt = np.diff(t)
    mean_util = (util[:-1] * dt[:, None]).sum(0) / (t[-1] - t[0])
    for r in range(fleet.r):
        bar = "#" * int(round(40 * mean_util[r]))
        print(f"  resource {r} ({int(fleet.num_pe[r])} PE @ "
              f"{float(fleet.mips_per_pe[r]):.0f} MIPS): "
              f"{100 * mean_util[r]:5.1f}% |{bar}")

    # -- audit the ring against the engine's own counters -------------
    assert sum(r["events"] for r in rows) == int(np.asarray(res.n_events))
    np.testing.assert_allclose(rows[-1]["spent"],
                               float(np.asarray(res.spent).sum()),
                               rtol=1e-6)
    # Utilisation integrates to executed MI: sum_r util_r * P_r * MIPS_r
    # over each inter-sample interval == total MI of completed work.
    npe = np.asarray(fleet.num_pe, np.float64)
    mips = np.asarray(fleet.mips_per_pe, np.float64)
    mi_rate = (util[:-1].astype(np.float64) * npe * mips).sum(1)
    mi_integral = float((mi_rate * dt).sum())
    done = np.asarray(res.gridlets.status) == types.DONE
    mi_done = float(np.asarray(res.gridlets.length_mi,
                               np.float64)[done].sum())
    print(f"utilisation integral: {mi_integral:.1f} MI "
          f"(engine executed {mi_done:.1f} MI)")
    np.testing.assert_allclose(mi_integral, mi_done, rtol=1e-3)
    assert n_done == farm.n, "farm did not finish: tighten budget/deadline consistently"
    print("OK: trace integrates to the engine's counters")


if __name__ == "__main__":
    enable_compilation_cache()
    main()
