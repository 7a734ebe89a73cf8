"""The integration demo: GridSim brokering THIS repo's own workloads.

Each assigned (arch x shape) dry-run cell becomes a Gridlet priced from
its roofline analysis (MODEL_FLOPS per step x a step budget); the fleet
is a heterogeneous set of TPU pods (different generations = different
FLOP/s "MIPS" ratings, different $/chip-hour = G$ rates, preemptible
pools = time-shared, reserved capacity = space-shared).  The DBC broker
then answers the capacity-planning question the paper was written for:
*which pods should each job lease under a deadline and a budget?* --
repeatably, without touching the real cluster.

  PYTHONPATH=src python examples/cluster_scheduling.py \
      [--deadline-hours 24] [--budget 50000]
"""
import argparse
import glob
import json
import os

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.core import gridlet, resource, simulation, types

HERE = os.path.dirname(os.path.abspath(__file__))
DRYRUN = os.path.join(HERE, "..", "benchmarks", "artifacts", "dryrun",
                      "pod16x16")

# A heterogeneous TPU fleet: (name, pods, chips/pod "PEs", peak TFLOP/s
# per chip -> "MIPS", $/chip-hour -> G$/PE-time-unit, policy)
TPU_FLEET = [
    ("v5e-reserved", 4, 256, 197.0, 1.2, types.SPACE_SHARED),
    ("v5e-preempt", 8, 256, 197.0, 0.5, types.TIME_SHARED),
    ("v4-reserved", 2, 256, 275.0, 3.2, types.SPACE_SHARED),
    ("v5p-reserved", 2, 448, 459.0, 4.2, types.SPACE_SHARED),
    ("v5p-preempt", 2, 448, 459.0, 1.7, types.TIME_SHARED),
]
STEPS_PER_JOB = 1000.0   # price each cell as a 1000-step run


def load_jobs():
    jobs = []
    for path in sorted(glob.glob(os.path.join(DRYRUN, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if not rec.get("ok") or rec.get("skipped"):
            continue
        kind = rec["kind"]
        tokens = rec["global_batch"] * (rec["seq_len"]
                                        if kind != "decode" else 1)
        mult = {"train": 6.0, "prefill": 2.0, "decode": 2.0}[kind]
        tflop = mult * rec["params_active"] * tokens * STEPS_PER_JOB / 1e12
        jobs.append((f"{rec['arch']}/{rec['shape']}", tflop))
    if not jobs:  # dry-run artifacts not built yet: analytic fallback
        from repro import configs
        from repro.models import count_params
        for arch in configs.names():
            cfg = configs.get(arch)
            total, active = count_params(cfg)
            for shape, spec in configs.SHAPES.items():
                if shape == "long_500k":
                    continue
                tokens = spec["global_batch"] * (
                    spec["seq_len"] if spec["kind"] != "decode" else 1)
                mult = 6.0 if spec["kind"] == "train" else 2.0
                jobs.append((f"{arch}/{shape}",
                             mult * active * tokens * STEPS_PER_JOB
                             / 1e12))
    return jobs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--deadline-hours", type=float, default=24.0)
    ap.add_argument("--budget", type=float, default=50_000.0)
    ap.add_argument("--opt", default="cost",
                    choices=["cost", "time", "cost_time"])
    args = ap.parse_args()

    # fleet: one resource per zone; PE = one POD (jobs gang-schedule a
    # whole pod, the dry-run's mesh unit), "MIPS" = pod TFLOP/s, so the
    # simulation time unit is the SECOND; price $/chip-hour -> G$ per
    # pod-second.  Time-shared zones model preemptible pools (jobs share
    # pods), space-shared zones model reserved capacity (dedicated pod,
    # FCFS queue).
    names, num_pe, mips, cost, policy = [], [], [], [], []
    for name, pods, chips, tf, price, pol in TPU_FLEET:
        names.append(name)
        num_pe.append(pods)
        mips.append(tf * chips)
        cost.append(price * chips / 3600.0)
        policy.append(pol)
    fleet = resource.make_fleet(num_pe, mips, cost, policy)

    jobs = load_jobs()
    # Gridlet "MI" = TFLOPs of work (rating TFLOP/s x seconds).
    lengths = jnp.asarray([t for _, t in jobs], jnp.float32)
    farm = gridlet.make_batch(lengths)
    opt = {"cost": types.OPT_COST, "time": types.OPT_TIME,
           "cost_time": types.OPT_COST_TIME}[args.opt]
    res = simulation.run_experiment(
        farm, fleet, deadline=args.deadline_hours * 3600.0,
        budget=args.budget, opt=opt)

    print(f"{len(jobs)} jobs (1000 steps each), "
          f"deadline {args.deadline_hours}h, budget ${args.budget:.0f}, "
          f"{args.opt}-optimisation\n")
    status = np.asarray(res.gridlets.status)
    res_idx = np.asarray(res.gridlets.resource)
    done = status == types.DONE
    per_pod = {}
    for j, (name, tflop) in enumerate(jobs):
        pod = names[res_idx[j]] if res_idx[j] >= 0 else "-"
        per_pod.setdefault(pod, []).append(name)
    for pod in sorted(per_pod):
        if pod == "-":
            continue
        jobs_here = per_pod[pod]
        print(f"{pod:16s} {len(jobs_here):3d} jobs  "
              f"e.g. {', '.join(jobs_here[:3])}")
    unsched = per_pod.get("-", [])
    print(f"\nscheduled {int(done.sum())}/{len(jobs)} jobs "
          f"({len(unsched)} unscheduled), spent "
          f"${float(res.spent[0]):.0f} of ${args.budget:.0f}, "
          f"makespan {float(res.term_time[0]) / 3600.0:.1f}h of "
          f"{args.deadline_hours:.1f}h")
    if args.deadline_hours > 2.0:
        print("\n(tip: rerun with --deadline-hours 1 to watch the "
              "broker lease the expensive reserved v4/v5p pods)")


if __name__ == "__main__":
    enable_compilation_cache()
    main()
