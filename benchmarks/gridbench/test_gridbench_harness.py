"""CPU rehearsal of whole runs: every configuration and traffic mix at a
tiny size through the harness, the harness finding files added by name,
and the command refusing to run without a TPU."""
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.gridbench import check, harness, traffic  # noqa: E402
from benchmarks.gridbench.conftest import (CELLS, run_tiny,  # noqa: E402
                                           tiny_root, tiny_spec)

@pytest.mark.parametrize("name", CELLS)
def test_every_cell_runs_tiny_on_cpu(name, root):
    line = run_tiny(name, root, tiny_spec())
    assert line["correct"], line["checked"]
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "checked"}
    assert list(line)[-1] == "checked"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["metrics"]["events_per_s"]["value"] > 0
    assert line["checked"]["compiles_in_window"]["value"] == 0
    assert set(check.NUMBERS) <= set(line["checked"])
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reads_per_layer_metrics(root):
    line = run_tiny("wwg_1user.points", root, tiny_spec(), trace=True)
    assert line["correct"]
    # the CPU has no device plane: only the host-side readers answer
    assert "loop.iters_per_call" in line["metrics"]
    assert "event_scan.share" not in line["metrics"]
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_every_seed_runs_the_same_work_in_another_order(root):
    cfg = traffic.load("configs", "gridsim_wwg_1user", root[0])
    tr = traffic.load("traffic", "points", root[0])
    import jax
    a = traffic.Workload(cfg, tr, 2 ** 31 + 3, jax.devices()[:1])
    b = traffic.Workload(cfg, tr, 2 ** 31 + 3, jax.devices()[:1])
    c = traffic.Workload(cfg, tr, 11, jax.devices()[:1])
    la, lb, lc = (w.host_inputs()[0] for w in (a, b, c))
    assert (la == lb).all() and (la == lc).all()
    # the gridlets are a stratified sample of the 0-10% spread
    n = la.size
    assert sorted(np.floor((la / cfg["mi_base"] - 1) / cfg["mi_spread"]
                           * n + 1e-3)) == list(range(n))
    assert a.points == b.points and a.points != c.points
    assert sorted(a.points) == sorted(c.points)


def test_files_added_by_name_are_found(tmp_path, root):
    """A configuration, a traffic mix and a per-layer metric, each added
    as a new file, run without any existing file edited."""
    root = (tiny_root(tmp_path), root[1])
    cfg = json.loads(open(os.path.join(
        root[0], "configs", "gridsim_wwg_1user.json")).read())
    cfg.update(name="added_cfg", gridlets_per_user=12)
    with open(os.path.join(root[0], "configs", "added_cfg.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root[0], "traffic", "added_mix.json"),
              "w") as f:
        json.dump({"entry": "run_experiment", "deadlines": 1,
                   "budgets": 2, "check_answers": 2, "trace_calls": 1},
                  f)
    with open(os.path.join(root[0], "metrics", "added.calls.py"),
              "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx['red'].calls))\n")
    sp = tiny_spec()
    sp["configs"].append({"name": "added_cfg"})
    sp["workloads"].append({"name": "added.cell", "config": "added_cfg",
                            "traffic": "added_mix", "chips": 1})
    sp["per_layer"].append({"name": "added.calls", "unit": "calls",
                            "workloads": ["added.cell"]})
    line = run_tiny("added.cell", root, sp, trace=True)
    assert line["correct"]
    assert line["metrics"]["added.calls"]["value"] == line["attempted"]


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "wwg_1user.points", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_exits_nonzero_outside_a_checkout(tmp_path):
    """With only BENCHMARK.json and the benchmark's own files, and no
    program beside them, the command fails and prints no result."""
    bench = tmp_path / "benchmarks" / "gridbench"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload",
         "wwg_1user.points", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
