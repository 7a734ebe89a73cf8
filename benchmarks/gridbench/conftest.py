"""Shared fixtures of the benchmark's tests: a copy of the benchmark's
files with every configuration cut to a tiny size, and a whole run of a
cell on it."""
import copy
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.gridbench import harness  # noqa: E402

TINY = {"gridsim_wwg_1user": dict(users=1, gridlets_per_user=24,
                                  deadlines=[100.0, 400.0],
                                  budgets=[1000.0, 3000.0]),
        "gridsim_wwg_20users": dict(users=3, gridlets_per_user=16,
                                    deadlines=[200.0, 600.0],
                                    budgets=[800.0, 2500.0])}


def tiny_root(tmp_path):
    """A copy of the benchmark's files with every configuration cut to a
    tiny size (same fleet, broker and limits)."""
    root = tmp_path / "gridbench"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*"))
    for name, cut in TINY.items():
        path = root / "configs" / (name + ".json")
        cfg = json.loads(path.read_text())
        cfg.update(cut)
        path.write_text(json.dumps(cfg))
    return str(root)


# The four-chip cell, rehearsed on one CPU device (added to the list if
# BENCHMARK.json should not hold it).
SHARDED = {"name": "wwg_1user.grid_4chip", "config": "gridsim_wwg_1user",
           "traffic": "grid_sharded", "chips": 1}
CELLS = [w["name"] for w in harness.spec()["workloads"]]
CELLS += [SHARDED["name"]] if SHARDED["name"] not in CELLS else []


def tiny_spec():
    """BENCHMARK.json with every cell on one device, and the sharded
    cell added."""
    sp = copy.deepcopy(harness.spec())
    for w in sp["workloads"]:
        w["chips"] = 1
    if SHARDED["name"] not in {w["name"] for w in sp["workloads"]}:
        sp["workloads"].append(dict(SHARDED))
    return sp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny copy, with a persistent compilation cache of its own
    that is switched off again when the module's tests end."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    base = tmp_path_factory.mktemp("tiny")
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    yield tiny_root(base), str(base / "jax_cache")
    jax.config.update("jax_compilation_cache_dir", old[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old[1])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", old[2])
    compilation_cache.reset_cache()


def run_tiny(name, root, spec_, trace=False, seed=2 ** 33 + 5):
    root, cache = root
    return harness.run(name, seed, 0.2, trace, 0.0, require_tpu=False,
                       root=root, spec_=spec_, cache_dir=cache)


