"""One run of one cell: set-up, the measured window, the traced window,
the check against the reference, and the result line.

``run.py`` is the command; it calls :func:`run` after it has made sure
the cell's chips are there.  Tests call :func:`run` with
``require_tpu=False`` to rehearse a whole run on the CPU.
"""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

import jax
import numpy as np

from . import check, traffic, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SCRATCH = os.path.join(ROOT, ".gridbench_tmp")


class NoChip(RuntimeError):
    """The cell's chips are not there."""


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(spec_: dict, name: str) -> dict:
    for w in spec_["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def reader(name: str, root: str = HERE):
    """The per-layer metric reader kept in ``metrics/<name>.py``."""
    path = os.path.join(root, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "gridbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


def devices_for(chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


class CompileCounter:
    """Counts XLA compilations (persistent-cache misses) and traces.
    One per process: JAX keeps its listeners for the process's life."""
    _one = None

    def __init__(self):
        self.compiles = self.hits = self.traces = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls):
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.compiles - self.hits, self.traces


@contextlib.contextmanager
def ir_dump(path):
    """Dump every module lowered inside the block to ``path``."""
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_dump_ir_to", path)
    try:
        yield
    finally:
        jax.config.update("jax_dump_ir_to", "")


def pallas_calls(path) -> int:
    """``tpu_custom_call``s in the largest program lowered (the
    engine)."""
    best = (0, 0)
    for f in glob.glob(os.path.join(path, "*.mlir")):
        text = open(f).read()
        best = max(best, (len(text), text.count("tpu_custom_call")))
    return best[1]


def _window(w, seconds, on_call=None, calls=None):
    """Closed loop of calls for at least ``seconds``, ending on a whole
    cycle of the cell's points so that every run does the same work in
    another order (or of exactly ``calls`` calls); each call timed from
    its start to ``block_until_ready`` on its result."""
    times, results = [], []
    t_start = time.perf_counter()
    i = 0
    while True:
        t0 = time.perf_counter()
        if on_call is None:
            res = jax.block_until_ready(w.call(i))
        else:
            with on_call(i):
                res = jax.block_until_ready(w.call(i))
        t1 = time.perf_counter()
        times.append(t1 - t0)
        results.append(res)
        i += 1
        done = i == calls if calls else (t1 - t_start >= seconds
                                         and i % w.cycle == 0)
        if done:
            return times, results, t1 - t_start


def _answers(w, results, rng):
    """The answers a check may draw from, as host arrays: the distinct
    points of a points cell, or the lanes of one call (drawn from the
    seed) of a sweep cell; each ``((deadline, budget), fields)``."""
    if w.entry == "run_experiment":
        first = {}
        for i, res in enumerate(results):
            first.setdefault(w.point_of(i, 0), res)
        return [(p, w.lanes(res)[0]) for p, res in first.items()]
    ci = int(rng.integers(len(results)))
    return list(zip(w.points, w.lanes(results[ci])))


def _check(w, cfg, tr, pool, rng):
    """(answers checked, numbers, correct): a sample of ``pool`` drawn
    from the seed, always with the answer of most events, each compared
    with the reference on the same inputs."""
    longest = int(np.argmax([int(a["n_events"]) for _, a in pool]))
    rest = [j for j in rng.permutation(len(pool)) if j != longest]
    chosen = [longest] + rest[:max(int(tr["check_answers"]) - 1, 0)]
    lengths, users = w.host_inputs()
    per = []
    for j in chosen:
        (d, b), answer = pool[j]
        ref = check.reference_answer(cfg, lengths, users, d, b,
                                     w.max_events)
        per.append(check.gaps(answer, ref, d, b,
                              cfg["gridlets_per_user"]))
    numbers, ok = check.judge(per, cfg["checks"])
    return len(chosen), numbers, ok


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True, err=sys.stderr,
        root: str = HERE, spec_: dict | None = None,
        cache_dir: str = CACHE_DIR):
    """One run; returns the result line as a dict.  ``root`` holds the
    configs, traffic mixes and metric readers; ``spec_`` stands in for
    ``BENCHMARK.json``; ``cache_dir`` is the persistent compilation
    cache (a fixed directory in the checkout)."""
    sp = spec() if spec_ is None else spec_
    cell = cell_of(sp, workload)
    cfg = traffic.load("configs", cell["config"], root)
    tr = traffic.load("traffic", cell["traffic"], root)
    devices = devices_for(cell["chips"], require_tpu)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter.get()

    dump = os.path.join(SCRATCH, f"ir_{os.getpid()}")
    try:
        with ir_dump(dump):
            w = traffic.Workload(cfg, tr, seed, devices)
            w.warm()
        n_pallas = pallas_calls(dump)
    finally:
        shutil.rmtree(dump, ignore_errors=True)
    if require_tpu and n_pallas == 0:
        raise RuntimeError("the cell's program holds no tpu_custom_call")
    setup_s = time.perf_counter() - t_process
    before = counter.snapshot()

    if trace:
        trace_dir = os.path.join(SCRATCH, f"trace_{os.getpid()}")
        spans = lambda i: jax.profiler.TraceAnnotation(
            trace_reduce.CALL_SPAN, call=i)
        try:
            jax.profiler.start_trace(trace_dir)
            try:
                times, results, wall = _window(
                    w, seconds, spans, calls=int(tr["trace_calls"]))
            finally:
                jax.profiler.stop_trace()
            red = trace_reduce.reduce(trace_reduce.events_from_file(
                trace_reduce.find_xplane(trace_dir)))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        times, results, wall = _window(w, seconds)
    compiles, traces = (a - b for a, b in zip(counter.snapshot(), before))

    mem = [d.memory_stats() or {} for d in devices]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    events = [w.n_events(r) for r in results]
    iters = [w.iterations(r) for r in results]
    faults = sum(int(np.asarray(r.overflow).sum() > 0
                     or np.asarray(r.truncated).any()) for r in results)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    print(f"calls {len(times)} events {sum(events)} window_s {wall} "
          f"lanes_per_s {len(times) * len(w.points) / wall} "
          f"compiles_in_window {compiles} traces_in_window {traces} "
          f"pallas_calls {n_pallas} setup_s {setup_s}", file=err)

    if trace:
        ctx = dict(red=red, iterations=iters, cfg=cfg,
                   peaks=peaks(d0.device_kind) if require_tpu else None)
        metrics = {}
        for m in sp["per_layer"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            v = reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.mean_busy_ns(red) / 1e9
        device["window_s"] = red.window_ns / 1e9
        breakdown = breakdown_of(red)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"},
                   "events_per_s": {"value": sum(events) / wall,
                                    "unit": "events/s"}}
        for m in sp["end_to_end"]:
            if m["name"] == "experiment_p90_ms" and workload in m.get(
                    "workloads", []):
                metrics[m["name"]] = {
                    "value": float(np.percentile(times, 90)) * 1e3,
                    "unit": "ms"}

    # the reference runs on the host once the program's results are
    # fetched and its device state is freed
    rng = np.random.default_rng(traffic.host_rng(seed).integers(2 ** 63))
    pool = _answers(w, results, rng)
    del results
    n_checked, numbers, ok = _check(w, cfg, tr, pool, rng)
    numbers["compiles_in_window"] = {"value": compiles, "limit": 0}
    ok = ok and compiles == 0
    for k, v in numbers.items():
        print(f"{k} {v['value']} limit {v['limit']}", file=err)
    line = {"correct": ok, "attempted": len(times), "failed": faults,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = breakdown
    line["checked"] = {"answers": n_checked, **numbers}
    return line


def breakdown_of(red) -> dict:
    """The device ops that took most time (leaf ops by HLO name, summed
    and averaged over chips) and the longest idle gaps of the first
    chip, by what the host was doing."""
    per_op = {}
    for ops in red.chips.values():
        for o in ops:
            if trace_reduce.kernel_of(o) in trace_reduce.CONTAINERS:
                continue
            key = trace_reduce.hlo_name(o)
            per_op[key] = per_op.get(key, 0.0) + o.duration_ns
    n = max(len(red.chips), 1)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = []
    for ops in list(red.chips.values())[:1]:
        iv = sorted((o.start_ns, o.end_ns) for o in ops)
        for s, e in red.calls:
            last = s
            for a, b in iv:
                if b <= s or a >= e:
                    continue
                if a > last:
                    gaps.append(("host inside a call: dispatch, "
                                 "parameters", (a - last) / 1e9))
                last = max(last, b)
            if e > last:
                gaps.append(("host inside a call: result fetch",
                             (e - last) / 1e9))
        for (_, e0), (s1, _) in zip(red.calls, red.calls[1:]):
            gaps.append(("host between calls", (s1 - e0) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v / n / 1e9] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps[:10]]}
