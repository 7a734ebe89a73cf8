"""Reduction of a profiler trace to the intervals the per-layer metrics
read.

Input is a list of events, each ``(plane, name, start_ns,
duration_ns)``: from an ``.xplane.pb`` through
:func:`events_from_file`, or built by hand in a test.  Device
operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO instruction (a Pallas
kernel's custom call carries the kernel's name, ``%event_scan.4 = ...
custom-call(...)``; the trace carries no ``jax.named_scope``); the
benchmark's own spans are the host events named ``gridbench.call`` (a
``jax.profiler.TraceAnnotation`` around each call, on the same clock as
the device).
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

CALL_SPAN = "gridbench.call"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    plane: str
    name: str
    start_ns: float
    duration_ns: float

    @property
    def end_ns(self):
        return self.start_ns + self.duration_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def events_from_file(path: str) -> list:
    """Device ops and the benchmark's call spans of one trace file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not device and e.name != CALL_SPAN:
                    continue
                out.append(Event(plane.name, e.name, float(e.start_ns),
                                 float(e.duration_ns)))
    return out


def union_length(intervals, lo=None, hi=None) -> float:
    """Length covered by the union of ``(start, end)`` intervals, each
    clipped to ``[lo, hi]`` where given."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def hlo_name(ev: Event) -> str:
    """The op's HLO instruction name: ``%event_scan.4 = (...)
    custom-call(...)`` gives ``event_scan.4``."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def kernel_of(ev: Event) -> str:
    """The instruction name without its number: a Pallas kernel's
    custom call carries the kernel's name (``event_scan``)."""
    head, _, tail = hlo_name(ev).rpartition(".")
    return head if tail.isdigit() else hlo_name(ev)


def is_custom_call(ev: Event) -> bool:
    return " custom-call(" in ev.name


CONTAINERS = ("while", "conditional", "call")


class Reduced(NamedTuple):
    """What the metric readers read: per chip, the ops and busy time
    inside the traced window, and the call spans."""
    window_ns: float
    calls: list            # [(start_ns, end_ns)] of every call span
    chips: dict            # plane -> [Event] device ops in the window
    busy_ns: dict          # plane -> union of op intervals in the window


def reduce(events) -> Reduced:
    calls = sorted((e.start_ns, e.end_ns) for e in events
                   if e.name == CALL_SPAN and not
                   e.plane.startswith(DEVICE_PREFIX))
    if not calls:
        raise ValueError("the trace holds no call span")
    lo, hi = calls[0][0], max(c[1] for c in calls)
    chips = {}
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.end_ns > lo \
                and e.start_ns < hi:
            chips.setdefault(e.plane, []).append(e)
    busy = {p: union_length([(e.start_ns, e.end_ns) for e in ops], lo, hi)
            for p, ops in chips.items()}
    return Reduced(hi - lo, calls, chips, busy)


def mean_busy_ns(red: Reduced) -> float:
    """Device busy time averaged over the chips that ran ops."""
    return sum(red.busy_ns.values()) / max(len(red.busy_ns), 1)


def idle_in_calls_ns(red: Reduced) -> list:
    """Per call, the time inside its span in which no chip ran an op
    (averaged over chips)."""
    out = []
    for s, e in red.calls:
        idle = [(e - s) - union_length(
            [(o.start_ns, o.end_ns) for o in ops], s, e)
            for ops in red.chips.values()]
        out.append(sum(idle) / max(len(idle), 1) if idle else e - s)
    return out


def kernel_time_ns(red: Reduced, kernel: str) -> tuple:
    """(time, count) of the calls of one Pallas kernel, per chip."""
    total, count = 0.0, 0
    for ops in red.chips.values():
        for o in ops:
            if is_custom_call(o) and kernel_of(o) == kernel:
                total += o.duration_ns
                count += 1
    n = max(len(red.chips), 1)
    return total / n, count / n
