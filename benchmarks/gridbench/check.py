"""The comparison that decides ``correct``: the simulator's answers from
the measured window against the plain reference (``reference/sim.py``)
on the same inputs.

Each answer is one experiment: one (deadline, budget) point, from a
single run or from one lane of a sweep.  The numbers compared are the
largest, over the answers checked, of:

* ``done_gap``: gridlets completed, per user, as a share of the user's
  gridlets;
* ``spent_gap``: budget spent, per user, as a share of the budget;
* ``term_gap``: the broker's termination time as a share of the
  deadline, the median over users (one user's broker can stop at a
  budget knife-edge -- spent plus its cheapest purchase against the
  budget -- that a last-bit difference in spent tips, and then ends
  up to a tenth of the deadline apart; see ``PERF.md``);
* ``gridlet_gap``: the share of gridlets whose status or resource
  differs, or whose finish time differs by more than a ten-thousandth
  of the deadline;
* ``events_gap``: simulated events, as a share of the reference's;
* ``faults``: answers that overflowed the job-slot table or were cut at
  ``max_events`` (an exact comparison: its limit is 0).

The limits are the configuration's (``checks`` in its file), each set
between the readings of sound runs and of the control; ``PERF.md``
gives the readings.
"""
from __future__ import annotations

import numpy as np

from .reference import sim

NUMBERS = ("done_gap", "spent_gap", "term_gap", "gridlet_gap",
           "events_gap", "faults")


def gaps(prog: dict, ref: dict, deadline: float, budget: float,
         per_user: int) -> dict:
    """The numbers of one answer."""
    fin_p = np.asarray(prog["finish"], np.float64)
    fin_r = np.asarray(ref["finish"], np.float64)
    both = np.isfinite(fin_p) & np.isfinite(fin_r)
    moved = np.abs(np.where(both, fin_p, 0.0) - np.where(both, fin_r, 0.0)) \
        > 1e-4 * deadline
    moved |= np.isfinite(fin_p) != np.isfinite(fin_r)
    moved |= np.asarray(prog["status"]) != ref["status"]
    moved |= np.asarray(prog["resource"]) != ref["resource"]
    rel = lambda a, b, s: float(np.max(np.abs(
        np.asarray(a, np.float64) - np.asarray(b, np.float64))) / s)
    return dict(
        done_gap=rel(prog["n_done"], ref["n_done"], per_user),
        spent_gap=rel(prog["spent"], ref["spent"], budget),
        term_gap=float(np.median(np.abs(
            np.asarray(prog["term_time"], np.float64)
            - np.asarray(ref["term_time"], np.float64)))) / deadline,
        gridlet_gap=float(moved.mean()),
        events_gap=abs(int(prog["n_events"]) - ref["n_events"])
        / max(ref["n_events"], 1),
        faults=float(int(prog["overflow"]) > 0 or bool(prog["truncated"])),
    )


def reference_answer(cfg, lengths, users, deadline, budget, max_steps,
                     dtype=np.float32) -> dict:
    """The reference's answer at one point, in ``dtype``."""
    return sim.simulate(
        lengths, users, cfg["users"], sim.Fleet(cfg["fleet"], dtype),
        deadline, budget, max_steps, dtype=dtype,
        max_gridlet_per_pe=cfg["max_gridlet_per_pe"],
        min_period=cfg["poll_min_period"], frac=cfg["poll_frac"])


def judge(per_answer: list, limits: dict):
    """(numbers, correct): each number the largest over the answers,
    beside its limit.  An empty list of answers is not correct."""
    numbers = {k: max((a[k] for a in per_answer), default=float("inf"))
               for k in NUMBERS}
    ok = bool(per_answer) and all(numbers[k] <= limits[k]
                                  for k in NUMBERS)
    return ({k: {"value": numbers[k], "limit": limits[k]}
             for k in NUMBERS}, ok)
