"""The general traffic generator: builds a cell's inputs from its
configuration file and its traffic-mix file, and drives the entry the
mix names.

A configuration file fixes the deployment (fleet, users, gridlets and
their lengths, grid axes); a traffic file fixes how the grid is offered
to the simulator (which entry, which points).  The run's seed orders
the points and draws the sample that is checked, so every run does the
same work in another order.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gridlet, resource, simulation, types

HERE = os.path.dirname(os.path.abspath(__file__))
POLICIES = {"time_shared": types.TIME_SHARED,
            "space_shared": types.SPACE_SHARED}


def load(kind: str, name: str, root: str = HERE) -> dict:
    """A configuration (``kind="configs"``) or traffic mix
    (``kind="traffic"``) by its name."""
    with open(os.path.join(root, kind, name + ".json")) as f:
        return json.load(f)


def host_rng(seed: int):
    """The run's generator from any whole number: it orders the points
    and draws the answers that are checked."""
    return np.random.default_rng(np.random.SeedSequence(int(seed)))


def make_fleet(cfg):
    rows = cfg["fleet"]
    return resource.make_fleet(
        num_pe=[r[1] for r in rows],
        mips_per_pe=[float(r[2]) for r in rows],
        cost_per_sec=[float(r[4]) for r in rows],
        policy=[POLICIES[r[3]] for r in rows],
        time_zone=[float(r[5]) for r in rows],
        baud_rate=float(cfg["baud_rate"]))


@functools.partial(jax.jit, static_argnames=("n", "base", "spread"))
def _lengths(key, n, base, spread):
    """Task-farm lengths: ``base`` MI plus a 0..``spread`` positive
    variation, as a stratified sample (one length in each of n equal
    slices of the range) in an order drawn from the key."""
    perm = jax.random.permutation(key, n).astype(jnp.float32)
    return base * (1.0 + spread * (perm + 0.5) / n)


def make_gridlets(cfg):
    """The configuration's gridlets, made on the device from its
    ``mi_seed``: every run of a cell simulates the same work."""
    n_users, per_user = cfg["users"], cfg["gridlets_per_user"]
    n = n_users * per_user
    lengths = _lengths(jax.random.PRNGKey(cfg["mi_seed"]), n=n,
                       base=float(cfg["mi_base"]),
                       spread=float(cfg["mi_spread"]))
    user = jnp.repeat(jnp.arange(n_users, dtype=jnp.int32), per_user)
    return gridlet.make_batch(lengths, in_bytes=float(cfg["in_bytes"]),
                              out_bytes=float(cfg["out_bytes"]),
                              user=user)


def _pick(axis, n):
    if n == "all" or n >= len(axis):
        return list(axis)
    at = np.round(np.linspace(0, len(axis) - 1, n)).astype(int)
    return [axis[i] for i in at]


def max_events(cfg) -> int:
    """The superstep budget ``sweep`` derives from the grid's largest
    deadline (``4 N + 2 max(deadline) + 100 + 64``), passed explicitly
    on every call so that a cell has one program."""
    n = cfg["users"] * cfg["gridlets_per_user"]
    return int(4 * n + (2.0 * max(cfg["deadlines"]) + 100.0) / 1.0 + 64)


class Workload:
    """One cell: its inputs, its warm-up and its calls.

    ``call(i)`` returns the entry's result for the i-th call of the
    window (not yet waited for); ``lanes(result)`` lists the simulated
    answers of a call as dicts of numpy fields, and ``point_of`` their
    (deadline, budget); ``iterations(result)`` is the engine loop's
    iteration count for that call, per chip.
    """

    def __init__(self, cfg, traffic, seed: int, devices):
        self.devices = devices
        self.rng = host_rng(seed)
        self.fleet = make_fleet(cfg)
        self.g = make_gridlets(cfg)
        self.n_users = cfg["users"]
        self.entry = traffic["entry"]
        self.deadlines = _pick(cfg["deadlines"], traffic["deadlines"])
        self.budgets = _pick(cfg["budgets"], traffic["budgets"])
        self.max_events = max_events(cfg)
        self.cycle = 1          # calls that cover every point once
        if self.entry == "run_experiment":
            pts = [(d, b) for d in self.deadlines for b in self.budgets]
            self.points = [pts[i] for i in self.rng.permutation(len(pts))]
            self.cycle = len(pts)
        elif self.entry in ("sweep", "sweep_sharded"):
            self.points = [(d, b) for d in self.deadlines
                           for b in self.budgets]
        else:
            raise ValueError(f"unknown entry {self.entry!r}")

    def call(self, i: int):
        if self.entry == "run_experiment":
            return self._entry(*self.points[i % len(self.points)])
        return self._entry(self.deadlines, self.budgets)

    def _entry(self, deadlines, budgets):
        kw = dict(opt=types.OPT_COST, n_users=self.n_users,
                  max_events=self.max_events)
        if self.entry == "run_experiment":
            return simulation.run_experiment(
                self.g, self.fleet, deadline=deadlines, budget=budgets,
                **kw)
        if self.entry == "sweep":
            return simulation.sweep(self.g, self.fleet, deadlines, budgets,
                                    **kw)
        return simulation.sweep_sharded(self.g, self.fleet, deadlines,
                                        budgets, devices=self.devices, **kw)

    def warm(self):
        """Load every program the window runs: one call at the cell's
        shapes and static arguments (every call of a cell has the
        same), with deadlines and budgets of 0 so that it ends at
        once."""
        if self.entry == "run_experiment":
            jax.block_until_ready(self._entry(0.0, 0.0))
        else:
            jax.block_until_ready(self._entry(
                [0.0] * len(self.deadlines), [0.0] * len(self.budgets)))

    def lanes(self, res) -> list:
        """Per-lane numpy views of the fields that are checked."""
        fields = dict(n_done=res.n_done, spent=res.spent,
                      term_time=res.term_time, status=res.gridlets.status,
                      resource=res.gridlets.resource,
                      finish=res.gridlets.finish, n_events=res.n_events,
                      n_steps=res.n_steps, overflow=res.overflow,
                      truncated=res.truncated)
        host = {k: np.asarray(v) for k, v in fields.items()}
        if self.entry == "run_experiment":
            return [host]
        d_n, b_n = len(self.deadlines), len(self.budgets)
        return [{k: v[i, j] for k, v in host.items()}
                for i in range(d_n) for j in range(b_n)]

    def point_of(self, call_i: int, lane: int):
        if self.entry == "run_experiment":
            return self.points[call_i % len(self.points)]
        return self.points[lane]

    def n_events(self, res) -> int:
        return int(np.asarray(res.n_events).sum())

    def iterations(self, res) -> list:
        """Engine loop iterations of one call on each chip: the
        committed supersteps of a single run, or the lane loop's count
        (its longest lane) on each chip's slice of lanes."""
        steps = np.asarray(res.n_steps).reshape(-1)
        if self.entry == "run_experiment":
            return [int(steps[0])]
        if self.entry == "sweep":
            return [int(steps.max())]
        k = len(self.devices)
        per = -(-steps.size // k)
        return [int(steps[i * per:(i + 1) * per].max()) for i in range(k)
                if steps[i * per:(i + 1) * per].size]

    def host_inputs(self):
        """Lengths and users as the reference takes them."""
        return (np.asarray(self.g.length_mi, np.float32),
                np.asarray(self.g.user))
