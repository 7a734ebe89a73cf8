"""The check that decides ``correct`` fails what it must: the bfloat16
control, and whole runs with the timed path broken underneath -- a
state returned unchanged, half of the batch left out, the results of
the other chips left out, one answer altered where it is produced."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.gridbench import check, control, traffic  # noqa: E402
from benchmarks.gridbench.conftest import run_tiny, tiny_spec  # noqa: E402
from repro.core import simulation  # noqa: E402


@pytest.mark.parametrize("config", ["gridsim_wwg_1user",
                                    "gridsim_wwg_20users"])
def test_control_fails_the_check(config, root):
    cfg = traffic.load("configs", config, root[0])
    tr = traffic.load("traffic", "points", root[0])
    numbers = control.control_numbers(cfg, tr, 2 ** 32 + 9)
    failed = [k for k in check.NUMBERS if numbers[k] > cfg["checks"][k]]
    assert failed, numbers


def _unchanged(orig):
    """The engine hands back the state it was given: nothing
    dispatched, run or done."""
    def call(*a, **k):
        res = orig(*a, **k)
        g = res.gridlets
        g0 = type(g)(**{**g.__dict__,
                        "status": jnp.zeros_like(g.status),
                        "resource": jnp.full_like(g.resource, -1),
                        "finish": jnp.full_like(g.finish, jnp.inf)})
        z = jnp.zeros_like
        return res._replace(gridlets=g0, n_done=z(res.n_done),
                            spent=z(res.spent), term_time=z(res.term_time),
                            n_events=z(res.n_events))
    return call


def _half_batch(orig):
    """Half of each user's gridlets left out of the simulation; the
    answer for the whole batch is made from the half that ran (its
    gridlets stand in for the rest, its totals doubled)."""
    def call(g, *a, **k):
        n_users = k["n_users"]
        per = g.n // n_users
        keep = np.concatenate([np.arange(u * per, u * per + per // 2)
                               for u in range(n_users)])
        half = type(g)(**{f: v[keep] for f, v in g.__dict__.items()})
        res = orig(half, *a, **k)
        h = per // 2
        back = np.concatenate([np.r_[u * h:(u + 1) * h,
                                     half.n + u * h:half.n + (u + 1) * h]
                               for u in range(n_users)])
        full = type(g)(**{f: jnp.concatenate([v, v], axis=-1)[..., back]
                          for f, v in res.gridlets.__dict__.items()})
        return res._replace(gridlets=full, n_done=2 * res.n_done,
                            spent=2 * res.spent,
                            n_events=2 * res.n_events)
    return call


def _first_chip_only(orig):
    """The other chips' lanes never gathered: the first quarter of the
    lanes stands in for all of them."""
    def tile(x):
        if x.ndim < 2:
            return x
        flat = x.reshape((-1,) + x.shape[2:])
        q = max(flat.shape[0] // 4, 1)
        reps = -(-flat.shape[0] // q)
        return jnp.concatenate([flat[:q]] * reps)[:flat.shape[0]] \
            .reshape(x.shape)
    return lambda *a, **k: jax.tree_util.tree_map(tile, orig(*a, **k))


def _altered(orig):
    """One answer altered where it is produced: its budget spent and
    completions moved as if they came from another point."""
    def call(*a, **k):
        res = orig(*a, **k)
        if res.spent.ndim == 1:
            return res._replace(spent=res.spent * 1.25,
                                n_done=jnp.maximum(res.n_done - 3, 0))
        return res._replace(
            spent=res.spent.at[0, 0].multiply(1.25),
            n_done=res.n_done.at[0, 0].set(
                jnp.maximum(res.n_done[0, 0] - 3, 0)))
    return call


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "first_chip_only": _first_chip_only, "altered": _altered}
ENTRY = {w["name"]: traffic.load("traffic", w["traffic"])["entry"]
         for w in tiny_spec()["workloads"]}


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in ENTRY for f in FAULTS
    if f != "first_chip_only" or ENTRY[c] == "sweep_sharded"])
def test_broken_timed_path_is_not_correct(cell, fault, root, monkeypatch):
    monkeypatch.setattr(simulation, ENTRY[cell],
                        FAULTS[fault](getattr(simulation, ENTRY[cell])))
    line = run_tiny(cell, root, tiny_spec(), seed=3)
    assert not line["correct"], line["checked"]
