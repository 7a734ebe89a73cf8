"""Regenerate tests/data/golden_gridlets.json -- the task-farm job
lengths the pinned goldens run on, committed as data so the goldens do
not depend on the PRNG implementation of the installed JAX.

    PYTHONPATH=src python tests/data/gen_golden_gridlets.py

The lengths are drawn with ``jax_threefry_partitionable=False``, the
PRNG the golden expectations (golden_pre_refactor.json,
golden_auction.json, golden_net_20u.json) were recorded under.  Each
entry is ``gridlet.task_farm(PRNGKey(seed), n_jobs, n_users)
.length_mi`` as float32 values.
"""
import json
import os

import jax
import numpy as np

from repro.core import gridlet

OUT = os.path.join(os.path.dirname(__file__), "golden_gridlets.json")

# name -> (PRNG seed, n_jobs per user, n_users)
FARMS = {
    "seed3_200x1": (3, 200, 1),     # golden_pre_refactor 1u_200j
    "seed3_100x20": (3, 100, 20),   # golden_pre_refactor 20u_100j, net
    "seed6_10x2": (6, 10, 2),       # golden_auction
}


def main():
    jax.config.update("jax_threefry_partitionable", False)
    out = {}
    for name, (seed, n_jobs, n_users) in FARMS.items():
        g = gridlet.task_farm(jax.random.PRNGKey(seed), n_jobs=n_jobs,
                              n_users=n_users)
        out[name] = {"n_jobs": n_jobs, "n_users": n_users,
                     "length_mi": np.asarray(g.length_mi).tolist()}
    with open(OUT, "w") as f:
        json.dump(out, f)
    print(f"wrote {OUT}: {', '.join(out)}")


if __name__ == "__main__":
    main()
