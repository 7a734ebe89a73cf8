"""The committed task-farm inputs of the pinned goldens (see
tests/data/gen_golden_gridlets.py)."""
import json
import os

import jax.numpy as jnp
import numpy as np

from repro.core import gridlet

_PATH = os.path.join(os.path.dirname(__file__), "data",
                     "golden_gridlets.json")


def golden_farm(name: str, in_bytes: float = 0.0,
                out_bytes: float = 0.0) -> gridlet.GridletBatch:
    """``gridlet.task_farm`` as the goldens were recorded: the job
    lengths come from the data file, everything else as task_farm
    builds it."""
    with open(_PATH) as f:
        farm = json.load(f)[name]
    user = np.repeat(np.arange(farm["n_users"], dtype=np.int32),
                     farm["n_jobs"])
    return gridlet.make_batch(
        jnp.asarray(np.asarray(farm["length_mi"], np.float32)),
        in_bytes=in_bytes, out_bytes=out_bytes, user=user)
