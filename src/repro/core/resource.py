"""Grid resource fleet (struct-of-arrays form of ``gridsim.GridResource``).

A resource = machines x PEs with a MIPS/SPEC rating, a management policy
(time-shared round-robin or space-shared FCFS/SJF), a price in G$ per
PE-time-unit, a time zone and a local (non-grid) load calendar.

The per-entity Java objects (PE, PEList, Machine, MachineList,
ResourceCharacteristics) flatten into one fleet table: for the allocation
algorithms in paper Figs 7-12 only (num_pe, mips_per_pe, policy) matter;
machine boundaries only matter for space-shared placement, which is
PE-count-equivalent under the paper's FCFS model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .types import SPACE_SHARED, TIME_SHARED, FCFS, pytree_dataclass


@pytree_dataclass
class Fleet:
    """All per-resource state. Shape [R] everywhere."""

    num_pe: jax.Array        # i32
    mips_per_pe: jax.Array   # f32: SPEC/MIPS rating of one PE
    cost_per_sec: jax.Array  # f32: G$ per PE-time-unit
    policy: jax.Array        # i32: TIME_SHARED | SPACE_SHARED
    queue_policy: jax.Array  # i32: FCFS | SJF (space-shared only)
    time_zone: jax.Array     # f32: hours offset
    base_load: jax.Array     # f32: [0,1) background (non-grid) load factor
    weekend_load: jax.Array  # f32: additional weekend load factor
    baud_rate: jax.Array     # f32: bytes / time-unit to+from this resource
    cost_per_mi: jax.Array   # f32: G$ per MI -- the broker's resource-trading
                             #      metric (Table 2), cost_per_sec / mips_per_pe

    @property
    def r(self) -> int:
        return self.num_pe.shape[0]

    @property
    def max_pe(self) -> int:
        return int(self.num_pe.max())

    def peak_rate(self) -> jax.Array:
        """Aggregate advertised MIPS per resource."""
        return self.mips_per_pe * self.num_pe.astype(jnp.float32)


def make_fleet(num_pe, mips_per_pe, cost_per_sec, policy,
               queue_policy=None, time_zone=None, base_load=None,
               weekend_load=None, baud_rate=None) -> Fleet:
    """Build a fleet on the host from per-resource values (scalars
    broadcast to every resource).

    The G$/MI price is divided here, once, in numpy: no traced division
    of it remains, so a fleet passed to ``jax.jit`` as an argument and
    one captured as a constant (which XLA folds on the host) carry the
    same bits on every backend.
    """
    num_pe = jnp.asarray(num_pe, jnp.int32)
    r = num_pe.shape[0]

    def arr(x, default, dtype=jnp.float32):
        if x is None:
            x = default
        return jnp.broadcast_to(jnp.asarray(x, dtype), (r,)).astype(dtype)

    mips_per_pe = arr(mips_per_pe, None)
    cost_per_sec = arr(cost_per_sec, None)
    return Fleet(
        num_pe=num_pe,
        mips_per_pe=mips_per_pe,
        cost_per_sec=cost_per_sec,
        policy=arr(policy, None, jnp.int32),
        queue_policy=arr(queue_policy, FCFS, jnp.int32),
        time_zone=arr(time_zone, 0.0),
        base_load=arr(base_load, 0.0),
        weekend_load=arr(weekend_load, 0.0),
        baud_rate=arr(baud_rate, 9600.0),  # GridSimTags.DEFAULT_BAUD_RATE
        cost_per_mi=jnp.asarray(np.asarray(cost_per_sec)
                                / np.asarray(mips_per_pe)),
    )


# ----------------------------------------------------------------------
# Paper Table 2: the WWG testbed fleet used in every section-5 experiment.
# (name, PEs, SPEC/MIPS rating, manager type, G$/PE-time-unit)
# ----------------------------------------------------------------------
WWG_TABLE2 = [
    ("R0", 4, 515, TIME_SHARED, 8.0),    # Compaq AlphaServer, VPAC Melbourne
    ("R1", 4, 377, TIME_SHARED, 4.0),    # Sun Ultra, AIST Tokyo
    ("R2", 4, 377, TIME_SHARED, 3.0),    # Sun Ultra, AIST Tokyo
    ("R3", 2, 377, TIME_SHARED, 3.0),    # Sun Ultra, AIST Tokyo
    ("R4", 2, 380, TIME_SHARED, 2.0),    # Intel VC820, CNR Pisa
    ("R5", 6, 410, TIME_SHARED, 5.0),    # SGI Origin 3200, ZIB Berlin
    ("R6", 16, 410, TIME_SHARED, 5.0),   # SGI Origin 3200, ZIB Berlin
    ("R7", 16, 410, SPACE_SHARED, 4.0),  # SGI Origin 3200, Charles U Prague
    ("R8", 2, 380, TIME_SHARED, 1.0),    # Intel VC820, Portsmouth UK
    ("R9", 4, 410, TIME_SHARED, 6.0),    # SGI Origin 3200, Manchester UK
    ("R10", 8, 377, TIME_SHARED, 3.0),   # Sun Ultra, ANL Chicago
]

WWG_TIME_ZONES = [10.0, 9.0, 9.0, 9.0, 1.0, 1.0, 1.0, 1.0, 0.0, 0.0, -6.0]


def wwg_fleet(baud_rate: float = 28000.0) -> Fleet:
    """The simulated WWG testbed of paper Table 2."""
    return make_fleet(
        num_pe=[x[1] for x in WWG_TABLE2],
        mips_per_pe=[float(x[2]) for x in WWG_TABLE2],
        cost_per_sec=[x[4] for x in WWG_TABLE2],
        policy=[x[3] for x in WWG_TABLE2],
        time_zone=WWG_TIME_ZONES,
        baud_rate=baud_rate,
    )


def table1_resource(policy: int) -> Fleet:
    """The 2-PE, 1-MIPS resource of paper Table 1 / Figs 9 and 12."""
    return make_fleet(num_pe=[2], mips_per_pe=1.0, cost_per_sec=1.0,
                      policy=policy, baud_rate=jnp.inf)
