"""Bytes and operations of the kernels the engine calls, from their
shapes alone (no property of any implementation), for roofline shares.
"""
from __future__ import annotations

BLOCK_R = 8      # the engine pads the resource axis to a multiple of 8


def job_slots(cfg) -> int:
    """Job-slot width J of the engine's [R_pad, J] table: every user
    stages at most 2 jobs per PE on the widest resource, capped at the
    number of gridlets."""
    n = cfg["users"] * cfg["gridlets_per_user"]
    widest = max(r[1] for r in cfg["fleet"])
    return min(n, cfg["users"] * cfg["max_gridlet_per_pe"] * widest)


def event_scan_bytes(cfg) -> int:
    """Least HBM traffic of one ``event_scan`` call at ``[R_pad, J]``:
    remaining and tie-break tables read, rate and rank tables written
    (f32), five per-row inputs read (rate, PEs, policy, blocked PEs, up
    mask) and three per-row outputs written (earliest forecast, its
    column, occupancy), each once."""
    r_pad = -(-len(cfg["fleet"]) // BLOCK_R) * BLOCK_R
    j = job_slots(cfg)
    return 4 * (4 * r_pad * j + 8 * r_pad)
