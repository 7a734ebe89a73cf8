"""Paper Table 1: the canonical 3-Gridlet schedule on 2x1-MIPS PEs."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import engine, gridlet, resource, types

from .common import art_path, time_call, write_csv

LENGTHS = [10.0, 8.5, 9.5]
ARRIVALS = jnp.array([0.0, 4.0, 7.0])
EXPECTED = {
    types.TIME_SHARED: ([0.0, 4.0, 7.0], [10.0, 14.0, 18.0]),
    types.SPACE_SHARED: ([0.0, 4.0, 10.0], [10.0, 12.5, 19.5]),
}
# The engine's event trace of the schedule (Figs 9 and 12): (t, kind,
# gridlet) with kinds 0 = completion, 1 = return, 2 = arrival.
TRACES = {
    types.TIME_SHARED: [
        (0.0, 2, 0), (4.0, 2, 1), (7.0, 2, 2),    # arrivals
        (10.0, 0, 0), (10.0, 1, 0),               # G1 done+returned
        (14.0, 0, 1), (14.0, 1, 1),               # G2
        (18.0, 0, 2), (18.0, 1, 2)],              # G3
    types.SPACE_SHARED: [
        (0.0, 2, 0), (4.0, 2, 1), (7.0, 2, 2),
        (10.0, 0, 0), (10.0, 1, 0),               # G1 frees the PE
        (12.5, 0, 1), (12.5, 1, 1),
        (19.5, 0, 2), (19.5, 1, 2)],              # queued G3 last
}


def run():
    rows, out = [], []
    for policy, pname in ((types.TIME_SHARED, "time_shared"),
                          (types.SPACE_SHARED, "space_shared")):
        g = gridlet.make_batch(LENGTHS)
        fleet = resource.table1_resource(policy)
        res = engine.run_direct(g, fleet, 0, ARRIVALS, max_events=64)
        us = time_call(lambda: engine.run_direct(
            g, fleet, 0, ARRIVALS, max_events=64))
        starts = [round(float(x), 2) for x in res.gridlets.start]
        fins = [round(float(x), 2) for x in res.gridlets.finish]
        ok = (starts == EXPECTED[policy][0]
              and fins == EXPECTED[policy][1])
        for i in range(3):
            rows.append([pname, f"G{i+1}", LENGTHS[i],
                         float(ARRIVALS[i]), starts[i], fins[i],
                         round(fins[i] - float(ARRIVALS[i]), 2)])
        out.append((f"table1_{pname}", us,
                    f"finish={'/'.join(str(f) for f in fins)}"
                    f" match={ok}"))
        assert ok, f"Table 1 mismatch for {pname}: {fins}"
    write_csv(art_path("table1.csv"),
              ["policy", "gridlet", "length_mi", "arrival", "start",
               "finish", "elapsed"], rows)
    return out
