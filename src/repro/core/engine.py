"""The vectorised discrete-event engine (paper sections 3.4-3.5),
refactored around a **resource-major superstep loop** over pluggable
:class:`repro.core.des.EventSource`'s.

State layout (shape/dtype conventions)
--------------------------------------
Gridlet state stays in the flat struct-of-arrays ``GridletBatch`` (the
broker's natural layout; every per-gridlet array is ``[N]``), but every
*executing* Gridlet additionally occupies one column of a resource-major
``[R_pad, J]`` i32 job-slot table (``R_pad`` = resources padded to the
kernel block, ``J`` = job slots per resource):

  ``SimState.slot[i]``          -- i32[N] column of Gridlet ``i`` (-1 = none),
  ``SimState.row_gridlet[r,j]`` -- i32[R_pad, J] inverse map: flat Gridlet
                                   index (-1 = free).

Slots are allocated on admission (RUNNING) and freed on completion or
resource failure, so the table always holds exactly the running set.
Each while-loop iteration -- one **superstep** -- gathers ``remaining``
into the table and evaluates the Fig 8 PE-share + forecast math in a
single call to ``kernels.ops.event_scan`` (compiled Pallas on TPU,
vectorised XLA fallback on CPU hosts); the kernel also emits the per-row
earliest completion (argmin) and PE occupancy so no second pass over the
state is needed.  Reservation-held PEs enter the kernel as its
``pe_blocked`` [R] input and failed resources as its ``row_ok`` mask.

Per-resource failure/reservation state (all ``[R]``): ``res_up`` bool,
``next_fail``/``next_recover``/``fail_since``/``downtime`` f32; per-user
accounting (``spent``, ``done_on``, ...) is ``[U]`` / ``[U, R]`` f32.

Superstep semantics
-------------------
The paper's engine (section 3.4) pops one timestamp-ordered event per
iteration.  A superstep instead asks every registered event source (the
``des.EventSource`` protocol: ``candidates(state)`` / ``apply(state,
now)``) for its pending instants -- one fused
``kernels.ops.event_frontier`` min/mask pass over the concatenated
candidate vectors yields the earliest instant t*, the per-source fired
masks, and (for the batched path) the speculation horizon:

  COMPLETION    -- forecast finish of the smallest-remaining-share job
                   (paper Fig 7 step 2d / Fig 10: internal events),
  FAILURE       -- a resource goes down (per-resource MTBF stream),
  RECOVERY      -- a failed resource comes back up (MTTR stream),
  RESERVATION   -- an advance-reservation window opens or closes,
  NETWORK       -- a fair-share link transfer drains its last byte (or
                   a pre-routed transfer enters its link),
  RETURN        -- processed Gridlet reaches its broker (GRIDLET_RETURN),
  ARRIVAL       -- dispatched Gridlet reaches its resource (GRIDLET_SUBMIT),
  CALENDAR_STEP -- a local-load calendar boundary (weekend edge),
  BROKER        -- periodic scheduling event of the economic broker,

advances all resident jobs analytically by the PE-share algebra of Fig 8
over ``[t, t*)`` (and, with the network subsystem on, all in-flight
transfers by their fair link shares), then applies **every** source due
at the earliest pending ``t*`` in one vectorised batch per kind, in the
fixed tie-break priority order

  COMPLETION > FAILURE > RECOVERY > RESERVATION > NETWORK > RETURN
             > ARRIVAL > CALENDAR_STEP > BROKER.

Within a kind, ties are FIFO by flat Gridlet index -- exactly the order
the one-event-at-a-time loop would have produced, so the Table 1 /
Fig 9 / Fig 12 traces are reproduced bit-for-bit.  Application order
inside the superstep differs from the priority order in exactly one
place: BROKER is *applied* before ARRIVAL so that two event chains the
paper engine spreads over extra zero-dt iterations fold into the same
superstep -- a zero-delay RETURN of a Gridlet that completed at ``t*``,
and the zero-delay ARRIVAL of a Gridlet the broker dispatched at ``t*``
(arrival application commutes with the broker event; pre-broker arrivals
keep admission precedence via the ``arr_pre`` mask, preserving the
ARRIVAL > BROKER tie-break).  Forecasts are recomputed from state every
superstep, so the paper's stale-internal-event discard rule (section
3.4) holds by construction: a superseded forecast simply never
materialises.  Sources with nothing pending report +inf and apply as
the identity, so scenarios that leave a source unused (zero failure
rate, empty reservation table, zero weekend load) are bit-for-bit
identical to runs without it.

Failure semantics: when a resource fails, its RUNNING and QUEUED
Gridlets move to ``types.FAILED``, their job slots are freed and their
committed cost is refunded (no double billing); Gridlets IN_TRANSIT to a
down resource fail-and-refund on arrival.  The broker re-plans FAILED
Gridlets exactly like CREATED ones (see broker._assign), re-billing only
on the new dispatch; ``SimState.n_resubmits`` counts those re-dispatches
and ``downtime`` accumulates per-resource down intervals.

Time-shared share allocation (Fig 8): with g jobs on P PEs,
  min_jobs = g // P PEs' worth of jobs run at MaxShare = eff_mips/min_jobs,
  the rest at MinShare = eff_mips/(min_jobs+1); jobs are laid onto PEs so
  the smallest-remaining jobs receive MaxShare -- this is the unique layout
  consistent with the worked trace of Fig 9 / Table 1 (G3 joins G2's PE at
  t=7, G1 keeps a whole PE and finishes at 10).  Reservation windows
  shrink P to the unreserved PE count.

Space-shared (Figs 10-12): dedicated PE per job, FCFS (or SJF) queue;
PE identity never affects the trace (all PEs of a resource are equal
rated), so only the per-resource occupancy count is tracked.
Reservations gate admission (never preempt residents).

Fair-share links (the network subsystem): the static ``net_cap`` knob
sizes a ``[R_pad, T]`` transfer-slot table (``SimState.xslot`` /
``link_gridlet`` / ``link_rem``) holding the remaining bytes of every
in-flight staging and result return whose payload can contend
(``network.link_tabled``); all concurrent transfers on a resource link
split ``params.link_baud`` equally (plus ``params.bg_flows`` phantom
background flows), forecasts run through ``kernels.ops.link_scan``
exactly like completion forecasts run through ``event_scan``, and the
NETWORK source releases a drained transfer's ARRIVAL/RETURN instant
into the same superstep.  ``net_cap = 0`` (default) disables the table
and keeps the analytic ``bytes / baud`` timestamps untouched;
zero-byte payloads and infinite links never table, so zero-contention
configurations are bit-for-bit identical to the analytic path (see
docs/ARCHITECTURE.md "The network layer").

Speculative k-step batching
---------------------------
One while-loop iteration can commit more than one superstep: after the
committing superstep, :func:`step_batched` derives a **speculation
horizon** ``t_safe`` -- the min over every registered source's
``horizon(state, t_max)`` hook (des.EventSource) -- and applies up to
``k - 1`` further COMPLETION/RETURN supersteps whose instants lie
*strictly* below it.  COMPLETION and RETURN are speculation-safe
(applying them never pulls another source's pending instant earlier);
FAILURE, RECOVERY, RESERVATION, ARRIVAL, CALENDAR_STEP and BROKER all
cut the horizon at their own next instant, so the first timestamp where
any of them could intervene ends the slab and the next committing
superstep handles it with the full priority/tie-break machinery.  Each
speculative superstep is the exact COMPLETION/RETURN slice of the
general superstep, so results, traces and counters are bit-for-bit
identical for every ``batch`` value -- only the iteration count (and
the per-iteration dispatch constant) changes.  Dense-interference
scenarios (failures every superstep) degrade gracefully: every
micro-step declines and the loop behaves like ``batch=1``.  See
docs/PERFORMANCE.md for the safety argument and measurements.

Slab-fed scans (the sort-free hot path)
---------------------------------------
The while-loop carry holds a **slab**: the last scan's (remaining, tie)
rank table plus the FCFS/SJF queue ranking, each with a validity flag.
Completions depart in rank order (a per-row prefix), so both rankings
survive ordinary supersteps by a per-row subtraction; the next scan --
committing or speculative -- injects the carried rank into the
identical Fig 8 arithmetic (``event_scan_xla(rank=...)``) and runs
with zero sorts.  Validity is *checked* each scan
(:func:`_partition_ok`: the rank's only consumer is the
MaxShare/MinShare divisor split, so boundary agreement with the value
order is sufficient) and the carry is dropped whenever the table
restructures where ranks matter (admissions/arrivals onto time-shared
rows, failures, recoveries, reservation boundaries; new queue members
for the queue half) -- one exact lexsort then reseeds it.
``SimState.n_reseeds`` counts those reseeds; completion-dominated runs
stay >90% sort-free, and the count is identical for every ``batch``
value.  See docs/PERFORMANCE.md.

``SimState.n_events`` counts applied events, ``n_steps`` counts
while-loop iterations (committing supersteps), ``n_spec`` counts the
speculative supersteps the batched path folded into them; ``overflow``
counts job-slot and transfer-slot allocation failures and must stay 0
(drivers size ``J`` / ``net_cap`` accordingly).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import broker as broker_mod
from . import calendar, des, network, rand
from . import economy as econ_mod
from . import reservation as resv_mod
from . import telemetry as telemetry_mod
from ..kernels import event_scan as _event_kernels
from ..kernels import ops as kernel_ops
from ..kernels.event_scan import BIG as _BIG  # empty-slot sentinel
from .segments import group_rank
from .types import (CREATED, DONE, FAILED, FCFS, IN_TRANSIT, INF, QUEUED,
                    RETURNING, RUNNING, SJF, SPACE_SHARED, TIME_SHARED,
                    pytree_dataclass)

TRACE_LEN = 64
BLOCK_R = 8          # event_scan row blocking; resource axis padded to it
DEFAULT_BATCH = 8    # superstep batching factor k (see step_batched)


@pytree_dataclass
class SimParams:
    """Per-experiment knobs; all traced so grids of experiments vmap."""
    deadline: jax.Array        # f32[U]
    budget: jax.Array          # f32[U]
    opt: jax.Array             # i32[U] broker optimisation strategy
    max_gridlet_per_pe: jax.Array  # i32[] dispatch staging limit (paper: 2)
    sched_min_period: jax.Array    # f32[] broker poll floor (paper: 1.0)
    sched_frac: jax.Array          # f32[] fraction of deadline-left (0.01)
    measure_alpha: jax.Array       # f32[] measurement smoothing
    registered: jax.Array          # bool[R] GIS availability mask
    mtbf: jax.Array            # f32[R] mean time between failures (0 = off)
    mttr: jax.Array            # f32[R] mean time to recovery
    fail_key: jax.Array        # PRNG key seeding the MTBF/MTTR streams
    resv_res: jax.Array        # i32[K] reservation -> resource
    resv_pes: jax.Array        # i32[K] PEs held
    resv_start: jax.Array      # f32[K] window start (inclusive)
    resv_end: jax.Array        # f32[K] window end (exclusive)
    link_baud: jax.Array       # f32[R] fair-share link capacity (net
                               #     mode; inf = uncontended link)
    bg_flows: jax.Array        # f32[R] phantom background flows riding
                               #     each link (net mode; may be
                               #     fractional)
    pricing_model: jax.Array   # i32[] economy.PRICE_* (0 = static --
                               #     both pricing sources inert)
    market_period: jax.Array   # f32[] commodity repricing period
    market_gain: jax.Array     # f32[] posted-price adjustment rate per
                               #     unit of excess demand
    price_floor: jax.Array     # f32[] posted-price clamp, x base price
    price_cap: jax.Array       # f32[] posted-price clamp, x base price
    auction_period: jax.Array  # f32[] sealed-bid auction round period
    auction_key: jax.Array     # PRNG key seeding the bid draws
    plan_ahead: jax.Array      # bool[] plan-ahead DBC dispatch: price
                               #     reservation windows + link queueing
                               #     into the capacity estimates and run
                               #     the exact cost-time grouping
                               #     (cs/0203020) -- see broker._measure
    # --- shared-trunk topology (None = private links only; a None
    #     field is an empty pytree subtree, so `is None` is a STATIC
    #     gate -- the default compiles the exact pre-trunk program) ---
    trunk_of: object           # i32[R] trunk id per resource (-1 =
                               #     private-only) or None
    trunk_baud: object         # f32[R] trunk capacity gathered out to
                               #     per-resource form, or None
    trunk_bg: object           # f32[R] trunk phantom background flows
                               #     (per-resource form), or None
    # --- trace-driven fault injection (None = no trace; same static
    #     None gate as the trunk fields) ---
    fault_time: object         # f32[K] scheduled instants, ascending
    fault_target: object       # i32[K] 0..R-1 = resource; R + id =
                               #     trunk id (every incident resource
                               #     flips in one apply)
    fault_up: object           # bool[K] True = bring up, False = cut
    # --- fault-tolerant broker knobs (always-present traced scalars;
    #     the defaults are vacuous, bitwise-frozen legacy behaviour) ---
    retry_limit: jax.Array     # i32[] max refund+resubmit cycles per
                               #     gridlet (default 2**30 = unbounded)
    backoff_base: jax.Array    # f32[] exponential backoff unit: the
                               #     n-th retry re-dispatches no earlier
                               #     than fail_t + base * 2**(n-1)
                               #     (default 0.0 = immediate)
    blacklist_cooldown: jax.Array  # f32[] broker _measure ignores
                               #     resources that recovered less than
                               #     this long ago (default 0.0 = off)


def default_params(deadline, budget, opt, n_users: int,
                   n_resources: int = 1, registered=None, mtbf=None,
                   mttr=None, reservations=None,
                   fail_key=None, link_baud=None,
                   bg_flows=None, pricing_model=econ_mod.PRICE_STATIC,
                   market_period=None, market_gain=None,
                   price_floor=None, price_cap=None,
                   auction_period=None, auction_key=None,
                   plan_ahead=False, trunk_of=None, trunk_baud=None,
                   trunk_bg=None, fault_trace=None, retry_limit=None,
                   backoff_base=None,
                   blacklist_cooldown=None) -> SimParams:
    """``mtbf``/``mttr`` broadcast to [R]; 0 disables the failure source.
    ``reservations`` is a ReservationBook, an iterable of (resource,
    pes, start, end) tuples, or the 4-array table itself.
    ``link_baud``/``bg_flows`` feed the fair-share network subsystem
    (only consulted when the engine runs with ``net_cap > 0``); the
    default infinite ``link_baud`` makes every link uncontended --
    callers that enable the subsystem pass ``fleet.baud_rate`` (or a
    scenario override) here.  ``pricing_model`` selects the dynamic
    pricing source (economy.PRICE_*; the default keeps fleet prices
    static and both pricing sources inert, bit-identical to the
    pre-economy engine); the remaining knobs default to the thesis-ish
    settings (reprice/auction every 10 time units, +-25% adjustment,
    posted prices clamped to [0.5, 2.0] x base).

    ``trunk_of`` (per-resource trunk id, -1 = private) enables the
    shared-trunk topology: ``trunk_baud``/``trunk_bg`` are per-TRUNK
    vectors (or scalars), gathered out to per-resource form via
    network.trunk_topology.  ``fault_trace`` enables trace-driven
    fault injection: an iterable of (time, target, up) rows or the
    [K, 3] array itself, where target 0..R-1 names a resource and
    R + id names a trunk (the whole failure domain flips at once);
    rows are time-sorted here so the engine's cursor replay is order-
    independent.  ``retry_limit``/``backoff_base``/
    ``blacklist_cooldown`` are the fault-tolerant broker knobs; the
    defaults freeze legacy behaviour bitwise (unbounded immediate
    retries, no blacklist)."""
    f = lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32), (n_users,))
    r = lambda x: jnp.broadcast_to(jnp.asarray(
        0.0 if x is None else x, jnp.float32), (n_resources,))
    if registered is None:
        registered = jnp.ones((n_resources,), bool)
    if reservations is None:
        resv = resv_mod.empty_tables()
    elif hasattr(reservations, "as_tables"):
        resv = reservations.as_tables()
    elif (isinstance(reservations, tuple) and len(reservations) == 4
          and all(hasattr(x, "dtype") for x in reservations)):
        resv = reservations
    else:
        resv = resv_mod.as_tables(reservations)
    if trunk_of is None:
        t_of = t_baud = t_bg = None
    else:
        t_of, t_baud, t_bg = network.trunk_topology(
            trunk_of, n_resources, trunk_baud=trunk_baud,
            trunk_bg=trunk_bg)
    if fault_trace is None:
        ft = ftgt = fup = None
    else:
        tr = jnp.asarray(
            [(float(a), int(b), bool(c)) for a, b, c in fault_trace]
            if not hasattr(fault_trace, "dtype") else fault_trace,
            jnp.float32).reshape(-1, 3)
        order = jnp.argsort(tr[:, 0], stable=True)
        tr = tr[order]
        ft = tr[:, 0]
        ftgt = tr[:, 1].astype(jnp.int32)
        fup = tr[:, 2] > 0.5
    return SimParams(
        deadline=f(deadline), budget=f(budget),
        opt=jnp.broadcast_to(jnp.asarray(opt, jnp.int32), (n_users,)),
        max_gridlet_per_pe=jnp.asarray(2, jnp.int32),
        sched_min_period=jnp.asarray(1.0, jnp.float32),
        sched_frac=jnp.asarray(0.01, jnp.float32),
        measure_alpha=jnp.asarray(0.5, jnp.float32),
        registered=registered,
        mtbf=r(mtbf), mttr=r(mttr),
        fail_key=(jax.random.PRNGKey(0) if fail_key is None else fail_key),
        resv_res=resv[0], resv_pes=resv[1],
        resv_start=resv[2], resv_end=resv[3],
        link_baud=jnp.broadcast_to(
            jnp.asarray(INF if link_baud is None else link_baud,
                        jnp.float32), (n_resources,)),
        bg_flows=r(bg_flows),
        pricing_model=jnp.asarray(pricing_model, jnp.int32),
        market_period=jnp.asarray(
            10.0 if market_period is None else market_period, jnp.float32),
        market_gain=jnp.asarray(
            0.25 if market_gain is None else market_gain, jnp.float32),
        price_floor=jnp.asarray(
            0.5 if price_floor is None else price_floor, jnp.float32),
        price_cap=jnp.asarray(
            2.0 if price_cap is None else price_cap, jnp.float32),
        auction_period=jnp.asarray(
            10.0 if auction_period is None else auction_period,
            jnp.float32),
        auction_key=(jax.random.PRNGKey(0) if auction_key is None
                     else auction_key),
        plan_ahead=jnp.asarray(plan_ahead, bool),
        trunk_of=t_of, trunk_baud=t_baud, trunk_bg=t_bg,
        fault_time=ft, fault_target=ftgt, fault_up=fup,
        retry_limit=jnp.asarray(
            2**30 if retry_limit is None else retry_limit, jnp.int32),
        backoff_base=jnp.asarray(
            0.0 if backoff_base is None else backoff_base, jnp.float32),
        blacklist_cooldown=jnp.asarray(
            0.0 if blacklist_cooldown is None else blacklist_cooldown,
            jnp.float32),
    )


@pytree_dataclass
class SimState:
    t: jax.Array               # f32 current simulation time
    g: object                  # GridletBatch
    slot: jax.Array            # i32[N] job-slot column (-1 = none)
    row_gridlet: jax.Array     # i32[R_pad, J] slot -> gridlet (-1 = free)
    xslot: jax.Array           # i32[N] transfer-slot column (-1 = none;
                               #     net mode only, see link_gridlet)
    link_gridlet: jax.Array    # i32[R_pad, T] transfer slot -> gridlet
                               #     (-1 = free); T = 0 disables the
                               #     fair-share network subsystem
    link_rem: jax.Array        # f32[R_pad, T] bytes still to move per
                               #     in-flight transfer
    spent: jax.Array           # f32[U] committed budget
    done_on: jax.Array         # f32[U,R] jobs of u completed on r
    first_dispatch: jax.Array  # f32[U,R] first dispatch instant (inf)
    next_sched: jax.Array      # f32 next broker event
    term_time: jax.Array       # f32[U] broker termination instant
    res_up: jax.Array          # bool[R] resource currently up
    next_fail: jax.Array       # f32[R] scheduled failure instant (inf = none)
    next_recover: jax.Array    # f32[R] scheduled recovery instant
    fail_since: jax.Array      # f32[R] instant the resource went down
    downtime: jax.Array        # f32[R] accumulated down intervals
    recovered_at: jax.Array    # f32[R] instant of the last recovery
                               #     (-inf = never; feeds the broker's
                               #     cooldown blacklist)
    trace_ptr: jax.Array       # i32 cursor into the fault-injection
                               #     trace (rows < ptr already applied)
    rng_key: jax.Array         # PRNG key for the MTBF/MTTR streams
    price: jax.Array           # f32[R] posted G$/MI trading metric
                               #     (== fleet.cost_per_mi until a
                               #     pricing round moves it; per-MI so
                               #     the broker never divides a carried
                               #     array by an invariant in-loop --
                               #     XLA may compile that division
                               #     differently per path, breaking the
                               #     bitwise cross-path contract)
    next_market: jax.Array     # f32 next commodity repricing instant
                               #     (inf = market source off)
    next_auction: jax.Array    # f32 next auction round instant (inf =
                               #     auction source off)
    auction_key: jax.Array     # PRNG key for sealed-bid draws (one
                               #     split consumed per fired round)
    n_events: jax.Array        # i32 applied events (batched kinds summed)
    n_steps: jax.Array         # i32 while-loop iterations (committing
                               #     supersteps; speculative ones excluded)
    n_spec: jax.Array          # i32 speculative supersteps applied by the
                               #     k-step batched path
    n_reseeds: jax.Array       # i32 scans that re-sorted the job-slot
                               #     table (slab carry misses)
    n_scans: jax.Array         # i32 Fig 8 scans performed (committing +
                               #     speculative, incl. declined micro-
                               #     steps) -- the reseed denominator
    n_trace: jax.Array         # i32 trace entries written
    n_failed: jax.Array        # i32 gridlets hit by a failure
    n_resubmits: jax.Array     # i32 FAILED gridlets re-dispatched
    overflow: jax.Array        # i32 job-slot / transfer-slot
                               #     allocation failures (== 0)
    trace_t: jax.Array         # f32[TRACE_LEN]
    trace_kind: jax.Array      # i32[TRACE_LEN] des.K_* codes
    trace_who: jax.Array       # i32[TRACE_LEN]


class SimResult(NamedTuple):
    gridlets: object
    spent: jax.Array
    term_time: jax.Array
    n_events: jax.Array
    trace: tuple
    n_steps: jax.Array
    overflow: jax.Array
    n_failed: jax.Array
    n_resubmits: jax.Array
    downtime: jax.Array
    n_spec: jax.Array
    n_reseeds: jax.Array
    n_scans: jax.Array
    # The metrics ring (core/telemetry.py) when the run recorded one,
    # else None.  Observability only: every "what" comparison across
    # engine paths / telemetry on-off excludes it (like the "how"
    # counters, it may pack supersteps differently per path).
    telemetry: object = None


# ----------------------------------------------------------------------
# Resource dynamics
# ----------------------------------------------------------------------

def _rates(state, fleet, n_resources):
    """Per-gridlet execution rate (MI per time unit) under Fig 8 shares.

    Flat-layout XLA reference path, kept as the oracle the kernel path
    must agree with (asserted in tests); the superstep loop itself goes
    through kernels.ops.event_scan on the resource-major table.
    """
    g = state.g
    running = g.status == RUNNING
    res = jnp.clip(g.resource, 0, n_resources - 1)
    eff = calendar.effective_mips(fleet, state.t)          # [R] per PE
    policy = fleet.policy[res]

    # --- time-shared: rank jobs on each resource by remaining MI ---
    ts_member = running & (policy == TIME_SHARED)
    rank, counts = group_rank(res, ts_member, g.remaining, n_resources)
    g_on_r = counts[res].astype(jnp.int32)                  # jobs on my res
    p_r = fleet.num_pe[res]
    min_jobs = g_on_r // jnp.maximum(p_r, 1)
    extra = g_on_r % jnp.maximum(p_r, 1)
    max_share_count = (p_r - extra) * min_jobs
    divisor = min_jobs + (rank >= max_share_count).astype(jnp.int32)
    ts_rate = eff[res] / jnp.maximum(divisor, 1).astype(jnp.float32)

    # --- space-shared: a dedicated PE at full effective rate ---
    ss_rate = eff[res]

    rate = jnp.where(policy == TIME_SHARED, ts_rate, ss_rate)
    return jnp.where(running, rate, 0.0)


def _reserved_pes(params, t, n_resources):
    """PEs blocked by committed reservation windows at ``t``: i32[R]."""
    return resv_mod.active_pes(params.resv_res, params.resv_pes,
                               params.resv_start, params.resv_end, t,
                               n_resources)


def _table_inputs(state, fleet, params, n_resources, r_pad):
    """Gather the [R_pad, J] job-slot table and the per-row kernel
    inputs -- the shared prologue of the committing scan and the
    slab-fed speculative scan (identical arithmetic is what keeps the
    two paths bit-for-bit interchangeable).

    An occupied slot whose remaining underflowed to exactly 0 (f32
    advance rounding) must stay visible to the kernel -- 0 is the
    empty-slot sentinel -- so it is clamped to a tiny epsilon: it then
    forecasts an immediate completion and keeps its PE share, exactly
    as a zero-remaining RUNNING job did in the one-event-at-a-time
    engine.
    """
    g = state.g
    rg = state.row_gridlet
    occupied = rg >= 0
    gid = jnp.clip(rg, 0, g.n - 1)
    rem_rj = jnp.where(occupied,
                       jnp.maximum(g.remaining[gid], 1e-30), 0.0)
    tie_rj = jnp.where(occupied, rg, 2 ** 30).astype(jnp.float32)
    pad = r_pad - n_resources
    eff = jnp.pad(calendar.effective_mips(fleet, state.t), (0, pad),
                  constant_values=1.0)
    npe = jnp.pad(fleet.num_pe, (0, pad), constant_values=1)
    pol = jnp.pad(fleet.policy, (0, pad))
    blocked = jnp.pad(
        _reserved_pes(params, state.t, n_resources).astype(jnp.float32),
        (0, pad))
    row_ok = jnp.pad(state.res_up, (0, pad),
                     constant_values=True).astype(jnp.float32)
    return rem_rj, tie_rj, eff, npe, pol, blocked, row_ok


def _scan_events(state, fleet, params, n_resources, r_pad, rank=None):
    """Resource-major Fig 8 scan through kernels.ops.event_scan.

    Gathers ``remaining`` into the [R_pad, J] job-slot table (flat
    gridlet index as the FIFO tie-break key) and returns the kernel
    outputs (rate [R_pad, J], t_min [R_pad], argmin col [R_pad],
    occupancy [R_pad], rank [R_pad, J]).  Reservation-held PEs and down
    resources enter as the kernel's ``pe_blocked`` / ``row_ok`` masks.
    ``rank`` injects a precomputed rank table (the slab-fed speculative
    path), making the scan entirely sort-free.
    """
    rem_rj, tie_rj, eff, npe, pol, blocked, row_ok = _table_inputs(
        state, fleet, params, n_resources, r_pad)
    return kernel_ops.event_scan(rem_rj, eff, npe, tie=tie_rj, policy=pol,
                                 pe_blocked=blocked, row_ok=row_ok,
                                 rank=rank, with_rank=True)


# ----------------------------------------------------------------------
# Fair-share link dynamics (the network subsystem)
# ----------------------------------------------------------------------
#
# The engine's static ``net_cap`` knob sizes the [R_pad, T] transfer-
# slot table (T = net_cap transfer slots per resource link; 0 disables
# the subsystem entirely -- the table is then [R_pad, 0] and every
# branch below is statically skipped, so the analytic path is untouched
# code, not a runtime no-op).  With the subsystem on, a transfer whose
# payload can actually contend (network.link_tabled: positive bytes
# over a finite-positive link) occupies one column of the table with
# its ``remaining_bytes``; all concurrent transfers on a link share its
# baud rate equally (kernels.ops.link_scan), remainders advance
# piecewise-constantly between events exactly like remaining MI under
# Fig 8 shares, and the NETWORK event source fires when a transfer
# drains -- releasing the gridlet's ARRIVAL/RETURN instant to "now" so
# the release folds into the same superstep.  Zero-byte payloads and
# infinite links never enter the table and keep the analytic
# (instantaneous) timestamps, which is what keeps zero-contention
# configurations bit-for-bit identical to the analytic engine.

def _net_on(state) -> bool:
    """Static: the fair-share network subsystem is enabled (T > 0)."""
    return state.link_rem.shape[1] > 0


def _residents_r(state, n_resources):
    """bool[R]: the resource hosts *resident* work -- RUNNING or QUEUED
    gridlets a failure/recovery strike would actually interfere with.
    Used by the speculation horizon: the resident set of a resource can
    only shrink inside a slab (queue admissions draw from already-
    resident QUEUED jobs; arrivals and broker dispatches cut the
    horizon), so a strike gated off here stays non-interfering for the
    whole slab and is fired by the speculative micro-steps instead."""
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    resident = (g.status == RUNNING) | (g.status == QUEUED)
    return jax.ops.segment_sum(resident.astype(jnp.int32), res,
                               num_segments=n_resources) > 0


def _xfer_bytes(g):
    """Payload of each gridlet's pending/possible transfer: input files
    while staging (IN_TRANSIT), result files on the way back."""
    return jnp.where(g.status == IN_TRANSIT, g.in_bytes, g.out_bytes)


def _link_scan(state, params, n_resources, r_pad):
    """Fair-share rates + next-transfer-completion forecast per link,
    through kernels.ops.link_scan (Pallas on TPU, XLA fallback on CPU).
    The flat gridlet index is the argmin tie-break key, mirroring the
    job-slot table's FIFO convention.

    With a shared-trunk topology (params.trunk_of, a static None gate)
    each row additionally receives a per-row fair-share rate *cap*:
    the trunk's capacity divided by its total occupancy across every
    incident row.  The cross-row occupancy gather runs here -- plain
    jnp over the [R_pad, T] table -- because the row-blocked kernel
    grid cannot see other rows; the kernel then just min()s the cap in
    (kernels.event_scan._link_math).  network.fastest_drain stays a
    valid speculation lower bound: a trunk can only *lower* rates, so
    no tabled drain ever finishes earlier than the private-link bound.
    """
    pad = r_pad - n_resources
    baud = jnp.pad(params.link_baud, (0, pad), constant_values=1.0)
    bg = jnp.pad(params.bg_flows, (0, pad))
    tie = jnp.where(state.link_gridlet >= 0, state.link_gridlet,
                    2 ** 30).astype(jnp.float32)
    cap = None
    if params.trunk_of is not None:
        # live-row occupancy, computed exactly like _link_math's m
        live = (baud > 0.0) & (baud < network.BIG)
        valid = ((state.link_rem > 0.0) & (state.link_rem < network.BIG)
                 & live[:, None])
        occ = jnp.sum(valid.astype(jnp.float32), axis=1)
        cap = network.trunk_rate_cap(
            occ,
            jnp.pad(params.trunk_of, (0, pad), constant_values=-1),
            jnp.pad(params.trunk_baud, (0, pad), constant_values=1.0),
            jnp.pad(params.trunk_bg, (0, pad)))
    return kernel_ops.link_scan(state.link_rem, baud, bg=bg, tie=tie,
                                cap=cap)


def _pending_entries(state, params, n_resources):
    """Transfers created with a *future* network-entry instant (pre-
    routed ``run_direct`` dispatches): tabled payloads holding their
    entry time in ``t_event`` while awaiting a transfer slot.  The
    NETWORK source enqueues them exactly at that instant."""
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    moving = (g.status == IN_TRANSIT) | (g.status == RETURNING)
    return (moving & (state.xslot < 0) & jnp.isfinite(g.t_event) &
            network.link_tabled(_xfer_bytes(g), params.link_baud[res]))


def _advance_transfers(state, ctx, t_next, any_event, gate=None):
    """Advance every in-flight transfer analytically over [t, t_next)
    by the fair-share rates in ``ctx["net_scan"]`` (the link twin of
    :func:`_advance_jobs`; must run while ``state.t`` still holds the
    interval start).  Transfers forecast to drain by ``t_next`` are
    zeroed and recorded in ``ctx["xfer_done"]`` for the NETWORK apply;
    survivors are clamped to a tiny epsilon so f32 rounding can never
    turn an occupied slot into the empty-slot sentinel.  ``gate`` (the
    sweep engine's masked micro-supersteps) makes the advance a bitwise
    no-op when False even for occupied slots whose remainder sits at
    the epsilon clamp."""
    from .types import replace
    rate_lt = ctx["net_scan"][0]
    occupied = state.link_gridlet >= 0
    rem = state.link_rem
    rel = jnp.where(occupied, rem / jnp.maximum(rate_lt, 1e-30), INF)
    dt = jnp.maximum(t_next - state.t, 0.0)
    due = occupied & any_event & (state.t + rel <= t_next)
    adv = occupied if gate is None else occupied & gate
    new_rem = jnp.where(
        due, 0.0,
        jnp.where(adv, jnp.maximum(rem - rate_lt * dt, 1e-30), rem))
    ctx["xfer_done"] = due
    return replace(state, link_rem=new_rem)


def _enqueue_transfers(state, mask, n_resources, r_pad):
    """Allocate a transfer-slot column on each masked gridlet's
    resource link, load its payload as ``remaining_bytes``, and mark
    the gridlet's pending instant load-dependent (``t_event = inf`` --
    the NETWORK source owns it now).  Same sort-free running-count +
    binary-search allocation as :func:`_alloc_slots`; gridlets that
    find no free column are counted in ``overflow`` (drivers size
    ``net_cap`` so this cannot happen)."""
    from .types import replace
    g = state.g
    n = g.n
    t_cap = state.link_gridlet.shape[1]
    res = jnp.clip(g.resource, 0, n_resources - 1)
    idx = jnp.arange(n, dtype=jnp.int32)
    free = state.link_gridlet < 0
    n_free = jnp.sum(free, axis=1)                        # [R_pad]
    rank = _count_rank(res, mask, n_resources)
    ok = mask & (rank < n_free[res])
    cumfree = jnp.cumsum(free.astype(jnp.int32), axis=1)  # [R_pad, T]
    want = rank + 1
    lo = jnp.zeros((n,), jnp.int32)
    hi = jnp.full((n,), t_cap - 1, jnp.int32)
    for _ in range(max(1, (t_cap - 1).bit_length())):
        mid = (lo + hi) // 2
        ge = cumfree[res, mid] >= want
        lo = jnp.where(ge, lo, mid + 1)
        hi = jnp.where(ge, mid, hi)
    col = hi
    rows = jnp.where(ok, res, r_pad)            # out of range: dropped
    cols = jnp.where(ok, col, 0)
    lg = state.link_gridlet.at[rows, cols].set(idx, mode="drop")
    lr = state.link_rem.at[rows, cols].set(
        jnp.where(ok, _xfer_bytes(g), 0.0), mode="drop")
    g2 = replace(g, t_event=jnp.where(ok, INF, g.t_event))
    return replace(
        state, g=g2, link_gridlet=lg, link_rem=lr,
        xslot=jnp.where(ok, col, state.xslot),
        overflow=state.overflow + jnp.sum(mask & ~ok, dtype=jnp.int32))


def _enqueue_new_transfers(state, params, n_resources, r_pad,
                           select_free=False):
    """End-of-superstep pass: transfers *created this superstep*
    (broker dispatches, completions' result returns) enter their link
    now.  Tabled creation marked them ``t_event == inf`` with no slot,
    so the condition is transient; pending entries (finite ``t_event``)
    wait for the NETWORK source instead.  ``select_free`` (static) runs
    the allocation unconditionally -- it is a bitwise no-op on an empty
    mask (the masked-apply contract), so the sweep engine skips the
    ``cond``."""
    g = state.g
    moving = (g.status == IN_TRANSIT) | (g.status == RETURNING)
    new = moving & (state.xslot < 0) & ~jnp.isfinite(g.t_event)
    if select_free:
        return _enqueue_transfers(state, new, n_resources, r_pad)
    return jax.lax.cond(
        new.any(),
        lambda s: _enqueue_transfers(s, new, n_resources, r_pad),
        lambda s: s, state)


def _free_link_slots(state, mask):
    """Release the transfer slots of every gridlet in ``mask`` (their
    transfer was consumed by an ARRIVAL/RETURN application)."""
    from .types import replace
    r_pad, t_cap = state.link_gridlet.shape
    res = jnp.clip(state.g.resource, 0, r_pad - 1)
    rows = jnp.where(mask, res, r_pad)          # out of range: dropped
    cols = jnp.where(mask, jnp.clip(state.xslot, 0, t_cap - 1), 0)
    lg = state.link_gridlet.at[rows, cols].set(-1, mode="drop")
    lr = state.link_rem.at[rows, cols].set(0.0, mode="drop")
    return replace(state, link_gridlet=lg, link_rem=lr,
                   xslot=jnp.where(mask, -1, state.xslot))


# ----------------------------------------------------------------------
# Batched event application
# ----------------------------------------------------------------------

def _free_slots(state, mask, res, r_pad):
    """Release the job slots of every gridlet in ``mask``."""
    from .types import replace
    j_cap = state.row_gridlet.shape[1]
    rows = jnp.where(mask, res, r_pad)          # out of range: dropped
    cols = jnp.where(mask, jnp.clip(state.slot, 0, j_cap - 1), 0)
    rg = state.row_gridlet.at[rows, cols].set(-1, mode="drop")
    return replace(state, row_gridlet=rg,
                   slot=jnp.where(mask, -1, state.slot))


def _count_rank(res, mask, n_resources):
    """Rank of each masked element among its resource's masked set, in
    flat-index order -- ``group_rank(res, mask, idx, R)`` without the
    sort: when the order key IS the array order, the rank is a running
    segmented count (one [N, R] cumsum; XLA CPU sorts at this size cost
    ~10x more).  Non-members get garbage (callers mask)."""
    onehot = ((res[:, None] ==
               jnp.arange(n_resources, dtype=jnp.int32)[None, :])
              & mask[:, None]).astype(jnp.int32)
    excl = jnp.cumsum(onehot, axis=0) - onehot
    return jnp.take_along_axis(excl, res[:, None].astype(jnp.int32),
                               axis=1)[:, 0]


def _alloc_slots(state, mask, res, n_resources, r_pad):
    """Allocate a free job-slot column to every gridlet in ``mask``.

    Within a resource, gridlets take columns in flat-index order (the
    FIFO tie-break also used by the kernel, so column identity never
    matters).  Gridlets that find no free column are counted in
    ``overflow`` -- drivers size J so this cannot happen.

    Sort-free: the per-resource batch rank is a running segmented count
    (:func:`_count_rank`), and the rank-th free column comes from an
    unrolled binary search over the row's running free-column count --
    log2(J) cheap gathers instead of a [R, J] argsort or scatter (both
    ~10x slower on XLA CPU at fleet shapes).
    """
    from .types import replace
    g = state.g
    n = g.n
    j_cap = state.row_gridlet.shape[1]
    idx = jnp.arange(n, dtype=jnp.int32)
    used = state.row_gridlet >= 0
    free = ~used
    n_free = jnp.sum(free, axis=1)                        # [R_pad]
    rank = _count_rank(res, mask, n_resources)
    ok = mask & (rank < n_free[res])
    # col = the rank-th free column of the row = the smallest c whose
    # inclusive free count reaches rank + 1 (same column the stable
    # argsort-of-used used to yield).
    cumfree = jnp.cumsum(free.astype(jnp.int32), axis=1)  # [R_pad, J]
    want = rank + 1
    lo = jnp.zeros((n,), jnp.int32)
    hi = jnp.full((n,), j_cap - 1, jnp.int32)
    for _ in range(max(1, (j_cap - 1).bit_length())):
        mid = (lo + hi) // 2
        ge = cumfree[res, mid] >= want
        lo = jnp.where(ge, lo, mid + 1)
        hi = jnp.where(ge, mid, hi)
    col = hi
    rows = jnp.where(ok, res, r_pad)            # out of range: dropped
    cols = jnp.where(ok, col, 0)
    rg = state.row_gridlet.at[rows, cols].set(idx, mode="drop")
    return replace(
        state, row_gridlet=rg,
        slot=jnp.where(ok, col, state.slot),
        overflow=state.overflow + jnp.sum(mask & ~ok, dtype=jnp.int32))


def _apply_completions(state, fleet, params, completes, t_next,
                       n_resources, r_pad):
    """RUNNING -> RETURNING for the whole batch; job slots freed.

    The result-return instant is analytic (``t_next + out_delay``)
    unless the network subsystem is on and the payload contends for its
    link: those transfers are marked load-dependent (``t_event = inf``)
    and enter the transfer-slot table at the end of this superstep
    (:func:`_enqueue_new_transfers`)."""
    from .types import replace
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    if _net_on(state):
        baud = params.link_baud[res]
        tabled = network.link_tabled(g.out_bytes, baud)
        t_ev = jnp.where(
            tabled, INF,
            t_next + network.transfer_delay(g.out_bytes, baud))
    else:
        t_ev = t_next + network.transfer_delay(g.out_bytes,
                                               fleet.baud_rate[res])
    g = replace(
        g,
        status=jnp.where(completes, RETURNING, g.status),
        finish=jnp.where(completes, t_next, g.finish),
        t_event=jnp.where(completes, t_ev, g.t_event),
    )
    return _free_slots(replace(state, g=g), completes, res, r_pad)


def _queue_rank(state, fleet, n_resources):
    """Fresh FCFS/SJF within-resource rank of every QUEUED gridlet --
    the seed of the queue-rank carry (one lexsort; both keys are static
    while a job stays queued, and admissions only ever remove a rank
    prefix, so the carry stays exact until the queue *membership*
    changes)."""
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    queued = g.status == QUEUED
    # FCFS: earliest arrival at the resource (QUEUED jobs keep their
    # arrival instant in t_event); SJF: smallest job. Ties by index.
    qkey = jnp.where(fleet.queue_policy[res] == SJF, g.length_mi,
                     g.t_event)
    return group_rank(res, queued, qkey, n_resources)[0]


def _admit_queued(state, fleet, free_pe, t_next, n_resources, qrank):
    """Freed space-shared PEs admit the next queued Gridlets in FCFS/SJF
    order (Fig 10 step 3) -- the ``qrank`` lowest ranks per resource.
    Returns (state, admitted mask) -- slots are allocated later
    together with the arrival batch.
    """
    from .types import replace
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    queued = g.status == QUEUED
    admitq = queued & (qrank < free_pe[res])
    g = replace(
        g,
        status=jnp.where(admitq, RUNNING, g.status),
        start=jnp.where(admitq, jnp.minimum(g.start, t_next), g.start),
        t_event=jnp.where(admitq, INF, g.t_event),
    )
    return replace(state, g=g), admitq


def _apply_returns(state, fleet, t_next, n_users, n_resources,
                   gate=None):
    """RETURNING & due -> DONE for the whole batch; broker measurement
    update (paper 4.2.1 step 6).  Includes zero-delay returns of jobs
    that completed earlier in this same superstep.  ``gate`` (the sweep
    engine's masked micro-supersteps) forces the due mask empty when
    False, making the application a bitwise no-op regardless of
    ``t_next``.
    """
    from .types import replace
    g = state.g
    ret_due = (g.status == RETURNING) & (g.t_event <= t_next)
    if gate is not None:
        ret_due &= gate
    g = replace(g,
                status=jnp.where(ret_due, DONE, g.status),
                returned=jnp.where(ret_due, t_next, g.returned))
    ur = g.user * n_resources + jnp.clip(g.resource, 0, n_resources - 1)
    done_on = state.done_on + jax.ops.segment_sum(
        ret_due.astype(jnp.float32), ur,
        num_segments=n_users * n_resources).reshape(n_users, n_resources)
    state = replace(state, g=g, done_on=done_on)
    if _net_on(state):    # consumed transfers release their link slots
        state = _free_link_slots(state, ret_due & (state.xslot >= 0))
    return state, ret_due


def _fail_gridlets(state, victims, n_users, now, params):
    """The fail-and-refund invariant, shared by the FAILURE source, the
    trace-injection source and the down-resource arrival path:
    ``victims`` move to FAILED, drop their broker assignment and
    pending event, and their committed cost is refunded (the broker
    re-bills only on the resubmission dispatch).  Each victim's retry
    counter ticks and its earliest re-dispatch instant moves to
    ``now + backoff_base * 2**(n_retries - 1)`` -- the broker's
    ``_retryable`` gate consumes both (at the default knobs the gate is
    vacuous: retry_at == now and the limit is unbounded, bitwise-frozen
    legacy behaviour).  Every write is gated on ``victims``, so the
    body is a bitwise no-op on an empty mask even at garbage ``now``
    (the masked-apply contract)."""
    from .types import replace
    g = state.g
    refund = jax.ops.segment_sum(jnp.where(victims, g.cost, 0.0),
                                 g.user, num_segments=n_users)
    n_retries = g.n_retries + victims.astype(jnp.int32)
    backoff = params.backoff_base * jnp.exp2(jnp.minimum(
        n_retries - 1, 30).astype(jnp.float32))
    g = replace(
        g,
        status=jnp.where(victims, FAILED, g.status),
        assigned=jnp.where(victims, -1, g.assigned),
        t_event=jnp.where(victims, INF, g.t_event),
        cost=jnp.where(victims, 0.0, g.cost),
        n_retries=n_retries,
        retry_at=jnp.where(victims, now + backoff, g.retry_at),
    )
    return replace(
        state, g=g, spent=state.spent - refund,
        n_failed=state.n_failed + jnp.sum(victims, dtype=jnp.int32))


def _apply_arrivals(state, fleet, params, free_pe, arr_pre, t_next,
                    n_users, n_resources, select_free=False):
    """IN_TRANSIT & due -> RUNNING (time-shared / free PE) or QUEUED,
    for the whole batch; arrivals at a *down* resource fail-and-refund.

    All time-shared arrivals commute (every resident job just
    re-shares).  Space-shared arrivals fill the ``free_pe`` PEs left
    after this superstep's queue admissions -- arrivals already due
    before the broker event (``arr_pre``) first, then this superstep's
    zero-delay dispatches, flat-index order within each class: exactly
    the order the one-at-a-time loop (ARRIVAL before BROKER at equal
    time) admits them -- and the rest join the queue stamped with their
    arrival instant (the FCFS key).  Returns (state, arrival mask,
    newly-running mask, newly-queued mask).
    """
    from .types import replace
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    idx = jnp.arange(g.n, dtype=jnp.int32)
    arr_due = (g.status == IN_TRANSIT) & (g.t_event <= t_next)
    arr_fail = arr_due & ~state.res_up[res]
    arr_live = arr_due & ~arr_fail
    is_ss = fleet.policy[res] == SPACE_SHARED
    arr_ss = arr_live & is_ss
    order = jnp.where(arr_pre, idx, idx + g.n)
    if select_free:
        # The rank is only consulted by arr_ss members (everyone else
        # short-circuits on ~is_ss or ~arr_live), so running group_rank
        # unconditionally is result-identical to the gated form.
        rank = group_rank(res, arr_ss, order, n_resources)[0]
    else:
        rank = jax.lax.cond(
            arr_ss.any(),
            lambda: group_rank(res, arr_ss, order, n_resources)[0],
            lambda: jnp.full((g.n,), jnp.int32(2 ** 30)))
    arr_run = arr_live & (~is_ss | (rank < free_pe[res]))
    arr_queue = arr_ss & ~arr_run
    state = _fail_gridlets(state, arr_fail, n_users, t_next, params)
    g = state.g
    g = replace(
        g,
        status=jnp.where(arr_run, RUNNING,
                         jnp.where(arr_queue, QUEUED, g.status)),
        start=jnp.where(arr_run, jnp.minimum(g.start, t_next), g.start),
        # QUEUED jobs keep their arrival instant in t_event (the FCFS
        # key); QUEUED is never scanned as a pending event so it's safe.
        t_event=jnp.where(arr_run, INF,
                          jnp.where(arr_queue, t_next, g.t_event)),
    )
    state = replace(state, g=g)
    if _net_on(state):    # consumed transfers release their link slots
        state = _free_link_slots(state, arr_due & (state.xslot >= 0))
    return state, arr_due, arr_run, arr_queue


def _apply_failures(state, fleet, params, due_r, now, n_users,
                    n_resources, r_pad, masked=False):
    """Down the resources in ``due_r``: RUNNING/QUEUED residents move to
    FAILED, their slots are freed and their committed cost refunded; the
    MTTR stream schedules each resource's recovery.  ``masked`` (static)
    makes the body a bitwise no-op on an empty ``due_r`` -- every write
    below is already gated on ``due_r``/``victim``; the PRNG split is
    the one non-maskable leaf, selected back when nothing fired (the
    masked-apply contract for the select-free sweep engine)."""
    from .types import replace
    g = state.g
    key, k1 = jax.random.split(state.rng_key)
    if masked:
        key = jnp.where(due_r.any(), key, state.rng_key)
    repair = jnp.where(params.mttr > 0.0,
                       rand.exponential(k1, params.mttr), 0.0)
    on_r = jnp.clip(g.resource, 0, n_resources - 1)
    victim = ((g.status == RUNNING) | (g.status == QUEUED)) & due_r[on_r]
    state = _fail_gridlets(state, victim, n_users, now, params)
    state = replace(
        state, rng_key=key,
        res_up=state.res_up & ~due_r,
        next_fail=jnp.where(due_r, INF, state.next_fail),
        next_recover=jnp.where(due_r, now + repair, state.next_recover),
        fail_since=jnp.where(due_r, now, state.fail_since),
        # Reset the brokers' measurement window for the failed resource:
        # the failure wiped its in-flight progress, and a measured rate
        # of 0/elapsed would otherwise predict zero capacity forever.
        # After recovery the broker re-trusts the advertised rate, as a
        # fresh GIS registration would.
        first_dispatch=jnp.where(due_r[None, :], INF,
                                 state.first_dispatch))
    return _free_slots(state, victim & (state.slot >= 0), on_r, r_pad)


def _apply_recoveries(state, params, due_r, now, masked=False):
    """Bring the resources in ``due_r`` back up (GIS re-registration);
    the MTBF stream schedules each one's next failure.  ``masked`` as
    in :func:`_apply_failures`: bitwise no-op on an empty ``due_r``,
    with the PRNG split selected back."""
    from .types import replace
    key, k1 = jax.random.split(state.rng_key)
    if masked:
        key = jnp.where(due_r.any(), key, state.rng_key)
    uptime = rand.exponential(k1, params.mtbf)     # inf where mtbf <= 0
    return replace(
        state, rng_key=key,
        res_up=state.res_up | due_r,
        next_fail=jnp.where(due_r, now + uptime, state.next_fail),
        next_recover=jnp.where(due_r, INF, state.next_recover),
        downtime=state.downtime +
        jnp.where(due_r, now - state.fail_since, 0.0),
        fail_since=jnp.where(due_r, INF, state.fail_since),
        # The broker's cooldown blacklist keys off this stamp; -inf
        # init means a never-failed resource is never blacklisted.
        recovered_at=jnp.where(due_r, now, state.recovered_at))


def _trace_masks(params, due, n_resources):
    """Expand the due fault-trace rows into per-resource down/up masks.

    A row's target in ``0..R-1`` names a single resource; ``R + id``
    names trunk ``id`` -- every resource with ``trunk_of == id`` flips
    in the same apply (the correlated failure domain).  Rows are
    expanded independently, downs and ups separately; the caller
    applies downs first so an up and a down of the same resource at
    the same instant nets to up (deterministic tie-break).
    """
    tgt = params.fault_target
    r_idx = jnp.arange(n_resources, dtype=jnp.int32)
    hit = tgt[None, :] == r_idx[:, None]                    # [R, K]
    if params.trunk_of is not None:
        hit |= (tgt[None, :] - n_resources) == params.trunk_of[:, None]
    down_r = jnp.any(hit & (due & ~params.fault_up)[None, :], axis=1)
    up_r = jnp.any(hit & (due & params.fault_up)[None, :], axis=1)
    return down_r, up_r


def _apply_trace(state, fleet, params, due, down_r, up_r, now, n_users,
                 n_resources, r_pad):
    """Apply one batch of due fault-trace rows: scheduled downs follow
    the FAILURE semantics (residents fail-and-refund, slots freed,
    measurement window reset), scheduled ups the RECOVERY semantics
    (downtime accrual, cooldown stamp) -- but both deterministic, no
    PRNG, and the trace *owns* its targets: a trace-down clears any
    pending stochastic failure/recovery instant for the resource and a
    trace-up does not re-arm the MTBF stream (mixing trace targets
    with nonzero MTBF on the same resource is unsupported; see
    docs/ARCHITECTURE.md "Failure domains").  Every write is gated on
    the masks, so the body is a bitwise no-op on an empty ``due``
    (masked-apply contract; no cond needed on the select-free path).
    """
    from .types import replace
    g = state.g
    on_r = jnp.clip(g.resource, 0, n_resources - 1)
    eff_down = down_r & state.res_up
    victim = ((g.status == RUNNING) | (g.status == QUEUED)) & \
        down_r[on_r]
    state = _fail_gridlets(state, victim, n_users, now, params)
    state = replace(
        state,
        res_up=state.res_up & ~down_r,
        next_fail=jnp.where(down_r, INF, state.next_fail),
        next_recover=jnp.where(down_r, INF, state.next_recover),
        fail_since=jnp.where(eff_down, now, state.fail_since),
        first_dispatch=jnp.where(eff_down[None, :], INF,
                                 state.first_dispatch),
        trace_ptr=state.trace_ptr + jnp.sum(due, dtype=jnp.int32))
    state = _free_slots(state, victim & (state.slot >= 0), on_r, r_pad)
    # ups after downs: same-instant down+up of one resource nets to up
    eff_up = up_r & ~state.res_up
    return replace(
        state,
        res_up=state.res_up | up_r,
        next_recover=jnp.where(up_r, INF, state.next_recover),
        downtime=state.downtime + jnp.where(
            eff_up & jnp.isfinite(state.fail_since),
            now - state.fail_since, 0.0),
        fail_since=jnp.where(eff_up, INF, state.fail_since),
        recovered_at=jnp.where(eff_up, now, state.recovered_at))


def _admit_after_reservation(state, fleet, params, now, n_resources,
                             qrank, gate=None):
    """A reservation boundary changed the blocked-PE counts: re-admit
    queued work onto whatever space-shared capacity is now free.
    ``gate`` (the select-free path) zeroes the free-PE budget when
    False, making the admission a bitwise no-op."""
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    busy = jax.ops.segment_sum(
        (g.status == RUNNING).astype(jnp.int32), res,
        num_segments=n_resources)
    avail = fleet.num_pe - _reserved_pes(params, now, n_resources) - busy
    free_pe = jnp.where((fleet.policy == SPACE_SHARED) & state.res_up,
                        jnp.maximum(avail, 0), 0)
    if gate is not None:
        free_pe = jnp.where(gate, free_pe, 0)
    return _admit_queued(state, fleet, free_pe, now, n_resources, qrank)


# ----------------------------------------------------------------------
# Event sources (des.EventSource protocol)
# ----------------------------------------------------------------------

def _make_sources(fleet, params, n_users, ctx):
    """The engine's registered event sources, ordered by
    des.PRIORITY_ORDER.  ``ctx`` is the per-superstep scratch dict the
    built-in sources share (kernel scan outputs, event masks, the
    remaining free-PE budget); sources communicate through it only
    *outside* lax.cond branches.  To plug in a new kind, build a
    des.FnSource with a fresh K_* code and splice it into this tuple at
    its priority rank (docs/ARCHITECTURE.md walks through an example);
    ``step`` derives all index wiring (apply order, fired flags, event
    counts, trace rows) from each ``source.kind``, so splicing never
    renumbers the built-ins.  A source that batches several events per
    superstep reports them via ``ctx[("count", kind)]`` (and optionally
    a representative ``ctx[("who", kind)]`` for the trace); otherwise
    the engine counts 1 per firing.
    """
    n_resources = fleet.r

    # -- COMPLETION: the kernel scan IS the candidate computation -------
    def completion_candidates(state):
        r_pad = state.row_gridlet.shape[0]
        if "scan" not in ctx:       # the speculative path presets it
            ctx["scan"] = _scan_events(state, fleet, params,
                                       n_resources, r_pad)
        tmin = ctx["scan"][1]
        # per-ROW forecast instants: the frontier op takes the min (the
        # add is monotone, so min(t + tmin_r) == t + min(tmin_r) in f32)
        return jnp.where(tmin < _BIG, state.t + tmin, INF)

    def completion_apply(state, now):
        r_pad = state.row_gridlet.shape[0]
        completes, res = ctx["completes"], ctx["res"]
        occ_rows = ctx["scan"][3]
        state = _apply_completions(state, fleet, params, completes, now,
                                   n_resources, r_pad)
        # Freed PEs admit queued Gridlets.  Queued jobs only exist while
        # every unreserved PE is busy, so the kernel occupancy minus
        # this batch's completions is the exact busy count.
        n_comp_r = jax.ops.segment_sum(completes.astype(jnp.int32), res,
                                       num_segments=n_resources)
        ctx["n_comp_r"] = n_comp_r
        avail = fleet.num_pe - _reserved_pes(params, now, n_resources)
        free_pe = jnp.maximum(avail - (occ_rows[:n_resources] - n_comp_r),
                              0)
        free_pe = jnp.where((fleet.policy == SPACE_SHARED) & state.res_up,
                            free_pe, 0)
        ss_freed = completes & (fleet.policy[res] == SPACE_SHARED)
        # The admission only runs when a space-shared completion could
        # actually admit something: with an empty queue the admission
        # is the identity (rank BIG for everyone), so gating on
        # QUEUED.any() is result-identical.  The FCFS/SJF queue rank
        # comes from the carried queue ordering when it is still valid
        # (admissions remove rank prefixes, so it usually is) -- the
        # lexsort seed only reruns after the queue membership changed.
        pred = ss_freed.any() & (state.g.status == QUEUED).any()
        qr0, qok = ctx["qcarry"]

        if ctx.get("select_free"):
            # Masked admission: a zero free-PE budget admits nothing
            # bitwise, so no cond is needed.  The sweep micro-steps
            # additionally run sort-free -- their fire gate guarantees
            # the carried queue rank is valid whenever an admission
            # could happen (see _sweep_micro), so qr0 is used as-is;
            # the committing superstep reseeds with one unconditional
            # lexsort selected against the carry (what the cond lowers
            # to under vmap anyway).
            if ctx.get("sort_free"):
                qr_used = qr0
            else:
                qr_used = jnp.where(qok, qr0,
                                    _queue_rank(state, fleet,
                                                n_resources))
            state, admitq = _admit_queued(
                state, fleet, jnp.where(pred, free_pe, 0), now,
                n_resources, qr_used)
        else:
            def admit(s):
                qr = jax.lax.cond(
                    qok, lambda: qr0,
                    lambda: _queue_rank(s, fleet, n_resources))
                s, admitq = _admit_queued(s, fleet, free_pe, now,
                                          n_resources, qr)
                return s, admitq, qr

            state, admitq, qr_used = jax.lax.cond(
                pred, admit,
                lambda s: (s, jnp.zeros_like(completes), qr0),
                state)
        n_admit_r = jax.ops.segment_sum(
            admitq.astype(jnp.int32), res, num_segments=n_resources)
        ctx["qcarry"] = (qr_used - n_admit_r[res], qok | pred)
        ctx["free_pe"] = free_pe - n_admit_r
        ctx["newly"] = admitq
        ctx[("count", des.K_COMPLETION)] = jnp.sum(completes,
                                                   dtype=jnp.int32)
        return state

    # -- FAILURE / RECOVERY: MTBF/MTTR renewal streams ------------------
    def failure_apply(state, now):
        r_pad = state.row_gridlet.shape[0]
        due_r = jnp.isfinite(state.next_fail) & (state.next_fail <= now)
        ctx[("count", des.K_FAILURE)] = jnp.sum(due_r, dtype=jnp.int32)
        ctx[("who", des.K_FAILURE)] = jnp.argmax(due_r).astype(jnp.int32)
        # QUEUED victims leave the queue mid-rank: the carried ordering
        # no longer describes it.
        qr, qok = ctx["qcarry"]
        ctx["qcarry"] = (qr, qok & ~due_r.any())
        if ctx.get("select_free"):
            return _apply_failures(state, fleet, params, due_r, now,
                                   n_users, n_resources, r_pad,
                                   masked=True)
        return jax.lax.cond(
            due_r.any(),
            lambda s: _apply_failures(s, fleet, params, due_r, now,
                                      n_users, n_resources, r_pad),
            lambda s: s, state)

    def recovery_apply(state, now):
        due_r = jnp.isfinite(state.next_recover) & \
            (state.next_recover <= now)
        ctx[("count", des.K_RECOVERY)] = jnp.sum(due_r, dtype=jnp.int32)
        ctx[("who", des.K_RECOVERY)] = jnp.argmax(due_r).astype(jnp.int32)
        if ctx.get("select_free"):
            return _apply_recoveries(state, params, due_r, now,
                                     masked=True)
        return jax.lax.cond(
            due_r.any(),
            lambda s: _apply_recoveries(s, params, due_r, now),
            lambda s: s, state)

    # -- TRACE: replayable fault-injection schedule ---------------------
    # The deterministic twin of FAILURE/RECOVERY: a cursor walks the
    # time-sorted (time, target, up) rows; due rows expand through the
    # trunk incidence into whole failure domains.  params.fault_time is
    # None (a static gate -- an empty pytree subtree) in the default
    # configuration, which compiles the exact pre-trace program: one
    # all-inf candidate, an identity apply.
    def trace_candidates(state):
        if params.fault_time is None:
            return jnp.full((1,), INF, jnp.float32)
        k_idx = jnp.arange(params.fault_time.shape[0], dtype=jnp.int32)
        return jnp.where(k_idx >= state.trace_ptr, params.fault_time,
                         INF)

    def trace_apply(state, now):
        if params.fault_time is None:
            return state
        r_pad = state.row_gridlet.shape[0]
        k_idx = jnp.arange(params.fault_time.shape[0], dtype=jnp.int32)
        # Rows are time-sorted, so the due set is exactly the cursor's
        # contiguous prefix of instants <= now -- empty whenever the
        # source did not fire (ascending times guarantee it), which is
        # what makes the unconditional select-free application a
        # bitwise no-op.
        due = (k_idx >= state.trace_ptr) & (params.fault_time <= now)
        down_r, up_r = _trace_masks(params, due, n_resources)
        ctx[("count", des.K_TRACE)] = jnp.sum(due, dtype=jnp.int32)
        ctx[("who", des.K_TRACE)] = jnp.where(
            due.any(), params.fault_target[jnp.argmax(due)],
            -1).astype(jnp.int32)
        # QUEUED victims leave the queue mid-rank (like FAILURE); ups
        # only add capacity, which never perturbs the carried rank.
        qr, qok = ctx["qcarry"]
        ctx["qcarry"] = (qr, qok & ~down_r.any())
        if ctx.get("select_free"):
            return _apply_trace(state, fleet, params, due, down_r, up_r,
                                now, n_users, n_resources, r_pad)
        return jax.lax.cond(
            due.any(),
            lambda s: _apply_trace(s, fleet, params, due, down_r, up_r,
                                   now, n_users, n_resources, r_pad),
            lambda s: s, state)

    # -- RESERVATION: windows open/close at params.resv_* boundaries ----
    def reservation_candidates(state):
        return resv_mod.boundary_candidates(params.resv_start,
                                            params.resv_end, state.t)

    def reservation_apply(state, now):
        fired = ctx["fired_resv"]
        pred = fired & (state.g.status == QUEUED).any()
        qr0, qok = ctx["qcarry"]

        if ctx.get("select_free"):
            qr_used = jnp.where(qok, qr0,
                                _queue_rank(state, fleet, n_resources))
            state, admitq = _admit_after_reservation(
                state, fleet, params, now, n_resources, qr_used,
                gate=pred)
        else:
            def admit(s):
                qr = jax.lax.cond(
                    qok, lambda: qr0,
                    lambda: _queue_rank(s, fleet, n_resources))
                s, admitq = _admit_after_reservation(s, fleet, params,
                                                     now, n_resources,
                                                     qr)
                return s, admitq, qr

            state, admitq, qr_used = jax.lax.cond(
                pred, admit,
                lambda s: (s, jnp.zeros((s.g.n,), bool), qr0), state)
        n_admit_r = jax.ops.segment_sum(
            admitq.astype(jnp.int32),
            jnp.clip(state.g.resource, 0, n_resources - 1),
            num_segments=n_resources)
        ctx["qcarry"] = (
            qr_used - n_admit_r[jnp.clip(state.g.resource, 0,
                                         n_resources - 1)],
            qok | pred)
        ctx["newly"] = ctx["newly"] | admitq
        ctx["free_pe"] = ctx["free_pe"] - n_admit_r
        return state

    # -- MARKET / AUCTION: dynamic pricing rounds (economy layer) -------
    # Both write only SimState.price / their own next-round instant, so
    # they are naturally maskable (every write gated on `due`, False at
    # a garbage `now`) and carry NO slab-invalidation duty: the posted
    # price never enters the Fig 8 rate arithmetic, it only shifts what
    # the broker buys at its next poll.  They keep the conservative
    # default horizon (own candidates), so speculation slabs cut at
    # each round boundary and the sources fire only in committing
    # supersteps -- speculation-safe with zero micro-step changes.
    def market_candidates(state):
        return state.next_market.reshape(1)

    def market_apply(state, now):
        from .types import replace
        due = jnp.isfinite(state.next_market) & (state.next_market <= now)
        g = state.g
        res = jnp.clip(g.resource, 0, n_resources - 1)
        resident = (g.status == RUNNING) | (g.status == QUEUED)
        n_res = jax.ops.segment_sum(resident.astype(jnp.float32), res,
                                    num_segments=n_resources)
        demand = n_res / jnp.maximum(fleet.num_pe.astype(jnp.float32),
                                     1.0)
        base = jnp.asarray(fleet.cost_per_mi, jnp.float32)
        newp = econ_mod.commodity_reprice(state.price, base, demand,
                                          params.market_gain,
                                          params.price_floor,
                                          params.price_cap)
        return replace(
            state,
            price=jnp.where(due, newp, state.price),
            next_market=jnp.where(due, now + params.market_period,
                                  state.next_market))

    def auction_candidates(state):
        return state.next_auction.reshape(1)

    def auction_apply(state, now):
        from .types import replace
        due = jnp.isfinite(state.next_auction) & \
            (state.next_auction <= now)
        # Masked PRNG contract (same pattern as _apply_failures): split
        # unconditionally, select the advanced key back only when the
        # round actually fired, so a masked-off apply is bitwise
        # identity and every fired round consumes exactly one split.
        key, kbid = jax.random.split(state.auction_key)
        key = jnp.where(due, key, state.auction_key)
        base = jnp.asarray(fleet.cost_per_mi, jnp.float32)
        newp = econ_mod.auction_round(kbid, base, params.price_floor,
                                      params.price_cap)
        return replace(
            state,
            price=jnp.where(due, newp, state.price),
            next_auction=jnp.where(due, now + params.auction_period,
                                   state.next_auction),
            auction_key=key)

    # -- NETWORK: fair-share links (the [R_pad, T] transfer table) ------
    def network_candidates(state):
        # With the subsystem off the source exposes no candidates and
        # applies as the identity: analytic runs never see it.
        if not _net_on(state):
            return jnp.zeros((0,), jnp.float32)
        r_pad = state.row_gridlet.shape[0]
        if "net_scan" not in ctx:   # the horizon frontier re-enters here
            ctx["net_scan"] = _link_scan(state, params, n_resources,
                                         r_pad)
        tmin = ctx["net_scan"][1]
        # per-LINK next-transfer-completion forecast + the pending
        # network-entry instants of pre-routed future dispatches
        link_cand = jnp.where(tmin < _BIG, state.t + tmin, INF)
        pend = _pending_entries(state, params, n_resources)
        return jnp.concatenate(
            [link_cand, jnp.where(pend, state.g.t_event, INF)])

    def network_apply(state, now):
        if not _net_on(state):
            return state
        from .types import replace
        r_pad = state.row_gridlet.shape[0]
        n = state.g.n
        # (1) transfers that drained by `now` (recorded by the advance
        # pass) release their gridlet's pending instant to `now`; the
        # RETURN/ARRIVAL batches later this superstep consume them.
        due = ctx["xfer_done"]
        done_n = jnp.zeros((n,), bool).at[
            jnp.where(due, state.link_gridlet, n)].set(True, mode="drop")
        state = replace(state, g=replace(
            state.g, t_event=jnp.where(done_n, now, state.g.t_event)))
        # (2) pending entries whose network-entry instant arrived join
        # their link with the full payload as remaining bytes.
        pend = _pending_entries(state, params, n_resources) & \
            (state.g.t_event <= now)
        if ctx.get("select_free"):
            # _enqueue_transfers is a bitwise no-op on an empty mask.
            state = _enqueue_transfers(state, pend, n_resources, r_pad)
        else:
            state = jax.lax.cond(
                pend.any(),
                lambda s: _enqueue_transfers(s, pend, n_resources,
                                             r_pad),
                lambda s: s, state)
        ctx[("count", des.K_NETWORK)] = (
            jnp.sum(done_n, dtype=jnp.int32) +
            jnp.sum(pend, dtype=jnp.int32))
        ctx[("who", des.K_NETWORK)] = jnp.where(
            done_n.any(), jnp.argmax(done_n),
            jnp.argmax(pend)).astype(jnp.int32)
        return state

    # -- RETURN / ARRIVAL / CALENDAR / BROKER ---------------------------
    def return_candidates(state):
        g = state.g
        mask = g.status == RETURNING
        if _net_on(state):
            # tabled transfers are owned by the NETWORK source until
            # they drain (t_event inf while in flight, `now` once due);
            # a pending-entry return must not fire at its entry instant.
            res = jnp.clip(g.resource, 0, n_resources - 1)
            mask &= ~(network.link_tabled(g.out_bytes,
                                          params.link_baud[res]) &
                      (state.xslot < 0))
        return jnp.where(mask, g.t_event, INF)

    def return_apply(state, now):
        state, ret_due = _apply_returns(state, fleet, now, n_users,
                                        n_resources,
                                        gate=ctx.get("gate"))
        ctx[("count", des.K_RETURN)] = jnp.sum(ret_due, dtype=jnp.int32)
        ctx[("who", des.K_RETURN)] = jnp.argmax(ret_due).astype(jnp.int32)
        return state

    def arrival_candidates(state):
        g = state.g
        mask = g.status == IN_TRANSIT
        if _net_on(state):
            res = jnp.clip(g.resource, 0, n_resources - 1)
            mask &= ~(network.link_tabled(g.in_bytes,
                                          params.link_baud[res]) &
                      (state.xslot < 0))
        return jnp.where(mask, g.t_event, INF)

    def arrival_apply(state, now):
        state, arr_due, arr_run, arr_queue = _apply_arrivals(
            state, fleet, params, ctx["free_pe"], ctx["arr_pre"], now,
            n_users, n_resources,
            select_free=bool(ctx.get("select_free")))
        ctx[("count", des.K_ARRIVAL)] = jnp.sum(arr_due, dtype=jnp.int32)
        ctx[("who", des.K_ARRIVAL)] = jnp.argmax(arr_due).astype(jnp.int32)
        ctx["newly"] = ctx["newly"] | arr_run
        # New QUEUED members: the carried queue ordering is stale.
        qr, qok = ctx["qcarry"]
        ctx["qcarry"] = (qr, qok & ~arr_queue.any())
        return state

    def calendar_candidates(state):
        return calendar.next_boundary(fleet, state.t)   # per resource

    def calendar_apply(state, now):
        # The boundary itself is the event: landing a superstep on it
        # makes the piecewise-constant load integrate exactly (shares
        # are recomputed from the new load next scan).
        return state

    def broker_candidates(state):
        active, _ = _user_flags(state, params, fleet, n_users)
        # max(next_sched, t): a failure refund can re-activate a broker
        # whose poll instant already passed; never step time backwards.
        return jnp.where(active.any(),
                         jnp.maximum(state.next_sched, state.t),
                         INF).reshape(1)

    def broker_apply(state, now):
        # Pre-broker arrivals hold admission precedence over the
        # broker's zero-delay dispatches (the ARRIVAL > BROKER
        # tie-break), recorded before the dispatch batch runs.
        g = state.g
        ctx["arr_pre"] = (g.status == IN_TRANSIT) & (g.t_event <= now)
        pre_transit = g.status == IN_TRANSIT
        if ctx.get("select_free"):
            # The broker's full Fig 20 cycle is not naturally maskable
            # (measurement smoothing, next_sched bumps): the generic
            # masked-apply fallback runs it once and selects every
            # leaf -- exactly what the cond lowers to under vmap.
            state = des.tree_select(
                ctx["fired_b"],
                broker_mod.broker_event(state, fleet, params, n_users),
                state)
        else:
            state = jax.lax.cond(
                ctx["fired_b"],
                lambda s: broker_mod.broker_event(s, fleet, params,
                                                  n_users),
                lambda s: s, state)
        if _net_on(state):
            # Re-time the broker's fresh dispatches under the network
            # subsystem: contending payloads become load-dependent
            # (t_event inf; they enter their link at the end of this
            # superstep), the rest take the analytic delay at the
            # subsystem's link_baud (0 for the instantaneous cases).
            from .types import replace
            g2 = state.g
            res = jnp.clip(g2.resource, 0, n_resources - 1)
            newt = (g2.status == IN_TRANSIT) & ~pre_transit
            baud = params.link_baud[res]
            tabled = newt & network.link_tabled(g2.in_bytes, baud)
            t_ev = jnp.where(
                tabled, INF,
                jnp.where(newt,
                          now + network.transfer_delay(g2.in_bytes, baud),
                          g2.t_event))
            state = replace(state, g=replace(g2, t_event=t_ev))
        return state

    # Speculation-safety is per source (des.EventSource horizon hooks),
    # and the micro-steps now fire the full *slab-safe* source subset --
    # COMPLETION, FAILURE, RECOVERY, NETWORK drains, RETURN -- so only
    # genuinely interfering firings cut the horizon:
    #
    # * COMPLETION and RETURN are fully speculation-safe (horizon_fn =
    #   no_interference): applying them never pulls another source's
    #   pending instant earlier.  With the network subsystem ON a
    #   completion may *create* a return transfer mid-slab; that is
    #   safe too, because the micro-steps run the same end-of-superstep
    #   link-entry pass as a commit and re-derive fair shares each
    #   micro-scan -- and the IN_TRANSIT bounds below are membership-
    #   invariant, so a new link member never invalidates them.
    # * FAILURE / RECOVERY cut only when the resource has *resident*
    #   (RUNNING | QUEUED) work to interfere with; a strike on an idle
    #   or purely-transit resource fires inside the slab through the
    #   micro-steps' failure/recovery applies.  The resident set per
    #   resource can only shrink mid-slab (admissions come from QUEUED
    #   residents; arrivals and broker dispatches cut the horizon), so
    #   a gate that holds at commit time holds slab-wide.
    # * NETWORK cuts at (a) each pending entry's network-entry instant
    #   (joining a link re-divides its fair shares) and (b) a
    #   membership-invariant lower bound on each in-flight *staging*
    #   (IN_TRANSIT) drain -- network.fastest_drain, the sole-member
    #   rate -- because a staging drain matures an ARRIVAL, which only
    #   the committing superstep applies.  Result-return (RETURNING)
    #   drains cut nothing: the micro-steps' NETWORK apply releases
    #   them and the same-superstep RETURN batch consumes them, exactly
    #   the commit path's slice.
    # * Every other source keeps the conservative default -- each
    #   candidate stream cuts at its own instant; +inf streams (an
    #   empty reservation table, a never-polling broker) cut nothing.
    def failure_horizon(state):
        return jnp.where(_residents_r(state, n_resources),
                         state.next_fail, INF)

    def recovery_horizon(state):
        return jnp.where(_residents_r(state, n_resources),
                         state.next_recover, INF)

    def network_horizon(state):
        if not _net_on(state):
            return jnp.zeros((0,), jnp.float32)
        g = state.g
        r_pad = state.row_gridlet.shape[0]
        pad = r_pad - n_resources
        baud = jnp.pad(params.link_baud, (0, pad), constant_values=1.0)
        bg = jnp.pad(params.bg_flows, (0, pad))
        gid = state.link_gridlet
        staging = (gid >= 0) & \
            (g.status[jnp.clip(gid, 0, g.n - 1)] == IN_TRANSIT)
        bound = state.t + network.fastest_drain(
            state.link_rem, baud[:, None], bg[:, None])
        pend = _pending_entries(state, params, n_resources)
        return jnp.concatenate(
            [jnp.where(staging, bound, INF).ravel(),
             jnp.where(pend, g.t_event, INF)])

    sources = (
        des.FnSource(des.K_COMPLETION, "completion",
                     completion_candidates, completion_apply,
                     horizon_fn=des.no_interference),
        des.FnSource(des.K_FAILURE, "failure",
                     lambda s: s.next_fail, failure_apply,
                     horizon_candidates_fn=failure_horizon),
        des.FnSource(des.K_RECOVERY, "recovery",
                     lambda s: s.next_recover, recovery_apply,
                     horizon_candidates_fn=recovery_horizon),
        # TRACE keeps the conservative default horizon: every pending
        # trace instant cuts the speculation horizon (exactly like a
        # per-resource FAILURE with residents would), so trace rows
        # only ever fire in committing supersteps and the speculative
        # micro-steps never need to know the source exists.
        des.FnSource(des.K_TRACE, "trace", trace_candidates,
                     trace_apply),
        des.FnSource(des.K_RESERVATION, "reservation",
                     reservation_candidates, reservation_apply),
        des.FnSource(des.K_MARKET, "market",
                     market_candidates, market_apply),
        des.FnSource(des.K_AUCTION, "auction",
                     auction_candidates, auction_apply),
        des.FnSource(des.K_NETWORK, "network", network_candidates,
                     network_apply,
                     horizon_candidates_fn=network_horizon),
        des.FnSource(des.K_RETURN, "return", return_candidates,
                     return_apply, horizon_fn=des.no_interference),
        des.FnSource(des.K_ARRIVAL, "arrival", arrival_candidates,
                     arrival_apply),
        des.FnSource(des.K_CALENDAR, "calendar_step",
                     calendar_candidates, calendar_apply),
        des.FnSource(des.K_BROKER, "broker", broker_candidates,
                     broker_apply),
    )
    # des.PRIORITY_ORDER is the single source of truth for the tie-break
    # ranking; a spliced-in source must be added there too (trace-time
    # check, free under jit).
    assert tuple(s.kind for s in sources) == des.PRIORITY_ORDER, \
        "engine sources out of sync with des.PRIORITY_ORDER"
    return sources


# ----------------------------------------------------------------------
# Main loop
# ----------------------------------------------------------------------

def _user_flags(state, params, fleet, n_users):
    """(active, finished) per user -- paper 4.2.1 step 7 semantics.

    A broker stays active only while its cheapest possible purchase --
    the user's smallest still-undispatched (CREATED or FAILED) Gridlet
    priced at the best G$/MI on the grid -- fits in the remaining
    budget.  With nothing left to dispatch the broker goes inactive
    (every further poll would be a no-op); the user is finished once
    inactive with nothing in flight.
    """
    g = state.g
    u = g.user
    not_done = (g.status != DONE).astype(jnp.int32)
    n_not_done = jax.ops.segment_sum(not_done, u, num_segments=n_users)
    inflight = ((g.status == IN_TRANSIT) | (g.status == QUEUED) |
                (g.status == RUNNING) | (g.status == RETURNING))
    n_inflight = jax.ops.segment_sum(inflight.astype(jnp.int32), u,
                                     num_segments=n_users)
    min_job_cost = broker_mod.min_affordable_cost(g, fleet, n_users,
                                                  price=state.price,
                                                  params=params)
    all_done = n_not_done == 0
    active = ((state.t < params.deadline) &
              (state.spent + min_job_cost <= params.budget) &
              ~all_done)
    finished = (all_done | ~active) & (n_inflight == 0)
    return active, finished


def _advance_jobs(state, ctx, t_next, any_event, n_resources):
    """Advance every running job analytically over [t, t_next) by the
    kernel rates in ``ctx["scan"]``; records the completion batch
    (``completes``/``res``) and its trace representative in ``ctx`` and
    moves the clock to ``t_next``."""
    from .types import replace
    g = state.g
    j_cap = state.row_gridlet.shape[1]
    rate_rj, tmin_rows, amin_rows = ctx["scan"][:3]
    res = jnp.clip(g.resource, 0, n_resources - 1)
    has_slot = (g.status == RUNNING) & (state.slot >= 0)
    rate = jnp.where(has_slot,
                     rate_rj[res, jnp.clip(state.slot, 0, j_cap - 1)], 0.0)
    rel = jnp.where(has_slot,
                    g.remaining / jnp.maximum(rate, 1e-30), INF)
    dt = jnp.maximum(t_next - state.t, 0.0)
    completes = has_slot & any_event & (state.t + rel <= t_next)
    new_remaining = jnp.where(
        completes, 0.0, jnp.maximum(g.remaining - rate * dt, 0.0))
    # Trace representative: the kernel's per-row argmin of the earliest
    # row (first row attaining the global forecast minimum).
    r_star = jnp.argmin(tmin_rows)
    who_c = state.row_gridlet[
        r_star, jnp.clip(amin_rows[r_star], 0, j_cap - 1)]
    ctx["completes"], ctx["res"] = completes, res
    ctx[("who", des.K_COMPLETION)] = who_c
    return replace(state, g=replace(g, remaining=new_remaining), t=t_next)


def _alloc_newly(state, ctx, n_resources, r_pad):
    """Allocate job slots for everything newly RUNNING this superstep.

    Re-check status: a same-instant FAILURE may have killed a gridlet
    completion_apply just admitted (it had no slot yet, so the failure
    freed nothing) -- allocating for it would leak a ghost slot."""
    newly = ctx["newly"] & (state.g.status == RUNNING)
    res_now = jnp.clip(state.g.resource, 0, n_resources - 1)
    if ctx.get("select_free"):
        # _alloc_slots is a bitwise no-op on an empty mask.
        return _alloc_slots(state, newly, res_now, n_resources, r_pad)
    return jax.lax.cond(
        newly.any(),
        lambda s: _alloc_slots(s, newly, res_now, n_resources, r_pad),
        lambda s: s, state)


def _bookkeep(state, fleet, params, n_users, kinds, counts, whos, t_next):
    """Record termination instants, trace rows and the event counter for
    one (full or speculative) superstep.  ``kinds``/``counts``/``whos``
    are aligned [S] vectors in priority order; a kind with count 0
    writes no trace row.  ``n_steps`` is NOT bumped here -- it counts
    while-loop iterations and is owned by :func:`step`.  Returns
    ``(state, finished)``: the per-user termination flags double as the
    while-loop's continue condition, carried alongside the state so the
    loop ``cond`` never re-derives :func:`_user_flags` from scratch
    (state is unchanged between here and the next cond evaluation)."""
    from .types import replace
    _, finished = _user_flags(state, params, fleet, n_users)
    term = jnp.where(finished & ~jnp.isfinite(state.term_time),
                     t_next, state.term_time)
    fired = counts > 0
    off = jnp.cumsum(fired.astype(jnp.int32)) - fired.astype(jnp.int32)
    # Out-of-range positions (unfired kinds / full trace) are dropped.
    pos = jnp.where(fired, state.n_trace + off, TRACE_LEN)
    return replace(
        state,
        term_time=term,
        n_events=state.n_events + jnp.sum(counts),
        n_trace=state.n_trace + jnp.sum(fired, dtype=jnp.int32),
        trace_t=state.trace_t.at[pos].set(t_next, mode="drop"),
        trace_kind=state.trace_kind.at[pos].set(kinds, mode="drop"),
        trace_who=state.trace_who.at[pos].set(whos, mode="drop"),
    ), finished


def step(state: SimState, fleet, params: SimParams, n_users: int):
    """One committing superstep: ask every source for its candidate
    instants, pick the earliest t* through the fused frontier pass,
    advance the Fig 8 share algebra over [t, t*), apply every source
    due at t*.  (Standalone form without the cross-iteration slab
    carry; the jitted loops run :func:`_step_commit` directly.)"""
    state, _, _, _ = _step_commit(state, fleet, params, n_users,
                                  _empty_slab(state))
    return state


def _step_commit(state: SimState, fleet, params: SimParams,
                 n_users: int, slab, select_free=False, tel=None):
    """The committing superstep.  Takes and returns the slab carry
    ``(rank f32[R_pad, J], ok bool[])`` -- the last scan's (remaining,
    tie) rank table shifted by every completion since, and whether it
    still describes the current table.  The commit's own scan is
    slab-fed exactly like the speculative micro-steps' (sort-free when
    the carry holds, one lexsort reseed when it does not), so a
    completion-dominated stretch of supersteps runs without any sort
    at all.  Returns ``(state, slab, finished, tel)`` -- the per-user
    termination flags ride in the while-loop carry so the loop
    condition never recomputes them, and ``tel`` is the telemetry ring
    carry (``None`` when telemetry is off; it never feeds back into
    the simulation arithmetic).

    ``select_free`` (static) is the sweep-engine variant: every
    ``lax.cond`` in the superstep body is replaced by a masked
    unconditional application (bitwise no-op when not due -- the
    des.py masked-apply contract), so nothing lowers to a
    both-branches select under an outer vmap.  Results are bit-for-bit
    identical."""
    from .types import replace
    n_resources = fleet.r
    r_pad = state.row_gridlet.shape[0]

    # ---- fused event frontier over every source's candidates ---------
    # (one min/mask pass replaces the per-source stacked scalar
    # reductions; the completion source's candidates come from the
    # slab-fed kernel scan, the network source's from the link scan,
    # both preset here)
    ctx = {"select_free": select_free}
    ctx["scan"], reseeded = _checked_scan(state, fleet, params,
                                          n_resources, r_pad, slab,
                                          select_free=select_free)
    ctx["qcarry"] = (slab[2], slab[3])
    state = replace(state, n_scans=state.n_scans + 1,
                    n_reseeds=state.n_reseeds +
                    reseeded.astype(jnp.int32))
    sources = _make_sources(fleet, params, n_users, ctx)
    cands = [s.candidates(state) for s in sources]
    sizes = tuple(c.shape[0] for c in cands)
    t_star, fired, _, _, _ = kernel_ops.event_frontier(
        jnp.concatenate(cands), sizes)
    any_event = jnp.isfinite(t_star)
    t_next = jnp.where(any_event, t_star, state.t)

    # ---- advance transfers + running jobs analytically over
    # [t, t_next) (transfers first: both passes read the interval start
    # from state.t, which _advance_jobs moves to t_next) --------------
    if _net_on(state):
        state = _advance_transfers(state, ctx, t_next, any_event)
    state = _advance_jobs(state, ctx, t_next, any_event, n_resources)
    # All index wiring below is derived from source.kind, so splicing a
    # new source into _make_sources never renumbers the built-ins.
    pos_of = {s.kind: i for i, s in enumerate(sources)}
    fired_t = [fired[i] for i in range(len(sources))]
    ctx["fired_resv"] = fired_t[pos_of[des.K_RESERVATION]]
    ctx["fired_b"] = fired_t[pos_of[des.K_BROKER]]

    # ---- apply every due source: priority order, except BROKER before
    # ARRIVAL (see module docstring) -----------------------------------
    order = list(range(len(sources)))
    order.remove(pos_of[des.K_BROKER])
    order.insert(order.index(pos_of[des.K_ARRIVAL]), pos_of[des.K_BROKER])
    for i in order:
        state = sources[i].apply(state, t_next)

    # ---- allocate job slots for everything newly RUNNING -------------
    state = _alloc_newly(state, ctx, n_resources, r_pad)
    # ---- transfers created this superstep enter their links ----------
    if _net_on(state):
        state = _enqueue_new_transfers(state, params, n_resources, r_pad,
                                       select_free=select_free)

    # ---- bookkeeping: termination instants, trace, counters ----------
    # Per-source event counts: a batching source reported its own count
    # through ctx[("count", kind)]; the rest count 1 per firing.
    no_who = jnp.asarray(-1, jnp.int32)
    counts = jnp.stack([
        ctx.get(("count", s.kind), fired_t[i].astype(jnp.int32))
        for i, s in enumerate(sources)])
    whos = jnp.stack([ctx.get(("who", s.kind), no_who) for s in sources])
    kinds = jnp.asarray([s.kind for s in sources], jnp.int32)
    state, finished = _bookkeep(state, fleet, params, n_users, kinds,
                                counts, whos, t_next)
    state = replace(state, n_steps=state.n_steps + 1)
    # Observability only: records the post-apply state into the metrics
    # ring.  Nothing below reads ``tel``; see core/telemetry.py.
    tel = telemetry_mod.record(tel, state, fleet, kinds, counts, t_next,
                               spec=False)

    fired_interfering = (fired_t[pos_of[des.K_FAILURE]]
                         | fired_t[pos_of[des.K_RECOVERY]]
                         | fired_t[pos_of[des.K_TRACE]]
                         | fired_t[pos_of[des.K_RESERVATION]])
    return state, _slab_after(state, ctx, ctx["scan"], fired_interfering,
                              fleet, n_resources, r_pad), finished, tel


def _empty_slab(state):
    """The no-carry slab: forces the next scan (and the next queue
    admission) through one exact lexsort reseed -- loop entry, and the
    unjitted :func:`step`.  Layout: ``(rank f32[R_pad, J], ok bool[],
    qrank i32[N], qok bool[])`` -- the job-slot table's (remaining,
    tie) rank and the FCFS/SJF queue rank, each with its own validity
    flag."""
    return (jnp.zeros(state.row_gridlet.shape, jnp.float32),
            jnp.asarray(False),
            jnp.zeros((state.g.n,), jnp.int32),
            jnp.asarray(False))


def _partition_ok(rem, tie, valid, rank, npe_e, g, pol):
    """True iff the carried rank still yields the exact Fig 8 rate
    assignment the fresh lexsort rank would.

    The rank feeds exactly one thing: the share divisor ``k + [rank >=
    msc]`` -- which of the row's jobs sit in the MaxShare set.  So the
    injected-rank scan is bit-identical to the fresh-sort scan iff the
    rank's msc-boundary partition matches the (remaining, tie) value
    order: the lexicographic max of the carried MaxShare side must lie
    strictly below the lexicographic min of the MinShare side.  That
    is two masked reductions per row -- no sorts, no scatters.  Rows
    that never consult the rank pass for free: space-shared rows
    (every job owns a PE) and rows with ``g <= P_eff`` (everyone gets
    divisor 1).  Within-partition order drift from f32 advance
    rounding (two jobs collapsing to equal remaining in "wrong" tie
    order) is harmless by construction -- equal values share a
    divisor, complete together, and never straddle a *passing*
    boundary check.
    """
    k = jnp.floor(g / jnp.maximum(npe_e, 1.0))
    extra = g - k * jnp.maximum(npe_e, 1.0)
    msc = (npe_e - extra) * k
    left = valid & (rank < msc)
    right = valid & (rank >= msc)
    rem_lo = jnp.max(jnp.where(left, rem, -_BIG), axis=1, keepdims=True)
    rem_hi = jnp.min(jnp.where(right, rem, _BIG), axis=1, keepdims=True)
    tie_lo = jnp.max(jnp.where(left & (rem == rem_lo), tie, -_BIG),
                     axis=1, keepdims=True)
    tie_hi = jnp.min(jnp.where(right & (rem == rem_hi), tie, _BIG),
                     axis=1, keepdims=True)
    row_ok = (rem_lo < rem_hi) | ((rem_lo == rem_hi) & (tie_lo < tie_hi))
    rank_free = (pol > 0.5) | (g <= npe_e)
    return jnp.all(rank_free | row_ok)


def _checked_scan(state, fleet, params, n_resources, r_pad, slab,
                  select_free=False):
    """The Fig 8 scan, slab-fed when possible: inject the carried rank
    (sort-free, purely elementwise) when it still describes the table,
    else reseed with one exact lexsort scan.  Both branches run the
    identical downstream arithmetic, so the choice never changes a
    result -- only whether a sort happens.

    ``select_free`` (static) replaces the two-branch cond with ONE
    injected scan whose rank is ``where(use, carry, fresh lexsort)``.
    Under vmap the cond lowers to a select executing BOTH full scans
    per lane; the select-free form pays one lexsort plus one
    elementwise scan -- the dominant term in the sweep engine's
    batched-throughput win.  Bit-identical: the fresh branch of
    ``event_scan_xla`` computes its rank through the very same
    ``_lexsort_rank`` before running the identical arithmetic."""
    rank_carry, slab_ok = slab[0], slab[1]
    rem, tie, eff, npe, pol, blk, row_ok = _table_inputs(
        state, fleet, params, n_resources, r_pad)
    pol_f = pol.astype(jnp.float32)[:, None]
    npe_e, valid, g = _event_kernels._row_masks(
        rem, npe.astype(jnp.float32)[:, None], pol_f, blk[:, None],
        row_ok[:, None])
    use = slab_ok & _partition_ok(rem, tie, valid, rank_carry, npe_e, g,
                                  pol_f)

    if select_free:
        rank_fresh = _event_kernels._lexsort_rank(rem, tie, valid)[0]
        rank_in = jnp.where(use, rank_carry, rank_fresh)
        return kernel_ops.event_scan(rem, eff, npe, tie=tie, policy=pol,
                                     pe_blocked=blk, row_ok=row_ok,
                                     rank=rank_in,
                                     with_rank=True), ~use

    def inject(_):
        return kernel_ops.event_scan(rem, eff, npe, tie=tie, policy=pol,
                                     pe_blocked=blk, row_ok=row_ok,
                                     rank=rank_carry, with_rank=True)

    def fresh(_):
        return kernel_ops.event_scan(rem, eff, npe, tie=tie, policy=pol,
                                     pe_blocked=blk, row_ok=row_ok,
                                     with_rank=True)

    return jax.lax.cond(use, inject, fresh, None), ~use


def _slab_after(state, ctx, scan, fired_interfering, fleet, n_resources,
                r_pad):
    """The slab carry after a superstep applied its events: survivors'
    ranks shift down by the per-row completed count (completions are a
    value-prefix, hence a rank-prefix), and the carry stays valid
    unless the table was restructured where ranks matter --
    newly-RUNNING jobs landing on a *time-shared* row (space-shared
    rows never consult the rank), or any interfering source firing
    (failure/recovery/reservation rewrite slots or row masks).  The
    queue-rank half of the carry was maintained in place by the apply
    chain (``ctx["qcarry"]``)."""
    n_comp_r = jnp.pad(ctx["n_comp_r"], (0, r_pad - n_resources))
    rank = scan[4] - n_comp_r[:, None].astype(jnp.float32)
    res = jnp.clip(state.g.resource, 0, n_resources - 1)
    ts_newly = ctx["newly"] & (fleet.policy[res] == TIME_SHARED)
    qrank, qok = ctx["qcarry"]
    return (rank, ~(ts_newly.any() | fired_interfering), qrank, qok)


def _speculative_step(state, fleet, params, n_users, t_safe, slab,
                      finished, tel=None):
    """One speculative micro-superstep of the k-step batched path.

    Applies the earliest pending batch of the *slab-safe* sources --
    COMPLETION, FAILURE, RECOVERY, NETWORK drains, RETURN -- if, and
    only if, it lies *strictly* inside the speculation horizon
    ``t_safe``.  Inside the horizon no other source (and no
    *interfering* firing of these: a strike on a resource with resident
    work, an IN_TRANSIT drain maturing an ARRIVAL, a pending link
    entry) can fire (see :func:`_speculation_horizon`), so the global
    earliest pending instant is the min over exactly these streams and
    the full superstep machinery reduces to the slice applied here --
    the resulting state, trace rows and counters are bit-for-bit what
    :func:`step` would have produced.

    ``slab = (rank, ok)`` is the precomputed-wave carry: the committing
    superstep's (remaining, tie) rank table, shifted by every departure
    since.  While it remains valid (``ok`` and :func:`_partition_ok`), the
    whole scan -- Fig 8 rates, forecasts, argmin, occupancy -- is
    recomputed **from the carried rank with zero sorts** through the
    identical arithmetic of the lexsort path (`kernels.event_scan_xla`
    with an injected rank), so micro-steps consume the slab's waves in
    rank order instead of re-ranking.  Whenever an admission or another
    structural change invalidated the carry, the micro-step falls back
    to one exact rescan and reseeds the carry from its fresh rank.
    With the network subsystem on, in-flight transfers drain at their
    fair-share rates across the micro-step's interval exactly as in a
    committing superstep, and RETURNING drains forecast inside the
    horizon fire through the NETWORK apply (their RETURN rides the same
    micro-step); only drains that would mature an ARRIVAL -- IN_TRANSIT
    stagings -- are horizon-cut and land in a commit.
    Returns ``(state, fired, slab', finished', tel')``; ``fired`` False means
    the state was returned untouched (the caller stops speculating:
    pending times only move when events apply) and ``finished`` passes
    through unchanged.
    """
    n_resources = fleet.r
    r_pad = state.row_gridlet.shape[0]
    ctx = {}
    sources = _make_sources(fleet, params, n_users, ctx)
    by_kind = {s.kind: s for s in sources}
    comp, ret = by_kind[des.K_COMPLETION], by_kind[des.K_RETURN]

    # ---- the scan: slab-fed (sort-free) or exact-rescan reseed -------
    from .types import replace as _replace
    ctx["scan"], reseeded = _checked_scan(state, fleet, params,
                                          n_resources, r_pad, slab)
    ctx["qcarry"] = (slab[2], slab[3])
    state = _replace(state, n_scans=state.n_scans + 1,
                     n_reseeds=state.n_reseeds +
                     reseeded.astype(jnp.int32))
    rank_used = ctx["scan"][4]
    if _net_on(state):
        ctx["net_scan"] = _link_scan(state, params, n_resources, r_pad)

    tmin = ctx["scan"][1].min()
    t_comp = jnp.where(tmin < _BIG, state.t + tmin, INF)
    t_next = jnp.minimum(t_comp, ret.next_time(state))
    # Slab-safe strikes and link drains fire here too: a FAILURE /
    # RECOVERY due on a resident-free resource and any RETURNING-drain
    # forecast can lie inside the horizon (their interfering cases cut
    # t_safe -- see _make_sources); IN_TRANSIT drains never pass the
    # `fire` test because their membership-invariant bound cut t_safe.
    t_next = jnp.minimum(t_next, jnp.min(state.next_fail))
    t_next = jnp.minimum(t_next, jnp.min(state.next_recover))
    if _net_on(state):
        tmin_l = ctx["net_scan"][1].min()
        t_next = jnp.minimum(
            t_next, jnp.where(tmin_l < _BIG, state.t + tmin_l, INF))
    # ~finished.all(): the while loop would have stopped -- a strike
    # stream never dries up on its own, so without this gate a slab
    # could keep firing failures past the batch=1 run's last superstep.
    fire = (jnp.isfinite(t_next) & (t_next < t_safe) &
            ~finished.all())

    def live(s):
        from .types import replace
        if _net_on(s):
            s = _advance_transfers(s, ctx, t_next, fire)
        s = _advance_jobs(s, ctx, t_next, fire, n_resources)
        # The commit path's apply order, restricted to the slab-safe
        # sources (priority order: COMP, FAIL, REC, NET, RET).
        s = comp.apply(s, t_next)     # completions + queue admissions
        s = by_kind[des.K_FAILURE].apply(s, t_next)
        s = by_kind[des.K_RECOVERY].apply(s, t_next)
        if _net_on(s):
            s = by_kind[des.K_NETWORK].apply(s, t_next)
        s = ret.apply(s, t_next)      # incl. zero-delay returns
        s = _alloc_newly(s, ctx, n_resources, r_pad)
        if _net_on(s):                # exact slice of the commit path;
            s = _enqueue_new_transfers(s, params, n_resources, r_pad)
        kind_list = [des.K_COMPLETION, des.K_FAILURE, des.K_RECOVERY]
        if _net_on(s):
            kind_list.append(des.K_NETWORK)
        kind_list.append(des.K_RETURN)
        kinds = jnp.asarray(kind_list, jnp.int32)
        counts = jnp.stack([ctx[("count", k)] for k in kind_list])
        whos = jnp.stack([ctx[("who", k)] for k in kind_list])
        s, fin = _bookkeep(s, fleet, params, n_users, kinds, counts,
                           whos, t_next)
        tel2 = telemetry_mod.record(tel, s, fleet, kinds, counts,
                                    t_next, spec=True)
        # A fired strike restructures rows/slots exactly as in a
        # commit: invalidate the rank carry so the next scan reseeds.
        interfering = (ctx[("count", des.K_FAILURE)] +
                       ctx[("count", des.K_RECOVERY)]) > 0
        slab2 = _slab_after(s, ctx, ctx["scan"], interfering,
                            fleet, n_resources, r_pad)
        return replace(s, n_spec=s.n_spec + 1), slab2, fin, tel2

    def dead(s):
        # Untouched state: the scan just performed (reseeded or not)
        # still describes the table, so hand it to the next scan.
        return s, (rank_used, jnp.asarray(True), slab[2], slab[3]), \
            finished, tel

    (state, slab_next, finished, tel) = jax.lax.cond(fire, live, dead,
                                                     state)
    return state, fire, slab_next, finished, tel


def _sweep_micro(state, fleet, params, n_users, t_safe, slab, finished,
                 alive, tel=None):
    """One **masked** speculative micro-superstep of the select-free
    sweep engine -- :func:`_speculative_step` with every branch point
    replaced by masked arithmetic, built for lanes of an outer vmap.

    The fire decision becomes a pure mask: the batch applies iff its
    instant lies strictly inside the horizon AND the slab carry is
    valid AND any space-shared queue admission it needs can ride the
    carried queue rank.  When any leg fails, every due mask below is
    forced empty (``t_eff`` collapses to ``state.t`` and the gate
    threads through the masked-apply contract), so the whole body is a
    bitwise no-op -- a *masked no-op superstep* -- and per-lane
    divergence costs zero extra work under vmap.

    Three deliberate deviations from :func:`_speculative_step`, none
    observable in results:

    * the scan always injects the carried rank (never a lexsort): a
      micro-step with an invalid carry *declines* instead of
      reseeding, and the next committing superstep -- whose select-free
      scan folds the reseed into its single injected scan -- handles
      the batch with full generality;
    * a batch needing a queue admission while the queue-rank carry is
      stale likewise declines (``slab[3] | ~pred_admit`` in the gate),
      so micro-steps never sort;
    * consequently the "how" counters (``n_steps``/``n_spec``/
      ``n_scans``/``n_reseeds``) count a different superstep packing
      than the reference whenever a carry invalidates mid-slab --
      results, traces and ``n_events`` stay bit-for-bit identical.

    Returns ``(state, fire, slab', finished', tel')``; ``fire`` doubles as
    the next micro-step's ``alive`` (once a micro-step declines, the
    state -- hence every pending instant -- is unchanged, so every
    later one declines too).
    """
    from .types import replace as _replace
    n_resources = fleet.r
    r_pad = state.row_gridlet.shape[0]
    ctx = {"select_free": True, "sort_free": True}
    sources = _make_sources(fleet, params, n_users, ctx)
    by_kind = {s.kind: s for s in sources}
    comp, ret = by_kind[des.K_COMPLETION], by_kind[des.K_RETURN]

    # ---- one unconditionally-injected, sort-free scan ----------------
    rem, tie, eff, npe, pol, blk, row_ok = _table_inputs(
        state, fleet, params, n_resources, r_pad)
    pol_f = pol.astype(jnp.float32)[:, None]
    npe_e, valid, g_row = _event_kernels._row_masks(
        rem, npe.astype(jnp.float32)[:, None], pol_f, blk[:, None],
        row_ok[:, None])
    use = slab[1] & _partition_ok(rem, tie, valid, slab[0], npe_e,
                                  g_row, pol_f)
    scan = kernel_ops.event_scan(rem, eff, npe, tie=tie, policy=pol,
                                 pe_blocked=blk, row_ok=row_ok,
                                 rank=slab[0], with_rank=True)
    ctx["scan"] = scan
    ctx["qcarry"] = (slab[2], slab[3])
    if _net_on(state):
        ctx["net_scan"] = _link_scan(state, params, n_resources, r_pad)

    tmin = scan[1].min()
    t_comp = jnp.where(tmin < _BIG, state.t + tmin, INF)
    t_next = jnp.minimum(t_comp, ret.next_time(state))
    # Slab-safe strikes and RETURNING link drains fire here too (their
    # interfering cases cut t_safe; see _make_sources / the unmasked
    # _speculative_step).
    t_next = jnp.minimum(t_next, jnp.min(state.next_fail))
    t_next = jnp.minimum(t_next, jnp.min(state.next_recover))
    if _net_on(state):
        tmin_l = ctx["net_scan"][1].min()
        t_next = jnp.minimum(
            t_next, jnp.where(tmin_l < _BIG, state.t + tmin_l, INF))
    # Preview (without applying) whether this batch would need a
    # space-shared queue admission; scan outputs are garbage when the
    # carry is invalid, but then ``use`` already kills the gate.
    g = state.g
    res = jnp.clip(g.resource, 0, n_resources - 1)
    j_cap = state.row_gridlet.shape[1]
    has_slot = (g.status == RUNNING) & (state.slot >= 0)
    rate = jnp.where(has_slot,
                     scan[0][res, jnp.clip(state.slot, 0, j_cap - 1)],
                     0.0)
    rel = jnp.where(has_slot, g.remaining / jnp.maximum(rate, 1e-30),
                    INF)
    would_c = has_slot & (state.t + rel <= t_next)
    pred_admit = ((would_c & (fleet.policy[res] == SPACE_SHARED)).any()
                  & (g.status == QUEUED).any())
    # ~finished.all() mirrors the while-loop stop: strike streams never
    # dry up, so a slab must not outlive the batch=1 run's last step.
    fire = (jnp.isfinite(t_next) & (t_next < t_safe) & use & alive &
            (slab[3] | ~pred_admit) & ~finished.all())
    t_eff = jnp.where(fire, t_next, state.t)
    ctx["gate"] = fire

    # ---- the masked slab-safe slice (COMP, FAIL, REC, NET, RET) ------
    if _net_on(state):
        state = _advance_transfers(state, ctx, t_eff, fire, gate=fire)
    state = _advance_jobs(state, ctx, t_eff, fire, n_resources)
    state = comp.apply(state, t_eff)
    state = by_kind[des.K_FAILURE].apply(state, t_eff)
    state = by_kind[des.K_RECOVERY].apply(state, t_eff)
    if _net_on(state):
        state = by_kind[des.K_NETWORK].apply(state, t_eff)
    state = ret.apply(state, t_eff)
    state = _alloc_newly(state, ctx, n_resources, r_pad)
    if _net_on(state):
        state = _enqueue_new_transfers(state, params, n_resources,
                                       r_pad, select_free=True)
    kind_list = [des.K_COMPLETION, des.K_FAILURE, des.K_RECOVERY]
    if _net_on(state):
        kind_list.append(des.K_NETWORK)
    kind_list.append(des.K_RETURN)
    kinds = jnp.asarray(kind_list, jnp.int32)
    counts = jnp.stack([ctx[("count", k)] for k in kind_list])
    whos = jnp.stack([ctx[("who", k)] for k in kind_list])
    state, finished = _bookkeep(state, fleet, params, n_users, kinds,
                                counts, whos, t_eff)
    state = _replace(
        state,
        n_spec=state.n_spec + fire.astype(jnp.int32),
        n_scans=state.n_scans + alive.astype(jnp.int32))
    # Masked recorder: a declined micro-step (``fire`` False) writes no
    # ring row -- the explicit gate, not the counts, decides (declined
    # steps are bitwise no-ops including counts, but being explicit
    # keeps the masked path's contract visible).
    tel = telemetry_mod.record(tel, state, fleet, kinds, counts, t_eff,
                               spec=True, gate=fire)

    # Slab: micro admissions are space-shared only (ts_newly is always
    # empty here), so validity persists from the input unless a strike
    # fired (it restructures rows/slots; mirror the commit's
    # invalidation so the next scan reseeds); the rank shifts by the
    # departed per-row completion counts (zero when declined).
    interfering = (ctx[("count", des.K_FAILURE)] +
                   ctx[("count", des.K_RECOVERY)]) > 0
    n_comp_r = jnp.pad(ctx["n_comp_r"], (0, r_pad - n_resources))
    slab2 = (scan[4] - n_comp_r[:, None].astype(jnp.float32),
             slab[1] & ~interfering) + ctx["qcarry"]
    return state, fire, slab2, finished, tel


def _speculation_horizon(state, fleet, params, n_users):
    """Earliest instant at which any source could interfere with the
    speculative micro-steps' slab-safe batching (COMPLETION, FAILURE /
    RECOVERY strikes on resident-free resources, RETURNING link drains,
    RETURN), derived from the registered sources' ``horizon_candidates``
    hooks (des.EventSource) through the same fused frontier pass as the
    committing superstep -- the safety condition is owned by the
    sources, not hard-coded here.

    COMPLETION and RETURN contribute no candidates (their firings never
    pull another source's pending instant earlier); FAILURE / RECOVERY
    contribute only strikes on resources with resident work; NETWORK
    contributes pending link-entry instants and membership-invariant
    lower bounds on IN_TRANSIT staging drains; every other source
    conservatively contributes its own candidate streams, each cutting
    at its own instant (+inf streams -- a zero-rate failure row, an
    empty reservation table -- cut nothing).  The derived cut is safe
    because within the slab only the slab-safe slice applies, and none
    of its firings can (re-)activate a broker, pull an interfering
    strike earlier, move a reservation or calendar boundary, or put a
    gridlet in transit.  Note the completion scan is *not* run here:
    interference candidates never need the forecast kernel.
    """
    ctx = {}
    sources = _make_sources(fleet, params, n_users, ctx)
    cands = [s.horizon_candidates(state) for s in sources]
    sizes = tuple(c.shape[0] for c in cands)
    _, _, _, t_safe, _ = kernel_ops.event_frontier(
        jnp.concatenate(cands), sizes)
    return t_safe


def step_batched(state: SimState, fleet, params: SimParams, n_users: int,
                 batch: int, slab=None, tel=None):
    """One batched while-loop iteration: a committing superstep (which
    handles whatever is due next, at full priority/tie-break
    generality) followed by up to ``batch - 1`` speculative
    COMPLETION/RETURN supersteps strictly inside the safety horizon,
    fed by the committing superstep's precomputed wave ranking (the
    slab carry -- see :func:`_speculative_step`).  Takes and returns
    ``(state, slab)`` so the ranking survives across while-loop
    iterations (returns ``(state, slab, finished, tel)`` -- the last
    superstep's per-user termination flags, which the jitted loops
    carry so the loop condition never recomputes :func:`_user_flags`,
    plus the telemetry ring carry, ``None`` when telemetry is off);
    ``slab=None`` starts without one.

    When the horizon is empty (an interfering source is due immediately
    -- dense failure scenarios, broker polls every superstep) every
    micro-step declines and the iteration degrades gracefully to the
    single-step path; ``batch=1`` skips the speculation machinery
    entirely and IS the single-step path.
    """
    if slab is None:
        slab = _empty_slab(state)
    state, slab, finished, tel = _step_commit(state, fleet, params,
                                              n_users, slab, tel=tel)
    if batch <= 1:
        return state, slab, finished, tel
    t_safe = _speculation_horizon(state, fleet, params, n_users)

    def micro(_, carry):
        s, alive, slab, fin, tel = carry

        def go(s):
            return _speculative_step(s, fleet, params, n_users, t_safe,
                                     slab, fin, tel)

        # Once a micro-step declines, every later one would too (the
        # state, hence every pending time, is unchanged): short-circuit.
        return jax.lax.cond(
            alive, go,
            lambda s: (s, jnp.asarray(False), slab, fin, tel), s)

    state, _, slab, finished, tel = jax.lax.fori_loop(
        0, batch - 1, micro,
        (state, jnp.asarray(True), slab, finished, tel))
    return state, slab, finished, tel


def step_sweep(state: SimState, fleet, params: SimParams, n_users: int,
               batch: int, slab=None, tel=None):
    """One select-free batched iteration -- :func:`step_batched` with
    every ``lax.cond`` replaced by masked arithmetic, built to live
    under an outer ``vmap`` over scenarios (the sweep engine).

    A select-free committing superstep handles whatever is due next at
    full generality, then a fixed ``batch - 1`` masked micro-supersteps
    (:func:`_sweep_micro`) are committed *unconditionally* -- a
    micro-step that must not fire executes as a bitwise no-op instead
    of branching, so under vmap no lane ever pays for another lane's
    divergence (a ``lax.cond`` would lower to a select running both
    branches for every lane).  Results are bit-for-bit identical to
    :func:`step_batched` for every batch value; only the "how"
    counters may pack supersteps differently (see
    :func:`_sweep_micro`).
    """
    if slab is None:
        slab = _empty_slab(state)
    state, slab, finished, tel = _step_commit(state, fleet, params,
                                              n_users, slab,
                                              select_free=True, tel=tel)
    if batch <= 1:
        return state, slab, finished, tel
    t_safe = _speculation_horizon(state, fleet, params, n_users)

    def micro(_, carry):
        s, alive, slab, fin, tel = carry
        return _sweep_micro(s, fleet, params, n_users, t_safe, slab,
                            fin, alive, tel)

    state, _, slab, finished, tel = jax.lax.fori_loop(
        0, batch - 1, micro,
        (state, jnp.asarray(True), slab, finished, tel))
    return state, slab, finished, tel


def _continue(state, finished, max_events):
    # Bound TOTAL supersteps (committing + speculative) so the budget
    # means the same thing for every batch value; a truncated batch=k
    # run stops within k-1 supersteps of the batch=1 run (check
    # ExperimentResult.truncated before comparing truncated runs).
    # ``finished`` is carried from the last superstep's bookkeeping
    # (ROADMAP "next constants to shrink": the loop cond no longer
    # re-derives _user_flags -- state cannot change between the
    # bookkeeping and this evaluation, so the carried flags are exact).
    return (~finished.all()) & (state.n_steps + state.n_spec < max_events)


def init_state(gridlets, fleet, n_users: int, first_sched: float = 0.0,
               max_jobs: int | None = None,
               params: SimParams | None = None,
               net_cap: int = 0) -> SimState:
    """``max_jobs`` bounds concurrently RUNNING gridlets per resource
    (the J axis of the job-slot table); defaults to the safe bound N.
    ``params`` seeds the failure stream (no failures when omitted).
    ``net_cap`` (static) sizes the fair-share transfer-slot table: T =
    net_cap transfer slots per resource link; 0 (the default) disables
    the network subsystem entirely -- transfers keep their analytic
    timestamps."""
    n = gridlets.n
    j_cap = n if max_jobs is None else min(max_jobs, n)
    t_cap = min(max(net_cap, 0), n)
    r_pad = -(-fleet.r // BLOCK_R) * BLOCK_R
    if params is None:
        key = jax.random.PRNGKey(0)
        next_fail = jnp.full((fleet.r,), INF, jnp.float32)
        next_market = jnp.asarray(INF, jnp.float32)
        next_auction = jnp.asarray(INF, jnp.float32)
        auction_key = jax.random.PRNGKey(0)
    else:
        key, k1 = jax.random.split(params.fail_key)
        next_fail = rand.exponential(k1, params.mtbf)  # inf if mtbf <= 0
        # First pricing round one full period in (inf = model off), so
        # PRICE_STATIC runs never see the sources fire and stay bitwise
        # identical to pre-pricing builds.
        next_market = jnp.where(
            (params.pricing_model == econ_mod.PRICE_COMMODITY) &
            (params.market_period > 0),
            params.market_period, INF).astype(jnp.float32)
        next_auction = jnp.where(
            (params.pricing_model == econ_mod.PRICE_AUCTION) &
            (params.auction_period > 0),
            params.auction_period, INF).astype(jnp.float32)
        auction_key = params.auction_key
    return SimState(
        t=jnp.asarray(0.0, jnp.float32),
        g=gridlets,
        slot=jnp.full((n,), -1, jnp.int32),
        row_gridlet=jnp.full((r_pad, j_cap), -1, jnp.int32),
        xslot=jnp.full((n,), -1, jnp.int32),
        link_gridlet=jnp.full((r_pad, t_cap), -1, jnp.int32),
        link_rem=jnp.zeros((r_pad, t_cap), jnp.float32),
        spent=jnp.zeros((n_users,), jnp.float32),
        done_on=jnp.zeros((n_users, fleet.r), jnp.float32),
        first_dispatch=jnp.full((n_users, fleet.r), INF, jnp.float32),
        next_sched=jnp.asarray(first_sched, jnp.float32),
        term_time=jnp.full((n_users,), INF, jnp.float32),
        res_up=jnp.ones((fleet.r,), bool),
        next_fail=next_fail,
        next_recover=jnp.full((fleet.r,), INF, jnp.float32),
        fail_since=jnp.full((fleet.r,), INF, jnp.float32),
        downtime=jnp.zeros((fleet.r,), jnp.float32),
        # -inf: t - recovered_at is +inf for a never-failed resource,
        # so the cooldown blacklist can never trigger on it.
        recovered_at=jnp.full((fleet.r,), -INF, jnp.float32),
        trace_ptr=jnp.asarray(0, jnp.int32),
        rng_key=key,
        price=jnp.broadcast_to(
            jnp.asarray(fleet.cost_per_mi, jnp.float32), (fleet.r,)),
        next_market=next_market,
        next_auction=next_auction,
        auction_key=auction_key,
        n_events=jnp.asarray(0, jnp.int32),
        n_steps=jnp.asarray(0, jnp.int32),
        n_spec=jnp.asarray(0, jnp.int32),
        n_reseeds=jnp.asarray(0, jnp.int32),
        n_scans=jnp.asarray(0, jnp.int32),
        n_trace=jnp.asarray(0, jnp.int32),
        n_failed=jnp.asarray(0, jnp.int32),
        n_resubmits=jnp.asarray(0, jnp.int32),
        overflow=jnp.asarray(0, jnp.int32),
        trace_t=jnp.full((TRACE_LEN,), INF, jnp.float32),
        trace_kind=jnp.full((TRACE_LEN,), -1, jnp.int32),
        trace_who=jnp.full((TRACE_LEN,), -1, jnp.int32),
    )


def _finalize(state: SimState, tel=None) -> SimResult:
    # Users that never started (e.g. zero budget) terminate at final t.
    term = jnp.where(jnp.isfinite(state.term_time), state.term_time,
                     state.t)
    # Resources still down at the end accrue downtime to the final t.
    downtime = state.downtime + jnp.where(
        state.res_up, 0.0, state.t - state.fail_since)
    return SimResult(gridlets=state.g, spent=state.spent, term_time=term,
                     n_events=state.n_events,
                     trace=(state.trace_t, state.trace_kind,
                            state.trace_who),
                     n_steps=state.n_steps, overflow=state.overflow,
                     n_failed=state.n_failed,
                     n_resubmits=state.n_resubmits, downtime=downtime,
                     n_spec=state.n_spec, n_reseeds=state.n_reseeds,
                     n_scans=state.n_scans, telemetry=tel)


@functools.partial(jax.jit, static_argnames=("n_users", "max_events",
                                             "max_jobs", "batch",
                                             "net_cap", "telemetry"))
def _run_jit(gridlets, fleet, params, n_users, max_events, max_jobs,
             batch, net_cap=0, telemetry=None):
    state = init_state(gridlets, fleet, n_users, max_jobs=max_jobs,
                       params=params, net_cap=net_cap)
    # The loop carry holds the slab (the last scan's rank table) and
    # the per-user termination flags next to the state, so
    # completion-dominated stretches of iterations -- committing AND
    # speculative supersteps -- run without any sort, and the loop
    # condition reads the carried flags instead of re-deriving
    # _user_flags per evaluation.  The telemetry ring rides the carry
    # as a fourth element; ``telemetry=None`` (static) makes it an
    # empty pytree node, lowering to exactly the telemetry-free loop.
    _, fin0 = _user_flags(state, params, fleet, n_users)
    tel0 = (telemetry_mod.init(telemetry, fleet.r)
            if telemetry else None)
    state, _, _, tel = jax.lax.while_loop(
        lambda c: _continue(c[0], c[2], max_events),
        lambda c: step_batched(c[0], fleet, params, n_users, batch,
                               c[1], c[3]),
        (state, _empty_slab(state), fin0, tel0))
    return _finalize(state, tel)


def run(gridlets, fleet, params: SimParams, n_users: int,
        max_events: int, max_jobs: int | None = None,
        batch: int = DEFAULT_BATCH, net_cap: int = 0,
        telemetry: int | None = None) -> SimResult:
    """Run a full experiment: broker-driven scheduling + execution.

    ``batch`` (static) is the superstep batching factor k: each
    while-loop iteration commits one superstep and then speculatively
    applies up to k-1 further COMPLETION/RETURN supersteps inside the
    safety horizon (see :func:`step_batched`).  ``batch=1`` is the
    single-step path; any k produces bit-for-bit identical results for
    runs that finish within ``max_events`` total supersteps (a
    truncated run stops within k-1 supersteps of the k=1 cut -- check
    ``truncated`` before comparing).

    ``net_cap`` (static) enables the contention-aware network
    subsystem: transfers with positive payloads over finite links
    fair-share each resource's ``params.link_baud`` instead of taking
    the analytic bytes/baud delay, with up to ``net_cap`` concurrent
    transfers per link (0 = analytic links, the default).

    ``telemetry`` (static) enables the observability ring: a positive
    capacity records one metrics row per committed superstep into
    ``SimResult.telemetry`` (see :mod:`repro.core.telemetry`).  The
    ring is a separate loop carry that never feeds back into the
    simulation -- results are bitwise identical with it on or off, and
    ``telemetry=None`` compiles to exactly the telemetry-free program.
    """
    return _run_jit(gridlets, fleet, params, n_users, max_events,
                    max_jobs, batch, net_cap, telemetry)


def run_inner(gridlets, fleet, params: SimParams, n_users: int,
              max_events: int, max_jobs: int | None = None,
              batch: int = 1, net_cap: int = 0,
              telemetry: int | None = None) -> SimResult:
    """Unjitted variant for use under an outer vmap/jit (sweep).

    ``batch`` defaults to 1 here: under vmap the speculative path's
    conditionals lower to selects that evaluate both branches, so
    batching saves no work for swept grids (results stay identical
    either way).
    """
    state = init_state(gridlets, fleet, n_users, max_jobs=max_jobs,
                       params=params, net_cap=net_cap)
    _, fin0 = _user_flags(state, params, fleet, n_users)
    tel0 = (telemetry_mod.init(telemetry, fleet.r)
            if telemetry else None)
    state, _, _, tel = jax.lax.while_loop(
        lambda c: _continue(c[0], c[2], max_events),
        lambda c: step_batched(c[0], fleet, params, n_users, batch,
                               c[1], c[3]),
        (state, _empty_slab(state), fin0, tel0))
    return _finalize(state, tel)


def run_sweep(gridlets, fleet, params: SimParams, n_users: int,
              max_events: int, max_jobs: int | None = None,
              batch: int = DEFAULT_BATCH, net_cap: int = 0,
              telemetry: int | None = None) -> SimResult:
    """Unjitted select-free variant for use under an outer vmap/jit --
    the sweep engine (see :func:`step_sweep`).

    Where :func:`run_inner` pins ``batch=1`` because the speculative
    path's conds lower to both-branch selects under vmap, this loop is
    select-free by construction: ``batch`` defaults to the full
    ``DEFAULT_BATCH`` and each lane of an outer vmap pays only for the
    work it actually commits.  Results are bit-for-bit identical to
    :func:`run_inner` / :func:`run` (asserted by
    tests/test_sweep_engine.py); the "how" counters (``n_steps``/
    ``n_spec``/``n_scans``/``n_reseeds``) may pack the same events into
    supersteps differently.
    """
    state = init_state(gridlets, fleet, n_users, max_jobs=max_jobs,
                       params=params, net_cap=net_cap)
    _, fin0 = _user_flags(state, params, fleet, n_users)
    tel0 = (telemetry_mod.init(telemetry, fleet.r)
            if telemetry else None)
    state, _, _, tel = jax.lax.while_loop(
        lambda c: _continue(c[0], c[2], max_events),
        lambda c: step_sweep(c[0], fleet, params, n_users, batch, c[1],
                             c[3]),
        (state, _empty_slab(state), fin0, tel0))
    return _finalize(state, tel)


# ----------------------------------------------------------------------
# Lane-batched sweep loop: the scenario axis INSIDE the while loop
# ----------------------------------------------------------------------

def _tree_where(pred, new, old):
    """Per-lane select over whole pytrees: ``pred`` is bool[L], every
    leaf carries a leading lane axis.  The freeze step of the
    lane-batched loop -- exactly the select ``vmap`` inserts around a
    lifted ``while_loop`` body, written out by hand."""
    def sel(a, b):
        return jnp.where(pred.reshape(pred.shape + (1,) * (a.ndim - 1)),
                         a, b)
    return jax.tree_util.tree_map(sel, new, old)


def _commit_lanes(state, fleet, params, n_users, slab, tel=None):
    """The select-free committing superstep over a whole lane batch --
    :func:`_step_commit` with the scenario axis *inside* the step, so
    expensive bodies that most supersteps do not need run under a real
    scalar ``lax.cond`` on an any-lane predicate instead of
    unconditionally per lane:

    * the rank reseed lexsort (the single most expensive commit term)
      runs only when some lane's slab carry actually went stale;
    * FAILURE/RECOVERY run only when some lane has a stream due;
    * RESERVATION only when some lane crossed a window boundary;
    * BROKER (the full Fig 20 cycle, which ``des.tree_select`` would
      otherwise evaluate every superstep for every lane) only when some
      lane's poll fired;
    * ARRIVAL only when some lane has an in-transit gridlet due
      (checked *post*-broker: zero-byte dispatches arrive in their
      creation superstep).

    Each skipped body is exact, not approximate: by the masked-apply
    contract (tests/test_sweep_engine.py::test_masked_apply_contract) a
    masked application with nothing due is a bitwise no-op, so skipping
    it when NO lane has anything due is the identity.  The always-hot
    pieces (the injected sort-free scan, the fused frontier, the
    analytic advances, COMPLETION and RETURN) stay vmapped over lanes.
    Under ``shard_map`` each device evaluates the predicates over *its*
    lanes only, so a shard whose lanes never poll skips polls other
    shards are paying for.  Results are bit-for-bit identical to
    :func:`_step_commit` per lane; only the "how" counters can differ.
    """
    from .types import replace
    n_resources = fleet.r
    r_pad = state.row_gridlet.shape[1]          # leaves are [L, ...]
    net = state.link_rem.shape[-1] > 0          # _net_on, lane-batched
    pos = {k: i for i, k in enumerate(des.PRIORITY_ORDER)}

    # ---- prologue (vmapped): is each lane's rank carry still valid? --
    def prologue(state, params, slab):
        rem, tie, eff, npe, pol, blk, row_ok = _table_inputs(
            state, fleet, params, n_resources, r_pad)
        pol_f = pol.astype(jnp.float32)[:, None]
        npe_e, valid, g_row = _event_kernels._row_masks(
            rem, npe.astype(jnp.float32)[:, None], pol_f, blk[:, None],
            row_ok[:, None])
        use = slab[1] & _partition_ok(rem, tie, valid, slab[0], npe_e,
                                      g_row, pol_f)
        return use, rem, tie, valid

    use, rem, tie, valid = jax.vmap(prologue)(state, params, slab)

    rank_fresh = jax.lax.cond(
        jnp.any(~use),
        lambda: jax.vmap(lambda r, t, v: _event_kernels._lexsort_rank(
            r, t, v)[0])(rem, tie, valid),
        lambda: slab[0])
    rank_in = jnp.where(use[:, None, None], slab[0], rank_fresh)

    # ---- head (vmapped): injected scan, frontier, advances,
    # COMPLETION -- every superstep needs these ------------------------
    def head(state, params, slab, rank_in, use):
        ctx = {"select_free": True}
        rem, tie, eff, npe, pol, blk, row_ok = _table_inputs(
            state, fleet, params, n_resources, r_pad)
        ctx["scan"] = kernel_ops.event_scan(
            rem, eff, npe, tie=tie, policy=pol, pe_blocked=blk,
            row_ok=row_ok, rank=rank_in, with_rank=True)
        ctx["qcarry"] = (slab[2], slab[3])
        state = replace(state, n_scans=state.n_scans + 1,
                        n_reseeds=state.n_reseeds +
                        (~use).astype(jnp.int32))
        sources = _make_sources(fleet, params, n_users, ctx)
        cands = [s.candidates(state) for s in sources]
        sizes = tuple(c.shape[0] for c in cands)
        t_star, fired, _, _, _ = kernel_ops.event_frontier(
            jnp.concatenate(cands), sizes)
        any_event = jnp.isfinite(t_star)
        t_next = jnp.where(any_event, t_star, state.t)
        if _net_on(state):
            state = _advance_transfers(state, ctx, t_next, any_event)
        state = _advance_jobs(state, ctx, t_next, any_event, n_resources)
        ctx["fired_resv"] = fired[pos[des.K_RESERVATION]]
        ctx["fired_b"] = fired[pos[des.K_BROKER]]
        state = sources[pos[des.K_COMPLETION]].apply(state, t_next)
        # The ctx keys later pieces consume, snapshotted as a pytree the
        # conds can thread (sources communicate through ctx only inside
        # one trace; across cond boundaries the pack IS the ctx).
        pack = {"scan": ctx["scan"], "qcarry": ctx["qcarry"],
                "free_pe": ctx["free_pe"], "newly": ctx["newly"],
                "n_comp_r": ctx["n_comp_r"],
                "count_comp": ctx[("count", des.K_COMPLETION)],
                "who_comp": ctx[("who", des.K_COMPLETION)]}
        if _net_on(state):
            pack["xfer_done"] = ctx["xfer_done"]
        fr_due = ((jnp.isfinite(state.next_fail) &
                   (state.next_fail <= t_next)).any() |
                  (jnp.isfinite(state.next_recover) &
                   (state.next_recover <= t_next)).any())
        return state, t_next, fired, pack, fr_due

    state, t_next, fired, pack, fr_due = jax.vmap(head)(
        state, params, slab, rank_in, use)

    def _ctx(pack, **extra):
        ctx = {"select_free": True, "scan": pack["scan"],
               "qcarry": pack["qcarry"], "free_pe": pack["free_pe"],
               "newly": pack["newly"], "n_comp_r": pack["n_comp_r"]}
        if "xfer_done" in pack:
            ctx["xfer_done"] = pack["xfer_done"]
        ctx.update(extra)
        return ctx

    zero_i = jnp.zeros(t_next.shape, jnp.int32)

    # ---- FAILURE + RECOVERY: cond on any lane having a stream due ----
    # (the due predicates are recomputed vs t_next exactly as
    # failure_apply/recovery_apply would -- COMPLETION touches neither
    # next_fail nor next_recover, so the head's snapshot is exact)
    def fr_taken(ops):
        state, params, t_next, pack = ops

        def one(state, params, t_next, pack):
            ctx = _ctx(pack)
            src = _make_sources(fleet, params, n_users, ctx)
            state = src[pos[des.K_FAILURE]].apply(state, t_next)
            state = src[pos[des.K_RECOVERY]].apply(state, t_next)
            return (state, dict(pack, qcarry=ctx["qcarry"]),
                    ctx[("count", des.K_FAILURE)],
                    ctx[("who", des.K_FAILURE)],
                    ctx[("count", des.K_RECOVERY)],
                    ctx[("who", des.K_RECOVERY)])

        return jax.vmap(one)(state, params, t_next, pack)

    def fr_skip(ops):
        state, params, t_next, pack = ops
        return state, pack, zero_i, zero_i, zero_i, zero_i

    state, pack, c_fail, w_fail, c_rec, w_rec = jax.lax.cond(
        jnp.any(fr_due), fr_taken, fr_skip,
        (state, params, t_next, pack))

    # ---- TRACE: static python gate + cond on any lane's cursor due ---
    # (no trace configured = the source is inert and the counts fall
    # through to the tail's fired-column default, which is always 0;
    # with a trace, the conservative horizon guarantees rows fire only
    # in committing supersteps -- exactly here -- and the ascending
    # fault times make the per-lane apply a bitwise no-op for lanes
    # whose cursor row is not yet due)
    if params.fault_time is not None:
        fired_tr = fired[:, pos[des.K_TRACE]]

        def trace_taken(ops):
            state, params, t_next, pack = ops

            def one(state, params, t_next, pack):
                ctx = _ctx(pack)
                src = _make_sources(fleet, params, n_users, ctx)
                state = src[pos[des.K_TRACE]].apply(state, t_next)
                return (state, dict(pack, qcarry=ctx["qcarry"]),
                        ctx[("count", des.K_TRACE)],
                        ctx[("who", des.K_TRACE)])

            return jax.vmap(one)(state, params, t_next, pack)

        def trace_skip(ops):
            state, params, t_next, pack = ops
            return state, pack, zero_i, zero_i

        state, pack, c_trace, w_trace = jax.lax.cond(
            jnp.any(fired_tr), trace_taken, trace_skip,
            (state, params, t_next, pack))

    # ---- RESERVATION: cond on any lane crossing a boundary -----------
    fired_resv = fired[:, pos[des.K_RESERVATION]]

    def resv_taken(ops):
        state, params, t_next, pack = ops

        def one(state, params, t_next, pack, f):
            ctx = _ctx(pack, fired_resv=f)
            src = _make_sources(fleet, params, n_users, ctx)
            state = src[pos[des.K_RESERVATION]].apply(state, t_next)
            return state, dict(pack, qcarry=ctx["qcarry"],
                               free_pe=ctx["free_pe"],
                               newly=ctx["newly"])

        return jax.vmap(one)(state, params, t_next, pack, fired_resv)

    state, pack = jax.lax.cond(
        jnp.any(fired_resv), resv_taken, lambda ops: (ops[0], ops[3]),
        (state, params, t_next, pack))

    # ---- MARKET + AUCTION: cond on any lane's pricing round firing ---
    # (both applies are pure functions of state + t_next with no ctx
    # traffic; their counts fall through to the tail's default wiring)
    fired_px = (fired[:, pos[des.K_MARKET]] |
                fired[:, pos[des.K_AUCTION]])

    def px_taken(ops):
        state, params, t_next = ops

        def one(state, params, t_next):
            src = _make_sources(fleet, params, n_users,
                                {"select_free": True})
            state = src[pos[des.K_MARKET]].apply(state, t_next)
            return src[pos[des.K_AUCTION]].apply(state, t_next)

        return jax.vmap(one)(state, params, t_next)

    state = jax.lax.cond(jnp.any(fired_px), px_taken,
                         lambda ops: ops[0], (state, params, t_next))

    # ---- NETWORK: static python gate (off = the source is inert) -----
    if net:
        def net_one(state, params, t_next, pack):
            ctx = _ctx(pack)
            src = _make_sources(fleet, params, n_users, ctx)
            state = src[pos[des.K_NETWORK]].apply(state, t_next)
            return (state, ctx[("count", des.K_NETWORK)],
                    ctx[("who", des.K_NETWORK)])

        state, c_net, w_net = jax.vmap(net_one)(state, params, t_next,
                                                pack)

    # ---- RETURN: always hot (it is what speculation feeds on) --------
    def ret_one(state, params, t_next, pack):
        ctx = _ctx(pack)
        src = _make_sources(fleet, params, n_users, ctx)
        state = src[pos[des.K_RETURN]].apply(state, t_next)
        return (state, ctx[("count", des.K_RETURN)],
                ctx[("who", des.K_RETURN)])

    state, c_ret, w_ret = jax.vmap(ret_one)(state, params, t_next, pack)

    # ---- BROKER: cond on any lane's poll firing ----------------------
    # (arr_pre -- the ARRIVAL > BROKER admission tie-break -- is
    # recorded lane-batched before the cond, exactly what broker_apply
    # snapshots first)
    arr_pre = ((state.g.status == IN_TRANSIT) &
               (state.g.t_event <= t_next[:, None]))
    fired_b = fired[:, pos[des.K_BROKER]]

    def broker_taken(ops):
        state, params, t_next, pack = ops

        def one(state, params, t_next, pack, f):
            ctx = _ctx(pack, fired_b=f)
            src = _make_sources(fleet, params, n_users, ctx)
            return src[pos[des.K_BROKER]].apply(state, t_next)

        return jax.vmap(one)(state, params, t_next, pack, fired_b)

    state = jax.lax.cond(
        jnp.any(fired_b), broker_taken, lambda ops: ops[0],
        (state, params, t_next, pack))

    # ---- ARRIVAL: cond on any in-transit gridlet due post-broker -----
    arr_due_any = jnp.any((state.g.status == IN_TRANSIT) &
                          (state.g.t_event <= t_next[:, None]))

    def arr_taken(ops):
        state, params, t_next, pack, pre = ops

        def one(state, params, t_next, pack, pre):
            ctx = _ctx(pack, arr_pre=pre)
            src = _make_sources(fleet, params, n_users, ctx)
            state = src[pos[des.K_ARRIVAL]].apply(state, t_next)
            return (state, dict(pack, qcarry=ctx["qcarry"],
                                newly=ctx["newly"]),
                    ctx[("count", des.K_ARRIVAL)],
                    ctx[("who", des.K_ARRIVAL)])

        return jax.vmap(one)(state, params, t_next, pack, pre)

    def arr_skip(ops):
        state, params, t_next, pack, pre = ops
        return state, pack, zero_i, zero_i

    state, pack, c_arr, w_arr = jax.lax.cond(
        arr_due_any, arr_taken, arr_skip,
        (state, params, t_next, pack, arr_pre))

    # CALENDAR applies as the identity: nothing to run.

    # ---- tail (vmapped): allocation, bookkeeping, the next slab ------
    c_by = {des.K_COMPLETION: pack["count_comp"],
            des.K_FAILURE: c_fail, des.K_RECOVERY: c_rec,
            des.K_RETURN: c_ret, des.K_ARRIVAL: c_arr}
    w_by = {des.K_COMPLETION: pack["who_comp"],
            des.K_FAILURE: w_fail, des.K_RECOVERY: w_rec,
            des.K_RETURN: w_ret, des.K_ARRIVAL: w_arr}
    if net:
        c_by[des.K_NETWORK] = c_net
        w_by[des.K_NETWORK] = w_net
    if params.fault_time is not None:
        c_by[des.K_TRACE] = c_trace
        w_by[des.K_TRACE] = w_trace
    no_who = jnp.full(t_next.shape, -1, jnp.int32)
    counts = jnp.stack(
        [c_by.get(k, fired[:, i].astype(jnp.int32))
         for i, k in enumerate(des.PRIORITY_ORDER)], axis=1)
    whos = jnp.stack([w_by.get(k, no_who)
                      for k in des.PRIORITY_ORDER], axis=1)
    fired_int = (fired[:, pos[des.K_FAILURE]]
                 | fired[:, pos[des.K_RECOVERY]]
                 | fired[:, pos[des.K_TRACE]]
                 | fired[:, pos[des.K_RESERVATION]])

    def tail(state, params, t_next, fired_int, pack, counts, whos, tel):
        ctx = _ctx(pack)
        state = _alloc_newly(state, ctx, n_resources, r_pad)
        if _net_on(state):
            state = _enqueue_new_transfers(state, params, n_resources,
                                           r_pad, select_free=True)
        kinds = jnp.asarray(des.PRIORITY_ORDER, jnp.int32)
        state, finished = _bookkeep(state, fleet, params, n_users,
                                    kinds, counts, whos, t_next)
        state = replace(state, n_steps=state.n_steps + 1)
        tel = telemetry_mod.record(tel, state, fleet, kinds, counts,
                                   t_next, spec=False)
        slab = _slab_after(state, ctx, ctx["scan"], fired_int, fleet,
                           n_resources, r_pad)
        return state, slab, finished, tel

    return jax.vmap(tail)(state, params, t_next, fired_int, pack,
                          counts, whos, tel)


def _step_sweep_lanes(state, fleet, params, n_users, batch, slab,
                      alive, tel=None):
    """One lane-batched while-loop iteration: a piece-wise committing
    superstep (:func:`_commit_lanes`) plus up to ``batch - 1``
    speculative micro-supersteps -- run in a ``while_loop`` that exits
    as soon as EVERY lane's micro declined (a declined
    :func:`_sweep_micro` is a bitwise no-op including its counters, so
    skipping the remaining iterations is exact).  ``alive`` seeds the
    per-lane micro gates so frozen (finished) lanes never count toward
    the any-lane exit test."""
    state, slab, finished, tel = _commit_lanes(state, fleet, params,
                                               n_users, slab, tel)
    if batch <= 1:
        return state, slab, finished, tel
    t_safe = jax.vmap(
        lambda s, p: _speculation_horizon(s, fleet, p, n_users))(
            state, params)

    def cond(c):
        i, _, fire, _, _, _ = c
        return (i < batch - 1) & jnp.any(fire)

    def body(c):
        i, s, fire, slab, fin, tel = c
        s, fire, slab, fin, tel = jax.vmap(
            lambda s, p, t, sl, f, a, tl: _sweep_micro(
                s, fleet, p, n_users, t, sl, f, a, tl))(
                    s, params, t_safe, slab, fin, fire, tel)
        return i + 1, s, fire, slab, fin, tel

    _, state, _, slab, finished, tel = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(0, jnp.int32), state, alive, slab, finished, tel))
    return state, slab, finished, tel


def run_sweep_lanes(gridlets, fleet, params: SimParams, n_users: int,
                    max_events: int, max_jobs: int | None = None,
                    batch: int = DEFAULT_BATCH, net_cap: int = 0,
                    telemetry: int | None = None) -> SimResult:
    """The lane-batched sweep engine: run one scenario per lane of
    ``params`` (every leaf carries a leading lane axis L, e.g. from
    ``vmap(_scenario_point)``), with the lane axis INSIDE the while
    loop rather than a vmap outside it.

    ``vmap(run_sweep)`` can never skip work a single lane needs: under
    vmap every ``lax.cond`` lowers to a both-branches select, which is
    why the select-free path exists at all -- but masked no-ops still
    *execute*.  Lifting the lane axis into the loop body restores real
    branches at the batch level: the reseed sort, the broker poll and
    the failure/reservation/arrival applies run only on iterations
    where at least one lane needs them (:func:`_commit_lanes`), and
    the speculation loop exits early once every lane declines
    (:func:`_step_sweep_lanes`).  The loop itself replicates the
    vmap-of-while lowering by hand -- body applied to every lane, then
    a per-lane freeze (:func:`_tree_where`) -- so results are
    bit-for-bit identical to ``vmap(run_sweep)`` and to the reference
    path (asserted by tests/test_sweep_engine.py); only the "how"
    counters may pack supersteps differently.

    Unjitted, like :func:`run_sweep`: callers jit (or ``shard_map``)
    around it -- see ``simulation.sweep`` / ``simulation.sweep_sharded``.
    """
    def mk(p):
        s = init_state(gridlets, fleet, n_users, max_jobs=max_jobs,
                       params=p, net_cap=net_cap)
        _, fin0 = _user_flags(s, p, fleet, n_users)
        tel0 = (telemetry_mod.init(telemetry, fleet.r)
                if telemetry else None)
        return s, _empty_slab(s), fin0, tel0

    state, slab, fin, tel = jax.vmap(mk)(params)

    def cond(c):
        state, _, fin, _ = c
        return jnp.any(jax.vmap(_continue, in_axes=(0, 0, None))(
            state, fin, max_events))

    def body(c):
        state, slab, fin, tel = c
        alive = jax.vmap(_continue, in_axes=(0, 0, None))(
            state, fin, max_events)
        s2, sl2, f2, tl2 = _step_sweep_lanes(state, fleet, params,
                                             n_users, batch, slab,
                                             alive, tel)
        return (_tree_where(alive, s2, state),
                _tree_where(alive, sl2, slab),
                _tree_where(alive, f2, fin),
                _tree_where(alive, tl2, tel))

    state, slab, fin, tel = jax.lax.while_loop(
        cond, body, (state, slab, fin, tel))
    return jax.vmap(_finalize)(state, tel)


def run_direct(gridlets, fleet, resource_idx, dispatch_time,
               max_events: int, reservations=None,
               batch: int = DEFAULT_BATCH, net_cap: int = 0,
               baud_rate=None, bg_flows=None) -> SimResult:
    """Broker-less mode: Gridlets are pre-routed into the fleet and the
    brokers stay inert -- the paper's Table 1 / Figs 9 and 12 scenario
    (arrivals straight into one resource).

    Parameters
    ----------
    gridlets : GridletBatch
        The jobs to run; status/resource/t_event are overwritten here.
    fleet : resource.Fleet
        Resource tables (policies, PEs, rates, load calendars).
    resource_idx : int or i32[N]
        Destination resource per gridlet (broadcast from a scalar).
    dispatch_time : float or f32[N]
        Instant each gridlet enters the network; it arrives after the
        input-file transfer delay at the resource's baud rate -- or,
        with the network subsystem on, after its fair share of the
        contended link has moved the payload.
    max_events : int
        Total-superstep bound (committing + speculative, not raw
        events) -- batch-independent.
    reservations : optional
        Advance-reservation windows -- a ReservationBook, an iterable of
        ``(resource, pes, start, end)`` tuples, or the 4-array table --
        blocking PE capacity exactly as in the broker-driven mode.
    batch : int, static
        Superstep batching factor k (see :func:`step_batched`); results
        are bit-for-bit identical for every k, k=1 disables speculation.
    net_cap : int, static
        Transfer slots per resource link for the contention-aware
        network subsystem; 0 (default) keeps the analytic links.
    baud_rate, bg_flows : optional
        Network-subsystem link overrides (default: ``fleet.baud_rate``
        and zero background flows); only consulted when ``net_cap > 0``.
    """
    from .types import replace
    n = gridlets.n
    r = jnp.broadcast_to(jnp.asarray(resource_idx, jnp.int32), (n,))
    t0 = jnp.broadcast_to(jnp.asarray(dispatch_time, jnp.float32), (n,))
    link_baud = fleet.baud_rate if baud_rate is None else \
        jnp.broadcast_to(jnp.asarray(baud_rate, jnp.float32), (fleet.r,))
    if net_cap:
        # Contending payloads hold their network-ENTRY instant in
        # t_event until the NETWORK source tables them at exactly t0;
        # everything else is instantaneous/never under the analytic
        # term at the subsystem's link rate.
        tabled = network.link_tabled(gridlets.in_bytes, link_baud[r])
        t_ev = jnp.where(
            tabled, t0,
            t0 + network.transfer_delay(gridlets.in_bytes, link_baud[r]))
    else:
        t_ev = t0 + network.transfer_delay(gridlets.in_bytes,
                                           fleet.baud_rate[r])
    g = replace(gridlets,
                status=jnp.full((n,), IN_TRANSIT, jnp.int32),
                resource=r, assigned=r, t_event=t_ev)
    params = default_params(jnp.asarray(-1.0), jnp.asarray(0.0),
                            jnp.asarray(0), 1, fleet.r,
                            reservations=reservations,  # brokers inert
                            link_baud=link_baud, bg_flows=bg_flows)
    return _run_jit(g, fleet, params, 1, max_events, None, batch,
                    net_cap, None)
