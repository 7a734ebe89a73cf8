"""High-level experiment drivers (the paper's section-4 "recipe").

`run_experiment` = create resources + users + brokers, start the clock,
collect statistics -- one call, one jit.  `sweep` vmaps a whole grid of
(deadline, budget) scenarios, which is how the repo regenerates the
paper's Figures 21-38 in seconds instead of one simulation per point.

`Scenario` bundles the dynamic-resource knobs the pluggable event
sources consume: per-resource MTBF/MTTR failure streams, advance
reservations, and the RNG seed for the failure draws.  The default
(all-zero) scenario registers every source with nothing to do, which is
bit-for-bit identical to not registering them at all -- asserted by
tests/test_superstep.py.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import economy, engine, gridlet
from .types import DONE, OPT_COST
from .types import replace as treplace


class Scenario(NamedTuple):
    """Dynamic-resource scenario knobs (all optional).

    mtbf: per-resource mean time between failures (scalar or [R]);
        0 or None disables the failure source entirely,
    mttr: per-resource mean time to recovery; 0 or None means instant
        recovery (failures still kill, refund and resubmit the
        resource's in-flight gridlets -- zero-downtime "blips"),
    reservations: a reservation.ReservationBook, an iterable of
        (resource, pes, start, end) tuples, or the exported 4-array
        table (``reservation.maintenance`` builds full-resource
        maintenance windows in this form),
    seed: PRNG seed for the MTBF/MTTR streams,
    baud_rate: per-resource link capacity override for the
        contention-aware network subsystem (scalar or [R]; default:
        ``fleet.baud_rate``) -- consulted when ``run_experiment`` runs
        with ``net_cap > 0``,
    bg_flows: per-resource phantom background flows sharing each link
        (scalar or [R], may be fractional; default 0) -- standing
        non-grid traffic that takes its fair share of the link without
        ever completing; net mode only,
    sched_min_period: broker poll-period floor in simulation time
        (default None = the engine default 1.0, the paper's setting),
    sched_frac: broker poll period as a fraction of the remaining
        deadline (default None = the engine default 0.01).  The broker
        re-evaluates its schedule every ``max(sched_min_period,
        sched_frac * deadline_left)`` simulated seconds; coarser
        polling trades scheduling reactivity for fewer pure-poll
        supersteps and deeper speculation horizons (see
        docs/PERFORMANCE.md, "Profiling checklist"),
    policy: broker optimisation strategy override (an OPT_* code;
        default None = the ``opt`` argument of the driver call).  Makes
        the strategy a first-class scenario axis: stack Scenario-built
        params over lanes and the same sweep compares policies,
    pricing_model: "static" (default), "commodity", or "auction" (or a
        PRICE_* code) -- selects which dynamic-pricing event source
        runs (see core/economy.py),
    market_period / market_gain: commodity-market repricing period and
        demand gain (defaults: engine defaults 10.0 / 0.25),
    auction_period: sealed-bid round period (default 10.0),
    auction_seed: PRNG seed for the auction bid draws (default: the
        scenario ``seed``, so auctions are deterministic per scenario),
    plan_ahead: enable the cs/0203020 plan-ahead DBC dispatch --
        reservation windows and link queueing delay priced into the
        capacity prediction, and the exact grouped cost-time key
        (default False = the legacy reactive broker),
    trunk_of: per-resource shared-trunk id ([R] ints; -1 = private
        link only; default None = no trunks, the bitwise-frozen legacy
        topology).  Resources sharing a trunk id form one failure
        domain AND split the trunk's bandwidth (net mode),
    trunk_baud: per-trunk capacity (scalar or [n_trunks]; default
        "never binds") -- the upstream WAN segment's fair share caps
        every member transfer's rate at ``trunk_baud / (M + trunk_bg)``
        with M the total resident transfers across the trunk,
    trunk_bg: per-trunk phantom background flows (scalar or
        [n_trunks]; default 0),
    fault_trace: replayable fault-injection schedule -- an iterable of
        ``(time, target, up)`` rows or an equivalent [K, 3] array;
        ``target`` is a resource index (0..R-1) or ``R + trunk_id`` to
        hit a whole trunk (every incident resource fails/recovers in
        one superstep).  ``up=0`` fails the target (in-flight gridlets
        refunded and resubmitted), ``up=1`` brings it back.  Rows are
        applied in time order; default None = no injection,
    retry_limit: max per-gridlet failure-resubmission count before the
        broker abandons it (default: unlimited, the legacy behaviour),
    backoff_base: exponential-backoff base delay after a failure; a
        gridlet's n-th failure blocks re-dispatch until
        ``t_fail + backoff_base * 2**(n-1)`` (default 0 = immediate),
    blacklist_cooldown: how long the broker shuns a freshly recovered
        resource (default 0 = dispatch immediately on recovery).
    """
    mtbf: Any = None
    mttr: Any = None
    reservations: Any = None
    seed: int = 0
    baud_rate: Any = None
    bg_flows: Any = None
    sched_min_period: Any = None
    sched_frac: Any = None
    policy: Any = None
    pricing_model: Any = None
    market_period: Any = None
    market_gain: Any = None
    auction_period: Any = None
    auction_seed: Any = None
    plan_ahead: Any = None
    trunk_of: Any = None
    trunk_baud: Any = None
    trunk_bg: Any = None
    fault_trace: Any = None
    retry_limit: Any = None
    backoff_base: Any = None
    blacklist_cooldown: Any = None


class ExperimentResult(NamedTuple):
    n_done: jax.Array        # f32[U] gridlets completed per user
    spent: jax.Array         # f32[U] budget spent per user
    term_time: jax.Array     # f32[U] broker termination time
    time_utilization: jax.Array   # f32[U] term_time / deadline
    budget_utilization: jax.Array  # f32[U] spent / budget
    per_resource_done: jax.Array  # f32[U,R] completions by resource
    gridlets: object
    n_events: jax.Array      # i32 events applied by the engine
    n_steps: jax.Array       # i32 engine while-loop iterations
    overflow: jax.Array      # i32 job-slot allocation failures (== 0)
    n_failed: jax.Array      # i32 gridlets hit by a resource failure
    n_resubmits: jax.Array   # i32 FAILED gridlets re-dispatched
    downtime: jax.Array      # f32[R] accumulated down intervals
    truncated: jax.Array     # bool: loop hit max_events before finishing
    n_spec: jax.Array        # i32 speculative supersteps folded into
                             #     the n_steps iterations (k-step batch)
    n_reseeds: jax.Array     # i32 scans that had to re-sort the
                             #     job-slot table (slab carry miss;
                             #     the rest ran sort-free)
    n_scans: jax.Array       # i32 scans performed (committing +
                             #     speculative supersteps, incl.
                             #     declined micro-steps)
    telemetry: Any = None    # telemetry.Telemetry metrics ring when the
                             # run recorded one (observability only --
                             # never part of result identity)


def _max_events(n_gridlets: int, n_users: int, horizon: float,
                min_period: float) -> int:
    # 4 events per gridlet lifecycle + broker polls over the horizon.
    # Failure scenarios can repeat lifecycles (fail -> refund ->
    # resubmit); the horizon term usually dominates, but failure-heavy
    # runs should pass an explicit max_events and check
    # ExperimentResult.truncated.
    return int(4 * n_gridlets + horizon / max(min_period, 1e-6) + 64)


def summarize(res: engine.SimResult, params, n_users: int,
              n_resources: int,
              max_events: int | None = None) -> ExperimentResult:
    g = res.gridlets
    done = (g.status == DONE).astype(jnp.float32)
    n_done = jax.ops.segment_sum(done, g.user, num_segments=n_users)
    ur = g.user * n_resources + jnp.clip(g.resource, 0, n_resources - 1)
    per_res = jax.ops.segment_sum(
        done, ur, num_segments=n_users * n_resources
    ).reshape(n_users, n_resources)
    return ExperimentResult(
        n_done=n_done,
        spent=res.spent,
        term_time=res.term_time,
        time_utilization=res.term_time / jnp.maximum(params.deadline, 1e-30),
        budget_utilization=res.spent / jnp.maximum(params.budget, 1e-30),
        per_resource_done=per_res,
        gridlets=g,
        n_events=res.n_events,
        n_steps=res.n_steps,
        overflow=res.overflow,
        n_failed=res.n_failed,
        n_resubmits=res.n_resubmits,
        downtime=res.downtime,
        truncated=(res.n_steps + res.n_spec >= max_events
                   if max_events is not None else jnp.asarray(False)),
        n_spec=res.n_spec,
        n_reseeds=res.n_reseeds,
        n_scans=res.n_scans,
        telemetry=res.telemetry,
    )


def safe_max_jobs(gridlets_batch, params, fleet) -> int:
    """Static bound on concurrently RUNNING gridlets per resource: the
    broker stages at most max_gridlet_per_pe * num_pe in-flight jobs per
    (user, resource), so the engine's job-slot table never needs more
    than U * that many columns (capped at N)."""
    limit = int(params.max_gridlet_per_pe) * fleet.max_pe
    return min(gridlets_batch.n, params.deadline.shape[0] * limit)


def safe_net_cap(gridlets_batch, params, fleet, n_users: int = 1) -> int:
    """Static bound on concurrent transfers per resource link: the
    broker keeps at most max_gridlet_per_pe * num_pe gridlets in flight
    per (user, resource), and every one of them holds at most one
    transfer (staging or return) at a time -- so U * that many slots
    per link always suffice (capped at N, the broker-less worst case of
    everything routed onto one link)."""
    limit = int(params.max_gridlet_per_pe) * fleet.max_pe
    return min(gridlets_batch.n, n_users * limit)


def _scenario_params(fleet, deadline, budget, opt, n_users,
                     scenario: Scenario | None) -> engine.SimParams:
    s = scenario or Scenario()
    p = engine.default_params(
        deadline, budget,
        opt if s.policy is None else s.policy,
        n_users, fleet.r,
        mtbf=s.mtbf, mttr=s.mttr, reservations=s.reservations,
        fail_key=jax.random.PRNGKey(s.seed),
        link_baud=(fleet.baud_rate if s.baud_rate is None
                   else s.baud_rate),
        bg_flows=s.bg_flows,
        pricing_model=economy.as_pricing_model(s.pricing_model),
        market_period=s.market_period,
        market_gain=s.market_gain,
        auction_period=s.auction_period,
        auction_key=jax.random.PRNGKey(
            s.seed if s.auction_seed is None else s.auction_seed),
        plan_ahead=bool(s.plan_ahead) if s.plan_ahead is not None
        else False,
        trunk_of=s.trunk_of, trunk_baud=s.trunk_baud,
        trunk_bg=s.trunk_bg, fault_trace=s.fault_trace,
        retry_limit=s.retry_limit, backoff_base=s.backoff_base,
        blacklist_cooldown=s.blacklist_cooldown)
    if s.sched_min_period is not None:
        p = treplace(p, sched_min_period=jnp.asarray(
            s.sched_min_period, jnp.float32))
    if s.sched_frac is not None:
        p = treplace(p, sched_frac=jnp.asarray(s.sched_frac, jnp.float32))
    return p


def run_experiment(gridlets_batch, fleet, deadline, budget,
                   opt=OPT_COST, n_users: int = 1,
                   max_events: int | None = None,
                   scenario: Scenario | None = None,
                   batch: int = engine.DEFAULT_BATCH,
                   net_cap: int | None = 0,
                   telemetry: int | None = None) -> ExperimentResult:
    """``batch`` is the engine's k-step superstep batching factor
    (static; see engine.step_batched) -- results are bit-for-bit
    identical for every value, ``batch=1`` disables speculation.

    ``net_cap`` (static) enables the contention-aware network
    subsystem: 0 (default) keeps the analytic links, ``None`` sizes the
    transfer-slot table automatically (:func:`safe_net_cap`), any
    positive int is the explicit transfer-slot count per link.  The
    scenario's ``baud_rate``/``bg_flows`` knobs configure the links.

    ``telemetry`` (static) enables the observability metrics ring: a
    positive row capacity records per-superstep time series into
    ``ExperimentResult.telemetry`` (see :mod:`repro.core.telemetry`).
    Purely observational -- results are bitwise identical on or off."""
    params, max_events, max_jobs, net_cap = experiment_args(
        gridlets_batch, fleet, deadline, budget, opt, n_users,
        max_events, scenario, net_cap)
    res = engine.run(gridlets_batch, fleet, params, n_users, max_events,
                     max_jobs=max_jobs, batch=batch, net_cap=net_cap,
                     telemetry=telemetry)
    return summarize(res, params, n_users, fleet.r, max_events)


def experiment_args(gridlets_batch, fleet, deadline, budget, opt=OPT_COST,
                    n_users: int = 1, max_events: int | None = None,
                    scenario: Scenario | None = None,
                    net_cap: int | None = 0):
    """The engine arguments :func:`run_experiment` resolves: (params,
    max_events, max_jobs, net_cap), the last three static."""
    params = _scenario_params(fleet, deadline, budget, opt, n_users,
                              scenario)
    if net_cap is None:
        net_cap = safe_net_cap(gridlets_batch, params, fleet, n_users)
    if max_events is None:
        horizon = float(jnp.max(params.deadline)) * 2.0 + 100.0
        max_events = _max_events(gridlets_batch.n, n_users, horizon, 1.0)
    return (params, max_events,
            safe_max_jobs(gridlets_batch, params, fleet), net_cap)


def run_experiment_factors(gridlets_batch, fleet, d_factor, b_factor,
                           opt=OPT_COST, n_users: int = 1,
                           max_events: int | None = None,
                           scenario: Scenario | None = None):
    """Paper 4.2.3: derive absolute deadline/budget from D-/B-factors."""
    total_mi = gridlets_batch.length_mi.sum()
    deadline = economy.deadline_from_factor(fleet, total_mi, d_factor)
    budget = economy.budget_from_factor(fleet, total_mi, b_factor)
    return run_experiment(gridlets_batch, fleet, deadline, budget, opt,
                          n_users, max_events, scenario), (deadline, budget)


def _scenario_point(template: engine.SimParams, d, b,
                    n_users: int) -> engine.SimParams:
    """Instantiate one grid point from the sweep's params template."""
    return treplace(template,
                    deadline=jnp.broadcast_to(d, (n_users,)),
                    budget=jnp.broadcast_to(b, (n_users,)))


def _run_point(gridlets_batch, fleet, template, d, b, *, n_users,
               max_events, max_jobs, batch, net_cap, select_free):
    params = _scenario_point(template, d, b, n_users)
    runner = engine.run_sweep if select_free else engine.run_inner
    res = runner(gridlets_batch, fleet, params, n_users, max_events,
                 max_jobs, batch=batch, net_cap=net_cap)
    return summarize(res, params, n_users, fleet.r, max_events)


def _run_lanes_flat(gridlets_batch, fleet, template, dd, bb, *, n_users,
                    max_events, max_jobs, batch, net_cap):
    """Run a flat vector of scenario lanes through the lane-batched
    sweep engine (:func:`engine.run_sweep_lanes`) and summarize each.
    The lane axis lives inside the engine's while loop, so rarely-due
    superstep bodies run under real any-lane ``lax.cond``s instead of
    per-lane masked no-ops -- the batched-throughput term of the sweep
    bench."""
    p_lanes = jax.vmap(
        lambda d, b: _scenario_point(template, d, b, n_users))(dd, bb)
    res = engine.run_sweep_lanes(gridlets_batch, fleet, p_lanes, n_users,
                                 max_events, max_jobs, batch=batch,
                                 net_cap=net_cap)
    return jax.vmap(
        lambda r, p: summarize(r, p, n_users, fleet.r, max_events))(
            res, p_lanes)


@functools.partial(jax.jit, static_argnames=(
    "n_users", "max_events", "max_jobs", "batch", "net_cap",
    "select_free"))
def _sweep_grid(gridlets_batch, fleet, template, deadlines, budgets,
                n_users: int, max_events: int, max_jobs: int,
                batch: int, net_cap: int, select_free: bool):
    """Jitted deadline x budget grid runner.

    Module-level (not a per-call closure) so repeated sweeps over the
    same static shapes hit jax's jit cache instead of retracing -- the
    scenario knobs travel in ``template`` as traced arrays.

    The select-free path flattens the grid deadline-major and runs the
    lane-batched engine loop (see :func:`_run_lanes_flat`); the
    reference path keeps the plain nested vmap.
    """
    if select_free:
        d_grid, b_grid = deadlines.shape[0], budgets.shape[0]
        out = _run_lanes_flat(
            gridlets_batch, fleet, template,
            jnp.repeat(deadlines, b_grid), jnp.tile(budgets, d_grid),
            n_users=n_users, max_events=max_events, max_jobs=max_jobs,
            batch=batch, net_cap=net_cap)
        return jax.tree_util.tree_map(
            lambda x: x.reshape((d_grid, b_grid) + x.shape[1:]), out)

    def one(d, b):
        return _run_point(gridlets_batch, fleet, template, d, b,
                          n_users=n_users, max_events=max_events,
                          max_jobs=max_jobs, batch=batch,
                          net_cap=net_cap, select_free=select_free)

    f = jax.vmap(jax.vmap(one, in_axes=(None, 0)), in_axes=(0, None))
    return f(deadlines, budgets)


def _sweep_statics(gridlets_batch, fleet, deadlines, opt, n_users,
                   max_events, scenario, batch, net_cap, select_free):
    """Shared static-argument resolution for sweep / sweep_sharded."""
    if batch is None:
        batch = engine.DEFAULT_BATCH if select_free else 1
    if max_events is None:
        horizon = float(deadlines.max()) * 2.0 + 100.0
        max_events = _max_events(gridlets_batch.n, n_users, horizon, 1.0)
    template = _scenario_params(fleet, 0.0, 0.0, opt, n_users, scenario)
    max_jobs = safe_max_jobs(gridlets_batch, template, fleet)  # static
    if net_cap is None:
        net_cap = safe_net_cap(gridlets_batch, template, fleet, n_users)
    return template, max_events, max_jobs, batch, net_cap


def sweep(gridlets_batch, fleet, deadlines, budgets, opt=OPT_COST,
          n_users: int = 1, max_events: int | None = None,
          scenario: Scenario | None = None, batch: int | None = None,
          net_cap: int | None = 0, select_free: bool = True):
    """vmap over the full deadline x budget grid (paper Figs 21-24).

    deadlines: [D], budgets: [B] -> every field gains leading [D, B] dims.

    ``select_free`` (default) routes every lane through the sweep
    engine (:func:`engine.run_sweep`): supersteps are committed
    unconditionally with masked no-ops in place of every cond/fallback,
    so under vmap each lane pays only for the work it commits and
    ``batch`` defaults to ``engine.DEFAULT_BATCH``.  With
    ``select_free=False`` the reference path runs instead and ``batch``
    defaults to 1 (under vmap its ``lax.cond`` speculation lowers to
    selects that evaluate both branches, so k > 1 saves nothing).
    Results are bit-for-bit identical either way (asserted by
    tests/test_sweep_engine.py).  ``net_cap`` as in
    :func:`run_experiment` (None = auto-size).
    """
    deadlines = jnp.asarray(deadlines, jnp.float32)
    budgets = jnp.asarray(budgets, jnp.float32)
    template, max_events, max_jobs, batch, net_cap = _sweep_statics(
        gridlets_batch, fleet, deadlines, opt, n_users, max_events,
        scenario, batch, net_cap, select_free)
    return _sweep_grid(gridlets_batch, fleet, template, deadlines,
                       budgets, n_users=n_users, max_events=max_events,
                       max_jobs=max_jobs, batch=batch, net_cap=net_cap,
                       select_free=select_free)


@functools.partial(jax.jit, static_argnames=(
    "n_users", "max_events", "max_jobs", "batch", "net_cap",
    "select_free", "mesh"), donate_argnames=("dd", "bb"))
def _sweep_lanes(gridlets_batch, fleet, template, dd, bb, n_users: int,
                 max_events: int, max_jobs: int, batch: int, net_cap: int,
                 select_free: bool, mesh: Mesh | None):
    """Jitted flat-lane runner behind :func:`sweep_sharded`.

    Module-level (not a per-call closure), like :func:`_sweep_grid`:
    jit caches by function identity, so only then do repeated calls
    with the same mesh and static arguments reuse one traced and
    compiled program instead of re-tracing the whole engine.  ``mesh``
    (hashable; equal for the same devices and axis names) splits the
    lanes over its ``"s"`` axis; None runs the same lane layout on one
    device without ``shard_map``.
    """
    def run_lanes(g, f, tmpl, dd_l, bb_l):
        if select_free:
            # Lane-batched engine loop per shard: each device's
            # any-lane cond predicates see only ITS lanes, so a shard
            # whose lanes never poll/reseed skips work other shards pay
            # for -- on top of the convoy-effect win.
            return _run_lanes_flat(g, f, tmpl, dd_l, bb_l,
                                   n_users=n_users,
                                   max_events=max_events,
                                   max_jobs=max_jobs, batch=batch,
                                   net_cap=net_cap)

        def one(d, b):
            return _run_point(g, f, tmpl, d, b, n_users=n_users,
                              max_events=max_events, max_jobs=max_jobs,
                              batch=batch, net_cap=net_cap,
                              select_free=select_free)
        return jax.vmap(one)(dd_l, bb_l)

    if mesh is not None:
        run_lanes = jax.shard_map(
            run_lanes, mesh=mesh,
            in_specs=(P(), P(), P(), P("s"), P("s")),
            out_specs=P("s"), check_vma=False)
    return run_lanes(gridlets_batch, fleet, template, dd, bb)


def sweep_sharded(gridlets_batch, fleet, deadlines, budgets,
                  opt=OPT_COST, n_users: int = 1,
                  max_events: int | None = None,
                  scenario: Scenario | None = None,
                  batch: int | None = None, net_cap: int | None = 0,
                  select_free: bool = True, devices=None):
    """:func:`sweep` with the scenario axis sharded across devices.

    The [D, B] grid is flattened deadline-major into one scenario axis
    of S = D*B lanes, padded up to a device multiple, and split across
    ``devices`` (default: all of them) with ``shard_map`` -- each
    device runs its contiguous slice of lanes as an independent vmap,
    so lanes that finish early stop costing while-loop iterations on
    *other* devices (the single-vmap convoy effect).  Inputs are passed
    as replicated operands (no closure capture) and the flattened
    deadline/budget vectors are donated.  One device runs the same
    lane layout without ``shard_map``.  The program is built once per
    mesh and static shape (:func:`_sweep_lanes`).  Results are bit-for-bit
    identical to :func:`sweep` (asserted by
    tests/test_sweep_engine.py).
    """
    deadlines = jnp.asarray(deadlines, jnp.float32)
    budgets = jnp.asarray(budgets, jnp.float32)
    template, max_events, max_jobs, batch, net_cap = _sweep_statics(
        gridlets_batch, fleet, deadlines, opt, n_users, max_events,
        scenario, batch, net_cap, select_free)
    d_grid, b_grid = deadlines.shape[0], budgets.shape[0]
    s = d_grid * b_grid
    devices = jax.devices() if devices is None else list(devices)
    n_dev = max(1, len(devices))
    s_pad = -(-s // n_dev) * n_dev
    dd = jnp.repeat(deadlines, b_grid)   # deadline-major flatten [S]
    bb = jnp.tile(budgets, d_grid)
    if s_pad != s:   # pad with copies of the last lane (discarded below)
        dd = jnp.concatenate([dd, jnp.broadcast_to(dd[-1:], (s_pad - s,))])
        bb = jnp.concatenate([bb, jnp.broadcast_to(bb[-1:], (s_pad - s,))])

    mesh = Mesh(np.asarray(devices), ("s",)) if n_dev > 1 else None
    out = _sweep_lanes(gridlets_batch, fleet, template, dd, bb,
                       n_users=n_users, max_events=max_events,
                       max_jobs=max_jobs, batch=batch, net_cap=net_cap,
                       select_free=select_free, mesh=mesh)
    return jax.tree_util.tree_map(
        lambda x: x[:s].reshape((d_grid, b_grid) + x.shape[1:]), out)
