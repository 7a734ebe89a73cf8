"""Economic grid resource broker (paper section 4.2, Figs 18-20).

Each user owns a broker; a BROKER engine event runs every broker at once
(vectorised over users).  All per-gridlet arrays are [N] (flat over
every user's Gridlets), per-user [U], per-resource [R], and the
measurement/capacity tables [U, R].  One event performs the full Fig 20
cycle, split into the helper per step so each stage can be tested and
profiled on its own:

  ``_measure``  -- 1. resource discovery (GIS mask, intersected with the
                   engine's ``res_up`` so failed resources drop out until
                   they re-register) + trading (cost per MI, Table 2
                   metric), 2. measure-and-extrapolate the per-resource
                   job consumption rate, 3. predict per-resource job
                   capacity by the deadline,
  ``_release``  -- 4. release over-committed jobs back to the
                   unassigned queue,
  ``_assign``   -- 5. assign unassigned jobs to resources in policy
                   order (cost / time / cost-time / none optimisation)
                   under the budget constraint.  FAILED Gridlets (their
                   resource went down mid-flight; the engine refunded
                   their committed cost) re-enter here exactly like
                   CREATED ones -- this is the resubmission path,
  ``_dispatch`` -- 6. dispatch up to MaxGridletPerPE * num_pe staged
                   jobs per resource, committing their exact processing
                   cost against the budget (a resubmitted Gridlet is
                   billed again only here, so a failure never double
                   bills; ``SimState.n_resubmits`` counts these).

The broker reads only the flat GridletBatch arrays plus the engine's
``done_on`` counters; it never touches the engine's resource-major
job-slot table (a Gridlet's slot column is an engine implementation
detail), which is what lets one broker event run inside a superstep at
any point after completions and returns have been applied.  BROKER is
the lowest-priority event kind in the engine's COMPLETION > FAILURE >
RECOVERY > RESERVATION > MARKET > AUCTION > NETWORK > RETURN > ARRIVAL >
CALENDAR_STEP > BROKER tie-break: at an equal timestamp the broker
observes every other batch's effects -- including same-instant pricing
rounds, so the trading metric below always reads fresh posted prices.

The measurement in step 2 counts fractional progress of in-flight jobs so
the estimate ramps smoothly from the advertised rate to the observed share
(the paper's "recalibration"; Fig 34 discusses the stale-first-estimate
overshoot this produces under competition, which this model reproduces).

A broker stays active only while its cheapest possible purchase -- the
user's smallest still-undispatched Gridlet priced at the best G$/MI on
the grid -- fits in the remaining budget (mirrors
``engine._user_flags``); a broker with nothing left to dispatch is
inactive, because every further poll would be a no-op.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .segments import group_rank, group_prefix_sum
from .types import (CREATED, DONE, FAILED, IN_TRANSIT, INF, OPT_COST,
                    OPT_COST_TIME, OPT_NONE, OPT_TIME, QUEUED, RETURNING,
                    RUNNING, replace)
from . import calendar, network
from . import reservation as resv_mod


def _policy_keys(opt, cost_per_mi, est_rate, r_index, plan_ahead=False):
    """Composite per-resource ordering key for each optimisation mode.

    cost: cheapest G$/MI first (ties by index, paper Fig 20 step 4);
    time: fastest estimated consumption rate first;
    cost-time: cheapest first, equal-cost resources ordered fastest-first
               (the [23] variant -- same-cost pools scheduled for time);
    none: resource index order.

    ``plan_ahead`` switches cost-time to the full cs/0203020 algorithm:
    resources are partitioned into *exact* equal-cost groups (a dense
    rank of the G$/MI metric, so two resources share a group iff their
    costs are bit-equal) and each group is ordered fastest-first.  The
    legacy key approximates the same ordering with a fixed 1e-4 rate
    nudge, which can jump a near-tie cost gap; the grouped key cannot
    -- group ranks differ by >= 1 and the within-group term is < 1.
    """
    shape = est_rate.shape
    est_norm = est_rate / jnp.maximum(est_rate.max(axis=-1, keepdims=True),
                                      1e-30)
    key_cost = jnp.broadcast_to(cost_per_mi + 1e-7 * r_index, shape)
    key_time = -est_rate + 1e-7 * r_index
    key_ct_legacy = jnp.broadcast_to(cost_per_mi, shape) \
        - 1e-4 * est_norm + 1e-7 * r_index
    # Dense cost rank: #resources strictly cheaper == group id; the
    # within-group term spans [0, 0.5] + eps so it never crosses the
    # unit gap between adjacent groups.
    cost = jnp.broadcast_to(cost_per_mi, shape)
    grp = jnp.sum((cost[..., None, :] < cost[..., :, None]),
                  axis=-1).astype(jnp.float32)
    key_ct_plan = grp + (1.0 - est_norm) * 0.5 + 1e-7 * r_index
    key_cost_time = jnp.where(plan_ahead, key_ct_plan, key_ct_legacy)
    key_none = jnp.broadcast_to(r_index * 1.0, shape)
    return jnp.select(
        [opt[:, None] == OPT_COST, opt[:, None] == OPT_TIME,
         opt[:, None] == OPT_COST_TIME, opt[:, None] == OPT_NONE],
        [key_cost, key_time, key_cost_time, key_none])


def _retryable(g, params, t):
    """Dispatchable-now mask: CREATED, or FAILED with retries left in
    its budget (``params.retry_limit``) whose exponential-backoff
    instant (``g.retry_at``, stamped by engine._fail_gridlets) has
    passed.  At the default knobs (unbounded limit, zero backoff base)
    this is exactly the legacy ``CREATED | FAILED`` mask, bit for
    bit."""
    ok = (g.n_retries <= params.retry_limit) & (t >= g.retry_at)
    return (g.status == CREATED) | ((g.status == FAILED) & ok)


def _not_abandoned(g, params):
    """CREATED, or FAILED still inside its retry budget -- including
    gridlets merely *waiting out* a backoff window.  This is the
    activity mask: a backoff wait must keep the broker polling (the
    retry fires at the first poll past ``retry_at``), whereas a
    gridlet beyond ``retry_limit`` is abandoned for good and must stop
    propping the broker's activity, or the run would poll until the
    deadline."""
    within = g.n_retries <= params.retry_limit
    return (g.status == CREATED) | ((g.status == FAILED) & within)


def min_affordable_cost(g, fleet, n_users: int, price=None,
                        params=None):
    """Cheapest possible next purchase per user: the smallest
    still-undispatched (CREATED, or FAILED awaiting resubmission)
    Gridlet priced at the best G$/MI.  +inf when nothing is left to
    dispatch.  ``price`` overrides the advertised G$/MI metric with the
    grid's posted per-MI prices (SimState.price) under dynamic
    pricing.  ``params`` enables the retry budget: gridlets beyond
    ``params.retry_limit`` are abandoned and no longer count as a
    possible purchase (None keeps the legacy unbounded mask)."""
    if params is None:
        undispatched = (g.status == CREATED) | (g.status == FAILED)
    else:
        undispatched = _not_abandoned(g, params)
    min_mi = jax.ops.segment_min(
        jnp.where(undispatched, g.length_mi, INF), g.user,
        num_segments=n_users)
    per_mi = fleet.cost_per_mi if price is None else price
    return min_mi * per_mi.min()


def _measure(state, fleet, params, n_users: int):
    """Fig 20 steps 1-3: trading metrics, measured consumption rate,
    capacity by deadline.  Returns the per-event context dict."""
    g = state.g
    t = state.t
    R = fleet.r
    u_idx = g.user

    # Cooldown blacklist: a resource that recovered less than
    # ``blacklist_cooldown`` ago is dark to discovery/pricing -- a
    # flapping resource must re-earn trust before the broker commits
    # new work to it.  recovered_at inits to -inf, so at the default
    # cooldown of 0.0 no resource is ever blacklisted (bitwise-frozen
    # legacy discovery).
    blacklisted = (t - state.recovered_at) < params.blacklist_cooldown
    registered = params.registered & state.res_up & ~blacklisted
    reserved = resv_mod.active_pes(params.resv_res, params.resv_pes,
                                   params.resv_start, params.resv_end,
                                   t, R)
    eff = calendar.effective_mips(fleet, t)                      # [R]
    # Plan-ahead (cs/0203020) advertises the FULL PE count here and
    # prices the reservation windows into the capacity integral below
    # instead; the legacy reactive broker subtracts currently-reserved
    # PEs from the advertised rate (and so re-discovers each window
    # only while it is open).
    plan = params.plan_ahead
    adv_rate = eff * jnp.maximum(
        fleet.num_pe - jnp.where(plan, 0, reserved),
        0).astype(jnp.float32)                                   # MIPS
    # Trading (Table 2 metric) off the POSTED per-MI price: bitwise
    # fleet.cost_per_mi until a pricing round moves it.
    cost_per_mi = state.price                                    # [R]

    ones = jnp.ones((g.n,), jnp.float32)
    cnt_per_user = jax.ops.segment_sum(ones, u_idx, num_segments=n_users)
    mi_per_user = jax.ops.segment_sum(g.length_mi, u_idx,
                                      num_segments=n_users)
    avg_mi = mi_per_user / jnp.maximum(cnt_per_user, 1.0)        # [U]

    inflight = ((g.status == IN_TRANSIT) | (g.status == QUEUED) |
                (g.status == RUNNING) | (g.status == RETURNING))
    on_res = jnp.clip(g.resource, 0, R - 1)
    ur_res_key = u_idx * R + on_res
    frac = jnp.where(inflight, 1.0 - g.remaining / g.length_mi, 0.0)
    progress = jax.ops.segment_sum(frac, ur_res_key,
                                   num_segments=n_users * R)
    progress = progress.reshape(n_users, R) + state.done_on      # jobs-equiv

    elapsed = jnp.maximum(t - state.first_dispatch, 1e-6)        # [U,R]
    adv_jobs = adv_rate[None, :] / jnp.maximum(avg_mi[:, None], 1e-30)
    measured = progress / elapsed
    started = jnp.isfinite(state.first_dispatch) & \
        (t > state.first_dispatch + 1e-9)
    est_jobs = jnp.where(started, jnp.minimum(measured, adv_jobs), adv_jobs)
    est_jobs = jnp.where(registered[None, :], est_jobs, 0.0)     # [U,R]

    time_left = jnp.maximum(params.deadline - t, 0.0)            # [U]
    cap_legacy = jnp.floor(est_jobs * time_left[:, None]).astype(jnp.int32)

    # ---- plan-ahead capacity (cs/0203020) ----------------------------
    # (a) Reservation windows: integrate the PE-time each window blocks
    # over [t, deadline_u] and convert it to jobs-equivalent at the
    # current calendar rate -- the capacity those windows will remove
    # before the deadline, charged NOW rather than rediscovered when
    # the window opens.
    dl = params.deadline                                         # [U]
    ov = jnp.clip(jnp.minimum(params.resv_end[None, :], dl[:, None]) -
                  jnp.maximum(params.resv_start[None, :], t),
                  0.0, None)                                     # [U,K]
    onehot = (params.resv_res[None, :] ==
              jnp.arange(R, dtype=params.resv_res.dtype)[:, None])
    blocked_pe_time = jnp.einsum(
        "uk,rk->ur", params.resv_pes.astype(jnp.float32)[None, :] * ov,
        onehot.astype(jnp.float32))                              # [U,R]
    blocked_jobs = blocked_pe_time * eff[None, :] / \
        jnp.maximum(avg_mi[:, None], 1e-30)
    # (b) Link queueing: bytes already queued on each resource's link
    # bound the earliest a fresh dispatch can even START computing
    # (fastest_drain is the membership-invariant per-transfer bound),
    # so plan-ahead buys capacity only over the post-drain window.
    if state.link_rem.shape[1] > 0:
        link_delay = network.fastest_drain(
            state.link_rem[:R].sum(axis=1), params.link_baud,
            params.bg_flows)                                     # [R]
    else:
        link_delay = jnp.zeros((R,), jnp.float32)
    cap_plan = jnp.floor(jnp.maximum(
        est_jobs * jnp.maximum(time_left[:, None] - link_delay[None, :],
                               0.0) - blocked_jobs,
        0.0)).astype(jnp.int32)
    cap_jobs = jnp.where(plan, cap_plan, cap_legacy)

    active = ((t < params.deadline) &
              (state.spent + min_affordable_cost(g, fleet, n_users,
                                                 price=state.price,
                                                 params=params)
               <= params.budget))

    return dict(registered=registered, cost_per_mi=cost_per_mi,
                est_jobs=est_jobs, cap_jobs=cap_jobs, avg_mi=avg_mi,
                inflight=inflight, ur_res_key=ur_res_key, active=active)


def _release(state, ctx, params, n_users: int, R: int):
    """Fig 20 step 4: release over-committed undispatched jobs."""
    g = state.g
    u_idx = g.user
    idx = jnp.arange(g.n, dtype=jnp.int32)
    ur_key = u_idx * R + jnp.clip(g.assigned, 0, R - 1)

    committed = (g.assigned >= 0) & (g.status != DONE)
    n_committed = jax.ops.segment_sum(
        committed.astype(jnp.int32),
        jnp.where(committed, ur_key, n_users * R),
        num_segments=n_users * R + 1)[:n_users * R].reshape(n_users, R)

    undispatched = _retryable(g, params, state.t) & (g.assigned >= 0)
    rel_rank, n_undisp = group_rank(ur_key, undispatched, -idx,
                                    n_users * R)
    n_release = jnp.clip(n_committed - ctx["cap_jobs"], 0,
                         n_undisp[:n_users * R].reshape(n_users, R))
    n_release = jnp.where(ctx["active"][:, None], n_release, 0)
    release = undispatched & (rel_rank <
                              n_release.reshape(-1)[jnp.clip(ur_key, 0,
                                                             n_users * R - 1)])
    assigned = jnp.where(release, -1, g.assigned)
    return assigned, n_committed - n_release


def _assign(state, ctx, assigned, n_committed, params, n_users: int,
            R: int):
    """Fig 20 step 5: fill per-resource capacity slots with unassigned
    jobs in policy order under the budget constraint."""
    g = state.g
    u_idx = g.user
    idx = jnp.arange(g.n, dtype=jnp.int32)
    cost_per_mi = ctx["cost_per_mi"]
    registered = ctx["registered"]

    exact_cost_now = g.length_mi * cost_per_mi[jnp.clip(assigned, 0, R - 1)]
    planned = (assigned >= 0) & _retryable(g, params, state.t)
    planned_cost = jax.ops.segment_sum(
        jnp.where(planned, exact_cost_now, 0.0), u_idx,
        num_segments=n_users)
    budget_left = jnp.maximum(params.budget - state.spent - planned_cost,
                              0.0)

    keys = _policy_keys(params.opt, cost_per_mi[None, :], ctx["est_jobs"],
                        jnp.arange(R, dtype=jnp.float32)[None, :],
                        plan_ahead=params.plan_ahead)
    keys = jnp.where(registered[None, :], keys, INF)
    order = jnp.argsort(keys, axis=-1)                           # [U,R]
    inv_order = jnp.zeros_like(order).at[
        jnp.arange(n_users)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(R), (n_users, R)))

    slots = jnp.maximum(ctx["cap_jobs"] - n_committed, 0)        # [U,R]
    job_cost_est = ctx["avg_mi"][:, None] * cost_per_mi[None, :]  # [U,R]

    # FAILED gridlets (engine-refunded) resubmit like fresh CREATED
    # ones -- once past their backoff window and within the retry
    # budget (_retryable; vacuous at the default knobs).
    unassigned = _retryable(g, params, state.t) & (assigned < 0)
    n_unassigned = jax.ops.segment_sum(
        unassigned.astype(jnp.int32), u_idx, num_segments=n_users)
    active = ctx["active"]

    def fill(j, carry):
        taken, budget_rem, take_at = carry
        r = order[:, j]                                          # [U]
        rows = jnp.arange(n_users)
        s = slots[rows, r]
        c = job_cost_est[rows, r]
        by_budget = jnp.floor(budget_rem / jnp.maximum(c, 1e-30))
        by_budget = jnp.clip(by_budget, 0, 2**30).astype(jnp.int32)
        n_fit = jnp.minimum(jnp.minimum(s, by_budget),
                            n_unassigned - taken)
        n_fit = jnp.where(active & registered[r], n_fit, 0)
        take_at = take_at.at[:, j].set(n_fit)
        return taken + n_fit, budget_rem - n_fit.astype(jnp.float32) * c, \
            take_at

    taken0 = jnp.zeros((n_users,), jnp.int32)
    take_at0 = jnp.zeros((n_users, R), jnp.int32)
    taken, _, take_at = jax.lax.fori_loop(
        0, R, fill, (taken0, budget_left, take_at0))
    cum_take = jnp.cumsum(take_at, axis=-1)                      # [U,R]

    una_rank, _ = group_rank(u_idx, unassigned, idx, n_users)
    k = una_rank                                                 # [N]
    cum_for_g = cum_take[u_idx]                                  # [N,R]
    j_star = jnp.sum((cum_for_g <= k[:, None]).astype(jnp.int32), axis=-1)
    gets = unassigned & (k < taken[u_idx]) & (j_star < R)
    new_assigned = jnp.where(
        gets, order[u_idx, jnp.clip(j_star, 0, R - 1)], assigned)
    return new_assigned, inv_order


def _dispatch(state, fleet, ctx, params, new_assigned, inv_order,
              n_users: int, R: int):
    """Fig 20 step 6: stage up to MaxGridletPerPE * num_pe jobs per
    resource, committing exact processing cost against the budget."""
    g = state.g
    t = state.t
    u_idx = g.user
    idx = jnp.arange(g.n, dtype=jnp.int32)
    cost_per_mi = ctx["cost_per_mi"]

    ur_key2 = u_idx * R + jnp.clip(new_assigned, 0, R - 1)
    cand = _retryable(g, params, t) & (new_assigned >= 0)
    n_inflight_ur = jax.ops.segment_sum(
        ctx["inflight"].astype(jnp.int32),
        jnp.where(ctx["inflight"], ctx["ur_res_key"], n_users * R),
        num_segments=n_users * R + 1)[:n_users * R].reshape(n_users, R)
    limit = params.max_gridlet_per_pe * fleet.num_pe[None, :]
    disp_slots = jnp.maximum(limit - n_inflight_ur, 0)           # [U,R]
    disp_rank, _ = group_rank(ur_key2, cand, idx, n_users * R)
    eligible = cand & (disp_rank < disp_slots.reshape(-1)[
        jnp.clip(ur_key2, 0, n_users * R - 1)])
    eligible = eligible & ctx["active"][u_idx] & ctx["registered"][
        jnp.clip(new_assigned, 0, R - 1)]

    exact_cost = g.length_mi * cost_per_mi[jnp.clip(new_assigned, 0, R - 1)]
    disp_order_key = (inv_order[u_idx, jnp.clip(new_assigned, 0, R - 1)]
                      .astype(jnp.float32) * (g.n + 1.0) +
                      idx.astype(jnp.float32))
    prefix = group_prefix_sum(u_idx, eligible, disp_order_key, exact_cost,
                              n_users)
    fits = prefix + exact_cost <= (params.budget - state.spent)[u_idx]
    dispatch = eligible & fits

    r_disp = jnp.clip(new_assigned, 0, R - 1)
    in_delay = network.transfer_delay(g.in_bytes, fleet.baud_rate[r_disp])
    g2 = replace(
        g,
        assigned=new_assigned,
        status=jnp.where(dispatch, IN_TRANSIT, g.status),
        resource=jnp.where(dispatch, new_assigned, g.resource),
        t_event=jnp.where(dispatch, t + in_delay, g.t_event),
        cost=jnp.where(dispatch, exact_cost, g.cost),
        # A resubmitted FAILED gridlet restarts from scratch (a no-op
        # for CREATED ones, whose remaining is still the full length).
        remaining=jnp.where(dispatch, g.length_mi, g.remaining),
    )
    spent = state.spent + jax.ops.segment_sum(
        jnp.where(dispatch, exact_cost, 0.0), u_idx, num_segments=n_users)
    fd = jax.ops.segment_min(
        jnp.where(dispatch, t, INF),
        jnp.where(dispatch, ur_key2, n_users * R),
        num_segments=n_users * R + 1)[:n_users * R].reshape(n_users, R)
    first_dispatch = jnp.minimum(state.first_dispatch, fd)
    n_resubmits = state.n_resubmits + jnp.sum(
        dispatch & (g.status == FAILED), dtype=jnp.int32)
    return replace(state, g=g2, spent=spent,
                   first_dispatch=first_dispatch,
                   n_resubmits=n_resubmits)


def broker_event(state, fleet, params, n_users: int):
    """One full Fig 20 cycle for every broker, plus the next poll."""
    R = fleet.r
    ctx = _measure(state, fleet, params, n_users)
    assigned, n_committed = _release(state, ctx, params, n_users, R)
    new_assigned, inv_order = _assign(state, ctx, assigned, n_committed,
                                      params, n_users, R)
    state = _dispatch(state, fleet, ctx, params, new_assigned, inv_order,
                      n_users, R)

    # ---- next scheduling event (paper Fig 17 hold heuristic) ----------
    dl_left = jnp.where(ctx["active"], params.deadline - state.t, 0.0)
    period = jnp.maximum(params.sched_min_period,
                         params.sched_frac * dl_left.max())
    return replace(state, next_sched=state.t + period)
