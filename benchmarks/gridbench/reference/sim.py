"""Plain reference simulator for the benchmark's cells.

An instant-at-a-time numpy simulation of GridSim's economic broker
(Buyya & Murshed, arXiv cs/0203019, sections 4.2 and 5) on a fleet of
time-shared and space-shared resources, written from the paper's
semantics and the engine's documented tie rules, with no code shared
with ``repro``.  It covers what the benchmark's cells use and nothing
more:

* resources: time-shared (paper Fig 8 PE shares) and space-shared
  (one PE per job, FCFS queue, Fig 10);
* the DBC cost-optimisation broker (Fig 20): measure and extrapolate
  each resource's job rate, release over-committed jobs, assign
  unassigned jobs cheapest resource first under the budget, and
  dispatch at most ``max_gridlet_per_pe * num_pe`` jobs in flight per
  (user, resource), polling every ``max(1, 0.01 * deadline left)``;
* static prices, zero-byte payloads (instant staging and return), no
  failures, reservations or load calendar.

Time advances from one instant to the next.  At each instant every due
event is applied in the priority order COMPLETION, RETURN, BROKER,
ARRIVAL (the broker runs before the arrivals of its own zero-delay
dispatches; arrivals due before it keep admission precedence), and
within a kind in gridlet index order.  Every quantity is held in
``dtype``: float32 as the engine states, or a lower precision for the
control.
"""
from __future__ import annotations

import numpy as np

CREATED, IN_TRANSIT, QUEUED, RUNNING, RETURNING, DONE = 0, 1, 2, 3, 4, 5
TIME_SHARED, SPACE_SHARED = 0, 1


def _rank_in_group(group, member, key, n_groups):
    """Rank of each member within its group by (key, index); -1 for
    non-members."""
    n = group.shape[0]
    idx = np.arange(n)
    gk = np.where(member, group, n_groups)
    order = np.lexsort((idx, key, gk))
    sg = gk[order]
    start = np.r_[True, sg[1:] != sg[:-1]]
    seg0 = np.maximum.accumulate(np.where(start, np.arange(n), 0))
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n) - seg0
    return np.where(member, rank, -1)


class Fleet:
    """Per-resource tables from the configuration's fleet rows
    ``[name, PEs, MIPS per PE, policy, G$ per PE-time]``."""

    def __init__(self, rows, dtype=np.float32):
        self.num_pe = np.array([r[1] for r in rows], np.int64)
        f32 = np.float32
        mips = np.array([r[2] for r in rows], f32)
        price = np.array([r[4] for r in rows], f32)
        self.policy = np.array(
            [SPACE_SHARED if r[3] == "space_shared" else TIME_SHARED
             for r in rows], np.int64)
        self.mips = mips.astype(dtype)
        self.cost_per_mi = (price / mips).astype(dtype)   # G$ per MI
        self.r = len(rows)


def simulate(length_mi, user, n_users, fleet: Fleet, deadline, budget,
             max_steps, dtype=np.float32, max_gridlet_per_pe=2,
             min_period=1.0, frac=0.01, route=None, arrive=None):
    """Run one experiment.  Returns a dict of numpy arrays: per user
    ``n_done``, ``spent``, ``term_time``; per gridlet ``status``,
    ``resource``, ``start``, ``finish``; and ``n_events`` (completions,
    returns, arrivals and broker polls applied) and ``truncated``.

    ``route``/``arrive`` pre-route every gridlet to a resource, arriving
    at the given instants, with no broker (give a negative deadline):
    the paper's Table 1 setting."""
    F = dtype
    one = F(1.0)
    zero = F(0.0)
    inf = F(np.inf)
    tiny = F(1e-30)
    L = np.asarray(length_mi).astype(F)
    u = np.asarray(user, np.int64)
    n, U, R = L.shape[0], int(n_users), fleet.r
    idx = np.arange(n)
    npe, mips, pol, cpm = fleet.num_pe, fleet.mips, fleet.policy, \
        fleet.cost_per_mi
    npe_f = npe.astype(F)
    dl = np.broadcast_to(np.asarray(deadline, np.float32).astype(F), (U,))
    bu = np.broadcast_to(np.asarray(budget, np.float32).astype(F), (U,))
    min_price = cpm.min()
    limit = max_gridlet_per_pe * npe                       # [R]

    status = np.full(n, CREATED, np.int64)
    res = np.full(n, -1, np.int64)
    assigned = np.full(n, -1, np.int64)
    rem = L.copy()
    t_ev = np.full(n, inf, F)
    if route is not None:
        status[:] = IN_TRANSIT
        res = np.asarray(route, np.int64).copy()
        assigned = res.copy()
        t_ev = np.asarray(arrive, np.float32).astype(F)
    start = np.full(n, inf, F)
    finish = np.full(n, inf, F)
    cost = np.zeros(n, F)
    spent = np.zeros(U, F)
    done_on = np.zeros((U, R), F)
    first = np.full((U, R), inf, F)
    term = np.full(U, inf, F)
    t = zero
    next_sched = zero
    n_events = 0
    steps = 0

    def seg_sum(values, keys, size):
        acc = np.zeros(size, values.dtype)
        np.add.at(acc, keys, values)
        return acc

    def user_state():
        undisp = (status == CREATED)
        min_mi = np.full(U, inf, F)
        np.minimum.at(min_mi, u, np.where(undisp, L, inf))
        min_cost = min_mi * min_price
        inflight = (status >= IN_TRANSIT) & (status <= RETURNING)
        n_not_done = np.bincount(u, status != DONE, minlength=U)
        n_inflight = np.bincount(u, inflight, minlength=U)
        all_done = n_not_done == 0
        active = (t < dl) & (spent + min_cost <= bu) & ~all_done
        return active, (all_done | ~active) & (n_inflight == 0)

    def shares():
        """Per-gridlet MI rate under Fig 8 shares (0 if not running)."""
        run = status == RUNNING
        rc = np.clip(res, 0, R - 1)
        sub = np.nonzero(run)[0]
        rank = np.full(n, -1, np.int64)
        rank[sub] = _rank_in_group(rc[sub], np.ones(sub.size, bool),
                                   np.maximum(rem[sub], tiny), R)
        g = np.bincount(rc[run], minlength=R).astype(F)    # jobs per row
        pe = np.maximum(npe_f, one)
        k = np.floor(g / pe)
        extra = g - k * pe
        msc = (npe_f - extra) * k
        div = k[rc] + (rank.astype(F) >= msc[rc]).astype(F)
        div = np.where(g[rc] <= npe_f[rc], one, div)
        div = np.where(pol[rc] == SPACE_SHARED, one, div)
        rate = mips[rc] / np.maximum(div, one)
        return np.where(run, rate, zero).astype(F), run, g

    active, finished = user_state()
    while not finished.all() and steps < max_steps:
        rate, run, occ = shares()
        key = np.maximum(rem, tiny)
        fore = np.where(run, key / np.maximum(rate, tiny), inf)
        cands = [t + fore.min() if run.any() else inf,
                 np.where(status == RETURNING, t_ev, inf).min(),
                 np.where(status == IN_TRANSIT, t_ev, inf).min(),
                 max(next_sched, t) if active.any() else inf]
        t_star = F(min(cands))
        any_event = np.isfinite(t_star)
        t_next = t_star if any_event else t
        fired_b = bool(active.any()) and cands[3] == t_star

        # advance running jobs over [t, t_next)
        rel = np.where(run, rem / np.maximum(rate, tiny), inf)
        dt = np.maximum(t_next - t, zero)
        completes = run & any_event & (t + rel <= t_next)
        rem = np.where(run, np.where(completes, zero,
                                     np.maximum(rem - rate * dt, zero)),
                       rem).astype(F)
        t = t_next

        # COMPLETION: results leave at once; freed PEs admit the queue
        rc = np.clip(res, 0, R - 1)
        status = np.where(completes, RETURNING, status)
        finish = np.where(completes, t, finish)
        t_ev = np.where(completes, t, t_ev)
        n_comp_r = np.bincount(rc[completes], minlength=R)
        free_pe = np.maximum(npe - (occ.astype(np.int64) - n_comp_r), 0)
        free_pe = np.where(pol == SPACE_SHARED, free_pe, 0)
        queued = status == QUEUED
        if (completes & (pol[rc] == SPACE_SHARED)).any() and queued.any():
            qrank = _rank_in_group(rc, queued, t_ev, R)
            admit = queued & (qrank < free_pe[rc])
            status = np.where(admit, RUNNING, status)
            start = np.where(admit, np.minimum(start, t), start)
            t_ev = np.where(admit, inf, t_ev)
            free_pe = free_pe - np.bincount(rc[admit], minlength=R)

        # RETURN
        ret = (status == RETURNING) & (t_ev <= t)
        status = np.where(ret, DONE, status)
        done_on = done_on + seg_sum(ret.astype(F), u * R + rc,
                                    U * R).reshape(U, R)

        # BROKER (before the arrivals of its own dispatches)
        arr_pre = (status == IN_TRANSIT) & (t_ev <= t)
        if fired_b:
            (status, res, assigned, t_ev, cost, rem, spent, first,
             next_sched) = _broker(
                t, L, u, U, R, status, res, assigned, t_ev, cost, rem,
                spent, done_on, first, dl, bu, npe_f, mips, cpm,
                min_price, limit, F, min_period, frac)

        # ARRIVAL
        arr = (status == IN_TRANSIT) & (t_ev <= t)
        rc = np.clip(res, 0, R - 1)
        ss = arr & (pol[rc] == SPACE_SHARED)
        order = np.where(arr_pre, idx, idx + n)
        srank = _rank_in_group(rc, ss, order, R) if ss.any() else order
        arr_run = arr & ((pol[rc] != SPACE_SHARED) | (srank < free_pe[rc]))
        arr_q = ss & ~arr_run
        status = np.where(arr_run, RUNNING,
                          np.where(arr_q, QUEUED, status))
        start = np.where(arr_run, np.minimum(start, t), start)
        t_ev = np.where(arr_run, inf, np.where(arr_q, t, t_ev))

        n_events += int(completes.sum() + ret.sum() + arr.sum()
                        + fired_b)
        steps += 1
        active, finished = user_state()
        term = np.where(finished & ~np.isfinite(term), t, term)

    term = np.where(np.isfinite(term), term, t)
    done = status == DONE
    return dict(
        n_done=np.bincount(u, done, minlength=U).astype(np.float32),
        spent=spent.astype(np.float32),
        term_time=term.astype(np.float32),
        status=status, resource=res, start=start.astype(np.float32),
        finish=finish.astype(np.float32),
        n_events=n_events, truncated=steps >= max_steps)


def _broker(t, L, u, U, R, status, res, assigned, t_ev, cost, rem, spent,
            done_on, first, dl, bu, npe_f, mips, cpm, min_price, limit,
            F, min_period, frac):
    """One Fig 20 cycle for every user's broker, and the next poll."""
    n = L.shape[0]
    idx = np.arange(n)
    zero, inf, tiny = F(0.0), F(np.inf), F(1e-30)

    # 1-3: discovery, trading, measured rate, capacity by the deadline
    adv_rate = mips * npe_f
    cnt = np.bincount(u, minlength=U).astype(F)
    mi = np.zeros(U, F)
    np.add.at(mi, u, L)
    avg_mi = (mi / np.maximum(cnt, F(1.0))).astype(F)
    inflight = (status >= IN_TRANSIT) & (status <= RETURNING)
    on_res = np.clip(res, 0, R - 1)
    frac_done = np.where(inflight, F(1.0) - rem / L, zero).astype(F)
    prog = np.zeros(U * R, F)
    np.add.at(prog, u * R + on_res, frac_done)
    prog = prog.reshape(U, R) + done_on
    elapsed = np.maximum(t - first, F(1e-6))
    adv_jobs = adv_rate[None, :] / np.maximum(avg_mi[:, None], tiny)
    measured = prog / elapsed
    started = np.isfinite(first) & (t > first + F(1e-9))
    est = np.where(started, np.minimum(measured, adv_jobs),
                   adv_jobs).astype(F)
    time_left = np.maximum(dl - t, zero)
    cap = np.floor(est * time_left[:, None]).astype(np.int64)
    undisp_mi = np.full(U, inf, F)
    np.minimum.at(undisp_mi, u, np.where(status == CREATED, L, inf))
    active = (t < dl) & (spent + undisp_mi * min_price <= bu)

    # 4: release over-committed, not yet dispatched jobs (newest first)
    a_c = np.clip(assigned, 0, R - 1)
    ur = u * R + a_c
    committed = (assigned >= 0) & (status != DONE)
    n_comm = np.bincount(ur[committed], minlength=U * R).reshape(U, R)
    undisp = (status == CREATED) & (assigned >= 0)
    n_undisp = np.bincount(ur[undisp], minlength=U * R).reshape(U, R)
    rel_rank = _rank_in_group(ur, undisp, -idx, U * R)
    n_rel = np.clip(n_comm - cap, 0, n_undisp)
    n_rel = np.where(active[:, None], n_rel, 0)
    release = undisp & (rel_rank < n_rel.reshape(-1)[ur])
    assigned = np.where(release, -1, assigned)
    n_comm = n_comm - n_rel

    # 5: assign unassigned jobs, cheapest resource first, under budget
    a_c = np.clip(assigned, 0, R - 1)
    planned = (assigned >= 0) & (status == CREATED)
    pc = np.zeros(U, F)
    np.add.at(pc, u, np.where(planned, L * cpm[a_c], zero).astype(F))
    budget_left = np.maximum(bu - spent - pc, zero)
    keys = cpm + F(1e-7) * np.arange(R, dtype=F)
    order = np.argsort(keys, kind="stable")                 # [R]
    inv_order = np.empty(R, np.int64)
    inv_order[order] = np.arange(R)
    slots = np.maximum(cap - n_comm, 0)                     # [U, R]
    job_cost = (avg_mi[:, None] * cpm[None, :]).astype(F)
    unassigned = (status == CREATED) & (assigned < 0)
    n_un = np.bincount(u[unassigned], minlength=U)
    taken = np.zeros(U, np.int64)
    brem = budget_left.astype(F)
    take_at = np.zeros((U, R), np.int64)
    for j in range(R):
        r = order[j]
        c = job_cost[:, r]
        by_b = np.floor(brem / np.maximum(c, tiny))
        by_b = np.clip(by_b, 0, 2 ** 30).astype(np.int64)
        fit = np.minimum(np.minimum(slots[:, r], by_b), n_un - taken)
        fit = np.where(active, fit, 0)
        take_at[:, j] = fit
        taken = taken + fit
        brem = (brem - fit.astype(F) * c).astype(F)
    cum = np.cumsum(take_at, axis=1)
    k = _rank_in_group(u, unassigned, idx, U)
    j_star = (cum[u] <= k[:, None]).sum(axis=1)
    gets = unassigned & (k < taken[u]) & (j_star < R)
    assigned = np.where(gets, order[np.clip(j_star, 0, R - 1)], assigned)

    # 6: dispatch within the staging limit and the budget
    a_c = np.clip(assigned, 0, R - 1)
    ur2 = u * R + a_c
    cand = (status == CREATED) & (assigned >= 0)
    n_infl = np.bincount((u * R + on_res)[inflight],
                         minlength=U * R).reshape(U, R)
    disp_slots = np.maximum(limit[None, :] - n_infl, 0)
    disp_rank = _rank_in_group(ur2, cand, idx, U * R)
    elig = cand & (disp_rank < disp_slots.reshape(-1)[ur2]) & active[u]
    exact = (L * cpm[a_c]).astype(F)
    okey = inv_order[a_c].astype(F) * F(n + 1.0) + idx.astype(F)
    # exclusive prefix of the costs per user, in (rank of resource,
    # index) order: one running sum over every user's eligible jobs,
    # less its value where the user's run begins
    gk = np.where(elig, u, U)
    srt = np.lexsort((idx, okey, gk))
    sv = np.where(elig, exact, zero).astype(F)[srt]
    cs = np.cumsum(sv, dtype=F)
    sg = gk[srt]
    first_of = np.r_[True, sg[1:] != sg[:-1]]
    base = np.maximum.accumulate(np.where(first_of, cs - sv, F(-np.inf)))
    prefix = np.empty(n, F)
    prefix[srt] = (cs - sv - base).astype(F)
    prefix = np.where(elig, prefix, zero)
    fits = prefix + exact <= (bu - spent)[u]
    disp = elig & fits

    status = np.where(disp, IN_TRANSIT, status)
    res = np.where(disp, assigned, res)
    t_ev = np.where(disp, t, t_ev)
    cost = np.where(disp, exact, cost)
    rem = np.where(disp, L, rem)
    add = np.zeros(U, F)
    np.add.at(add, u, np.where(disp, exact, zero).astype(F))
    spent = (spent + add).astype(F)
    fd = np.full(U * R, np.inf, F)
    np.minimum.at(fd, ur2, np.where(disp, t, inf))
    first = np.minimum(first, fd.reshape(U, R))

    dl_left = np.where(active, dl - t, zero)
    period = np.maximum(F(min_period), F(frac) * dl_left.max())
    return (status, res, assigned, t_ev, cost, rem, spent, first,
            F(t + period))
