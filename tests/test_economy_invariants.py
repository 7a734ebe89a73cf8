"""Property tests for the economic invariants of the broker suite.

The paper's economy only makes sense if four properties hold on every
execution path, under every strategy and pricing model:

  * a user's ``spent`` never exceeds its ``budget`` -- including the
    failure refund/resubmit cycle, where committed cost is returned and
    re-committed at (possibly repriced) dispatch,
  * an inactive broker (deadline passed, or the cheapest possible
    purchase no longer fits the remaining budget) dispatches nothing,
  * auction rounds are deterministic given the scenario seed (bitwise
    replay) and actually draw different prices under different seeds,
  * repriced costs stay positive, finite and inside the
    ``[floor, cap] * base`` clamp for any demand history.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import des, economy, engine, gridlet, resource, \
    simulation, types
from _golden_farm import golden_farm

MAX_EVENTS = 4096


def _run(sc, opt=types.OPT_COST, deadline=500.0, budget=20_000.0,
         n_jobs=8, n_users=2, seed=0):
    fleet = resource.make_fleet([2, 4], [300.0, 500.0], [2.0, 5.0],
                                [types.TIME_SHARED, types.SPACE_SHARED])
    g = gridlet.task_farm(jax.random.PRNGKey(seed), n_jobs=n_jobs,
                          n_users=n_users)
    params = simulation._scenario_params(fleet, deadline, budget, opt,
                                         n_users, sc)
    res = engine.run(g, fleet, params, n_users, MAX_EVENTS, batch=1)
    assert int(res.n_steps) + int(res.n_spec) < MAX_EVENTS
    return res, params


SCENARIOS = [
    ("static", None),
    ("commodity", simulation.Scenario(pricing_model="commodity",
                                      market_period=25.0,
                                      market_gain=0.5)),
    ("auction", simulation.Scenario(pricing_model="auction",
                                    auction_period=25.0, seed=3)),
    ("plan+failures", simulation.Scenario(plan_ahead=True, mtbf=150.0,
                                          mttr=20.0, seed=11)),
    ("auction+failures", simulation.Scenario(pricing_model="auction",
                                             auction_period=30.0,
                                             mtbf=120.0, mttr=15.0,
                                             seed=7)),
]


@pytest.mark.parametrize("tag,sc", SCENARIOS)
@pytest.mark.parametrize("opt", [types.OPT_COST, types.OPT_TIME,
                                 types.OPT_COST_TIME, types.OPT_NONE])
def test_spent_never_exceeds_budget(tag, sc, opt):
    """Dispatch commits exact cost against the remaining budget, and a
    failure refund can only lower ``spent`` -- so it never crosses the
    budget, on tight budgets and through refund/resubmit cycles."""
    for budget in (300.0, 2_000.0, 20_000.0):
        res, params = _run(sc, opt=opt, budget=budget)
        spent = np.asarray(res.spent)
        assert np.all(np.isfinite(spent)) and np.all(spent >= 0.0)
        assert np.all(spent <= np.asarray(params.budget)), \
            f"{tag}/opt={opt}/budget={budget}: overspent {spent}"


@pytest.mark.parametrize("tag,sc", SCENARIOS)
def test_inactive_broker_dispatches_nothing(tag, sc):
    """deadline <= 0 (never active) and budget == 0 (nothing
    affordable): every gridlet stays CREATED and nothing is billed."""
    for deadline, budget in ((0.0, 20_000.0), (500.0, 0.0)):
        res, _ = _run(sc, deadline=deadline, budget=budget)
        assert np.all(np.asarray(res.gridlets.status) == types.CREATED)
        assert np.all(np.asarray(res.spent) == 0.0)


def test_auction_rounds_deterministic_given_seed():
    """Same scenario seed -> bitwise-identical replay (including every
    auction draw); a different auction_seed moves the posted prices and
    hence the spend under cost optimisation."""
    sc = simulation.Scenario(pricing_model="auction", auction_period=20.0,
                            seed=4)
    a, _ = _run(sc, opt=types.OPT_COST)
    b, _ = _run(sc, opt=types.OPT_COST)
    kinds = np.asarray(a.trace[1])
    assert (kinds == des.K_AUCTION).sum() >= 1, "no auction round fired"
    for f in ("spent", "term_time", "n_events"):
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f)))
    for i in range(3):
        assert np.array_equal(np.asarray(a.trace[i]),
                              np.asarray(b.trace[i]))
    c, _ = _run(sc._replace(auction_seed=99), opt=types.OPT_COST)
    assert not np.array_equal(np.asarray(a.gridlets.cost),
                              np.asarray(c.gridlets.cost)), \
        "different auction seed left every dispatch cost untouched"


def test_repriced_costs_stay_positive_finite_and_clamped():
    """Iterating the commodity adjustment over random demand histories
    keeps the posted price inside [floor, cap] * base -- positive and
    finite by construction; the auction draw lands in the same box."""
    rng = np.random.RandomState(0)
    base = jnp.asarray([0.004, 0.01, 2.5], jnp.float32)   # G$/MI
    floor, cap, gain = 0.5, 2.0, 0.25
    lo, hi = np.asarray(base * floor), np.asarray(base * cap)
    price = base
    for _ in range(200):
        demand = jnp.asarray(rng.uniform(0.0, 8.0, 3), jnp.float32)
        price = economy.commodity_reprice(price, base, demand, gain,
                                          floor, cap)
        p = np.asarray(price)
        assert np.all(np.isfinite(p)) and np.all(p > 0.0)
        assert np.all(p >= lo) and np.all(p <= hi)
    for s in range(20):
        p = np.asarray(economy.auction_round(jax.random.PRNGKey(s), base,
                                             floor, cap))
        assert np.all(np.isfinite(p)) and np.all(p > 0.0)
        assert np.all(p >= lo) and np.all(p <= hi)


def test_golden_auction_trace_pinned_across_batch():
    """The committed golden_auction.json scenario replays bitwise --
    times, kinds, actors, spend, termination -- at batch=1 AND the
    default batch, pinning the auction source's event ordering, PRNG
    stream and price-driven dispatch decisions (regenerate with
    tests/data/gen_golden_auction.py).  The job lengths are committed
    data; the bid stream is drawn inside the engine, so the runs use the
    threefry variant it was recorded under."""
    import json
    import os
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "golden_auction.json")) as f:
        gold = json.load(f)
    fleet = resource.make_fleet([2, 4], [300.0, 500.0], [2.0, 5.0],
                                [types.TIME_SHARED, types.SPACE_SHARED])
    g = golden_farm("seed6_10x2")
    sc = simulation.Scenario(pricing_model="auction", auction_period=15.0,
                             seed=8)
    params = simulation._scenario_params(fleet, 400.0, 20_000.0,
                                         types.OPT_COST, 2, sc)
    max_jobs = simulation.safe_max_jobs(g, params, fleet)
    assert np.asarray(gold["trace_kind"]).tolist().count(
        des.K_AUCTION) >= 3
    for batch in (1, None):
        kw = {} if batch is None else dict(batch=batch)
        with jax.threefry_partitionable(False):
            r = engine.run(g, fleet, params, 2, 4096, max_jobs=max_jobs,
                           **kw)
        tt, kind, who = (np.asarray(x) for x in r.trace)
        m = kind >= 0
        assert np.array_equal(tt[m],
                              np.asarray(gold["trace_t"], np.float32))
        assert np.array_equal(kind[m], np.asarray(gold["trace_kind"]))
        assert np.array_equal(who[m], np.asarray(gold["trace_who"]))
        assert np.array_equal(np.asarray(r.gridlets.returned),
                              np.asarray(gold["returned"], np.float32))
        assert np.array_equal(np.asarray(r.spent),
                              np.asarray(gold["spent"], np.float32))
        assert np.array_equal(np.asarray(r.term_time),
                              np.asarray(gold["term_time"], np.float32))
        assert int(np.asarray(r.n_events)) == gold["n_events"]
        assert int(np.asarray(r.overflow)) == gold["overflow"]
        assert int((np.asarray(r.gridlets.status)
                    == types.DONE).sum()) == gold["n_done"]


def test_engine_prices_stay_clamped_under_pricing():
    """End-to-end: drive the real engine sources over many rounds and
    check the carried posted price never leaves the clamp box."""
    fleet = resource.make_fleet([2, 4], [300.0, 500.0], [2.0, 5.0],
                                [types.TIME_SHARED, types.SPACE_SHARED])
    g = gridlet.task_farm(jax.random.PRNGKey(1), n_jobs=6, n_users=2)
    for model in ("commodity", "auction"):
        params = simulation._scenario_params(
            fleet, 500.0, 20_000.0, types.OPT_COST, 2,
            simulation.Scenario(pricing_model=model, market_period=10.0,
                                auction_period=10.0, seed=2))
        state = engine.init_state(g, fleet, 2, params=params)
        sources = engine._make_sources(fleet, params, 2,
                                       {"select_free": True})
        pos = {s.kind: i for i, s in enumerate(sources)}
        kind = des.K_MARKET if model == "commodity" else des.K_AUCTION
        src = sources[pos[kind]]
        base = np.asarray(fleet.cost_per_mi, np.float32)
        lo = base * float(params.price_floor)
        hi = base * float(params.price_cap)
        now = 10.0
        for _ in range(50):
            state = src.apply(state, jnp.asarray(now, jnp.float32))
            p = np.asarray(state.price)
            assert np.all(np.isfinite(p)) and np.all(p > 0.0)
            assert np.all(p >= lo) and np.all(p <= hi)
            now += 10.0
