"""Unit tests: des, economy, stats, rand, reservation, gis, calendar,
segments."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without dev deps: seeded fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core import (calendar, des, economy, gis, gridlet, rand,
                        reservation, resource, segments, stats, types)


# ---------------------------------------------------------- des --------
def test_event_queue_orders_by_time_then_fifo():
    q = des.make_queue(8)
    q = des.schedule(q, 5.0, 0, 1, 10)
    q = des.schedule(q, 2.0, 0, 1, 11)
    q = des.schedule(q, 5.0, 0, 1, 12)   # same time as first -> FIFO
    order = []
    for _ in range(3):
        q, (t, src, dst, tag, data, valid) = des.pop_next(q)
        assert bool(valid)
        order.append((float(t), int(tag)))
    assert order == [(2.0, 11), (5.0, 10), (5.0, 12)]
    assert int(q.overflow) == 0
    q, (*_, valid) = des.pop_next(q)
    assert not bool(valid)


def test_event_queue_full_drops_and_counts():
    """A full calendar must not overwrite a live event (it previously
    clobbered slot 0); the dropped schedule is counted in overflow."""
    q = des.make_queue(2)
    q = des.schedule(q, 1.0, 0, 0, 10)
    q = des.schedule(q, 2.0, 0, 0, 11)
    q = des.schedule(q, 0.5, 0, 0, 12)   # full: dropped, not slot 0
    assert int(q.overflow) == 1
    q, (t, *_, tag, _d, valid) = des.pop_next(q)
    assert bool(valid) and float(t) == 1.0
    # freeing a slot makes schedule work again, overflow is sticky
    q = des.schedule(q, 3.0, 0, 0, 13)
    assert int(des.size(q)) == 2 and int(q.overflow) == 1


def test_event_queue_cancel():
    q = des.make_queue(4)
    q = des.schedule(q, 1.0, 7, 1, 10)
    q = des.schedule(q, 2.0, 8, 1, 11)
    q = des.cancel(q, lambda q: q.src == 7)  # stale-event discard rule
    q, (t, *_, valid) = des.pop_next(q)
    assert bool(valid) and float(t) == 2.0


@settings(max_examples=20, deadline=None)
@given(times=st.lists(st.floats(0.0, 100.0), min_size=1, max_size=16))
def test_event_queue_pop_sorted(times):
    q = des.make_queue(len(times))
    for i, t in enumerate(times):
        q = des.schedule(q, t, 0, 0, i)
    popped = []
    for _ in times:
        q, (t, *_, valid) = des.pop_next(q)
        popped.append(float(t))
    # Devices compare f32 with subnormals flushed to zero, so a
    # subnormal time ties with 0.0 and pops FIFO among its ties.
    t32 = np.float32(times)
    key = np.where(np.abs(t32) < np.finfo(np.float32).tiny, 0.0, t32)
    assert popped == t32[np.argsort(key, kind="stable")].tolist()
    assert int(q.overflow) == 0


# ------------------------------------------------------ economy --------
def test_eq1_eq2_bounds():
    fleet = resource.wwg_fleet()
    total_mi = 200 * 10_000.0
    tmin = float(economy.t_min(fleet, total_mi))
    tmax = float(economy.t_max(fleet, total_mi))
    cmin = float(economy.c_min(fleet, total_mi))
    cmax = float(economy.c_max(fleet, total_mi))
    assert 0 < tmin < tmax
    assert 0 < cmin < cmax
    # D/B factor endpoints
    assert float(economy.deadline_from_factor(fleet, total_mi, 0.0)) == \
        pytest.approx(tmin)
    assert float(economy.deadline_from_factor(fleet, total_mi, 1.0)) == \
        pytest.approx(tmax)
    assert float(economy.budget_from_factor(fleet, total_mi, 0.0)) == \
        pytest.approx(cmin)
    # negative factors produce infeasible constraints (< minimum)
    assert float(economy.deadline_from_factor(fleet, total_mi, -0.5)) < tmin


# -------------------------------------------------------- stats --------
def test_accumulator_moments():
    acc = stats.accumulator()
    xs = [1.0, 2.0, 3.0, 4.0]
    for x in xs:
        acc = stats.add(acc, x)
    assert float(stats.mean(acc)) == pytest.approx(2.5)
    assert float(stats.std(acc)) == pytest.approx(np.std(xs))
    assert float(acc.vmin) == 1.0 and float(acc.vmax) == 4.0


def test_accumulator_bulk_masked():
    acc = stats.accumulator()
    acc = stats.add_many(acc, jnp.array([1.0, 100.0, 3.0]),
                         mask=jnp.array([1.0, 0.0, 1.0]))
    assert float(stats.mean(acc)) == pytest.approx(2.0)
    assert float(acc.vmax) == 3.0


# --------------------------------------------------------- rand --------
@settings(max_examples=20, deadline=None)
@given(d=st.floats(1.0, 1e4), fl=st.floats(0.0, 1.0),
       fm=st.floats(0.0, 1.0), seed=st.integers(0, 2**31 - 1))
def test_gridsim_random_range(d, fl, fm, seed):
    v = float(rand.real(jax.random.PRNGKey(seed), d, fl, fm))
    assert (1 - fl) * d - 1e-3 <= v <= (1 + fm) * d + 1e-3


def test_gridsim_random_deterministic():
    k = jax.random.PRNGKey(0)
    assert float(rand.real(k, 10.0, 0.1, 0.1)) == \
        float(rand.real(k, 10.0, 0.1, 0.1))


# -------------------------------------------------- reservation --------
def test_reservation_booking_and_conflicts():
    book = reservation.ReservationBook([2, 4])
    r1 = book.book(0, 1, 0.0, 10.0)
    book.book(0, 1, 0.0, 10.0)
    with pytest.raises(ValueError):
        book.book(0, 1, 5.0, 15.0)       # both PEs held on [5,10)
    book.book(0, 2, 10.0, 20.0)          # back-to-back is fine
    assert book.reserved_pes(0, 5.0) == 2
    assert book.reserved_pes(0, 15.0) == 2
    book.cancel(r1)
    assert book.reserved_pes(0, 5.0) == 1
    assert book.load_factor(1, 0.0) == 0.0


def test_reservation_validation():
    book = reservation.ReservationBook([2])
    with pytest.raises(ValueError):
        book.book(0, 0, 0.0, 1.0)
    with pytest.raises(ValueError):
        book.book(0, 1, 5.0, 5.0)
    with pytest.raises(ValueError):
        book.book(1, 1, 0.0, 1.0)


# ---------------------------------------------------------- gis --------
def test_gis_register_deregister():
    fleet = resource.wwg_fleet()
    g = gis.init(fleet)
    assert bool(gis.resource_list(g).all())
    g = gis.deregister(g, 3)
    rate, cost = gis.dynamics(g, fleet, 0.0)
    assert float(rate[3]) == 0.0
    assert float(rate[0]) > 0.0
    g = gis.register(g, 3)
    rate, _ = gis.dynamics(g, fleet, 0.0)
    assert float(rate[3]) > 0.0


# ----------------------------------------------------- calendar --------
def test_calendar_weekend_load():
    fleet = resource.make_fleet([1, 1], 100.0, 1.0, types.TIME_SHARED,
                                time_zone=[0.0, 0.0],
                                base_load=0.1, weekend_load=0.4)
    # t=0 is Monday 00:00 UTC; Saturday starts at hour 120.
    weekday = np.asarray(calendar.load(fleet, 10.0))
    weekend = np.asarray(calendar.load(fleet, 121.0))
    np.testing.assert_allclose(weekday, 0.1, atol=1e-6)
    np.testing.assert_allclose(weekend, 0.5, atol=1e-6)
    assert float(calendar.effective_mips(fleet, 10.0)[0]) == \
        pytest.approx(90.0)


def test_calendar_time_zone_shift():
    fleet = resource.make_fleet([1, 1], 100.0, 1.0, types.TIME_SHARED,
                                time_zone=[0.0, 24.0 * 5],
                                base_load=0.0, weekend_load=0.5)
    load = np.asarray(calendar.load(fleet, 1.0))
    assert load[0] == 0.0 and load[1] == 0.5  # zone-shifted into Saturday


# ----------------------------------------------------- segments --------
@settings(max_examples=25, deadline=None)
@given(
    groups=st.lists(st.integers(0, 3), min_size=1, max_size=24),
    seed=st.integers(0, 1000),
)
def test_group_rank_matches_numpy(groups, seed):
    rng = np.random.RandomState(seed)
    n = len(groups)
    keys = rng.rand(n).astype(np.float32)
    member = rng.rand(n) > 0.3
    gk = jnp.asarray(groups, jnp.int32)
    rank, counts = segments.group_rank(gk, jnp.asarray(member),
                                       jnp.asarray(keys), 4)
    rank, counts = np.asarray(rank), np.asarray(counts)
    for grp in range(4):
        idxs = [i for i in range(n) if member[i] and groups[i] == grp]
        assert counts[grp] == len(idxs)
        expect = sorted(idxs, key=lambda i: (keys[i], i))
        for want_rank, i in enumerate(expect):
            assert rank[i] == want_rank


@settings(max_examples=25, deadline=None)
@given(
    groups=st.lists(st.integers(0, 2), min_size=1, max_size=16),
    seed=st.integers(0, 1000),
)
def test_group_prefix_sum_matches_numpy(groups, seed):
    rng = np.random.RandomState(seed)
    n = len(groups)
    vals = rng.rand(n).astype(np.float32) * 10
    order = rng.rand(n).astype(np.float32)
    member = rng.rand(n) > 0.3
    out = np.asarray(segments.group_prefix_sum(
        jnp.asarray(groups, jnp.int32), jnp.asarray(member),
        jnp.asarray(order), jnp.asarray(vals), 3))
    for grp in range(3):
        idxs = [i for i in range(n) if member[i] and groups[i] == grp]
        idxs.sort(key=lambda i: (order[i], i))
        run = 0.0
        for i in idxs:
            assert out[i] == pytest.approx(run, abs=1e-4)
            run += vals[i]
