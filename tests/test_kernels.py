"""Pallas kernels vs ref.py oracles (interpret mode on CPU).

Per the brief: sweep shapes/dtypes per kernel; property tests via
hypothesis on the system invariants (softmax normalisation, state decay).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without dev deps: seeded fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.core import engine, gridlet, resource, types
from repro.core.types import replace as treplace


# ------------------------------------------------------------------
# flash attention
# ------------------------------------------------------------------
FLASH_SHAPES = [
    # (b, hq, hkv, sq, d, causal, window, cap)
    (1, 2, 2, 64, 16, True, 0, 0.0),
    (2, 4, 2, 128, 32, True, 0, 0.0),
    (2, 4, 1, 128, 32, True, 32, 0.0),      # GQA + window
    (1, 8, 8, 256, 64, True, 0, 50.0),      # softcap
    (1, 2, 2, 64, 16, False, 0, 0.0),       # bidirectional
    (2, 6, 2, 96, 16, True, 16, 30.0),      # everything at once
]


@pytest.mark.parametrize("b,hq,hkv,s,d,causal,window,cap", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, hq, hkv, s, d, causal, window,
                                     cap, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * 7 + s), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              cap=cap, block_q=32, block_kv=32,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   cap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_lowers_for_tpu_shapes():
    """The kernel must at least trace/lower with production block sizes."""
    q = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, 2048, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 2, 2048, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, interpret=True), q, k, v)


# ------------------------------------------------------------------
# SSD scan
# ------------------------------------------------------------------
SSD_SHAPES = [
    # (b, s, h, p, n, chunk, block_h)
    (1, 32, 4, 8, 16, 8, 4),
    (2, 64, 8, 16, 32, 16, 4),
    (1, 128, 8, 32, 64, 32, 8),
    (2, 48, 2, 8, 8, 16, 2),
]


@pytest.mark.parametrize("b,s,h,p,n,chunk,bh", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(b, s, h, p, n, chunk, bh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(s + h), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(
        jnp.float32)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    cm = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    got = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, block_h=bh,
                       interpret=True)
    want = ref.ssd_ref(x, dt, a, bm, cm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([16, 32, 64]), h=st.sampled_from([2, 4]),
       seed=st.integers(0, 99))
def test_ssd_scan_property_decay_bounds(s, h, seed):
    """With x == 0 the output is 0 (pure decay); states never blow up."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    b, p, n = 1, 8, 8
    x = jnp.zeros((b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[0], (b, s, n))
    y = ops.ssd_scan(x, dt, a, bm, cm, chunk=8, block_h=2,
                     interpret=True)
    np.testing.assert_allclose(np.asarray(y), 0.0, atol=1e-6)


# ------------------------------------------------------------------
# event scan (paper Fig 8)
# ------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    r=st.sampled_from([8, 16]),
    j=st.sampled_from([8, 32]),
    seed=st.integers(0, 999),
)
def test_event_scan_matches_ref(r, j, seed):
    rng = np.random.RandomState(seed)
    remaining = rng.exponential(50.0, (r, j)).astype(np.float32)
    remaining[rng.rand(r, j) < 0.4] = 0.0   # empty slots
    mips = rng.uniform(1.0, 500.0, (r,)).astype(np.float32)
    pes = rng.randint(1, 9, (r,)).astype(np.int32)
    rate, tmin, amin, occ = ops.event_scan(
        jnp.asarray(remaining), jnp.asarray(mips), jnp.asarray(pes),
        interpret=True)
    rate_ref, tmin_ref, amin_ref, occ_ref = ref.event_scan_ref(
        remaining, mips, pes)
    np.testing.assert_allclose(np.asarray(rate), np.asarray(rate_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(tmin), np.asarray(tmin_ref),
                               rtol=1e-4)
    assert np.array_equal(np.asarray(occ), np.asarray(occ_ref))
    # argmin cols must agree wherever the row forecast is unambiguous
    # at f32 resolution (the oracle ranks in f64).
    np.testing.assert_allclose(np.asarray(amin), np.asarray(amin_ref))


def test_event_scan_matches_engine_rates():
    """The kernel, its oracle and the engine's XLA path must agree."""
    n_jobs, num_pe = 7, 2
    g = gridlet.make_batch(jnp.full((n_jobs,), 100.0))
    g = treplace(g, status=jnp.full((n_jobs,), types.RUNNING, jnp.int32),
                 resource=jnp.zeros((n_jobs,), jnp.int32),
                 remaining=jnp.arange(1.0, n_jobs + 1.0))
    fleet = resource.make_fleet([num_pe], 3.0, 1.0, types.TIME_SHARED)
    st_ = engine.init_state(g, fleet, 1)
    st_ = treplace(st_, g=g)
    engine_rates = np.asarray(engine._rates(st_, fleet, 1))

    remaining = jnp.arange(1.0, n_jobs + 1.0).reshape(1, n_jobs)
    remaining = jnp.pad(remaining, ((0, 7), (0, 0)))  # block_r alignment
    rate, tmin, _, _ = ops.event_scan(remaining, jnp.full((8,), 3.0),
                                      jnp.full((8,), num_pe, jnp.int32),
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(rate)[0], engine_rates,
                               rtol=1e-5)
    assert float(tmin[0]) == pytest.approx(
        float((jnp.arange(1.0, n_jobs + 1.0) / engine_rates).min()))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999))
def test_event_scan_capacity_conservation(seed):
    """Fig 8 invariant: allocated rate sums to min(jobs, PEs) * mips."""
    rng = np.random.RandomState(seed)
    r, j = 8, 16
    remaining = rng.exponential(10.0, (r, j)).astype(np.float32)
    remaining[rng.rand(r, j) < 0.5] = 0.0
    mips = rng.uniform(1.0, 10.0, (r,)).astype(np.float32)
    pes = rng.randint(1, 5, (r,)).astype(np.int32)
    rate, _, _, _ = ops.event_scan(jnp.asarray(remaining),
                                   jnp.asarray(mips), jnp.asarray(pes),
                                   interpret=True)
    jobs = (remaining > 0).sum(axis=1)
    expect = np.minimum(jobs, pes) * mips
    np.testing.assert_allclose(np.asarray(rate).sum(axis=1), expect,
                               rtol=1e-4)


# ------------------------------------------------------------------
# event scan slab (k-wave completion forecast, one fused call)
# ------------------------------------------------------------------
def _random_slab_case(seed, r=8, j=12):
    rng = np.random.RandomState(seed)
    remaining = rng.exponential(50.0, (r, j)).astype(np.float32)
    remaining[rng.rand(r, j) < 0.3] = 0.0
    if seed % 2:  # integer remainings force ties within and across rows
        remaining = np.where(
            remaining > 0, rng.randint(1, 5, (r, j)).astype(np.float32),
            0.0)
    mips = rng.uniform(1.0, 500.0, (r,)).astype(np.float32)
    pes = rng.randint(1, 9, (r,)).astype(np.int32)
    kw = dict(tie=rng.permutation(r * j).reshape(r, j).astype(np.float32),
              policy=rng.randint(0, 2, (r,)).astype(np.int32),
              pe_blocked=rng.randint(0, 4, (r,)).astype(np.float32),
              row_ok=(rng.rand(r) < 0.8).astype(np.float32))
    return remaining, mips, pes, kw


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 999), k=st.sampled_from([1, 4, 6]))
def test_event_scan_slab_paths_agree(seed, k):
    """Pallas interpret, the XLA fallback and the iterated-single-scan
    oracle agree on the k-wave forecast, masks and tie keys included."""
    remaining, mips, pes, kw = _random_slab_case(seed)
    jkw = {a: jnp.asarray(v) for a, v in kw.items()}
    args = (jnp.asarray(remaining), jnp.asarray(mips), jnp.asarray(pes))
    pallas_out = ops.event_scan_slab(*args, k, **jkw, interpret=True)
    xla_out = ops.event_scan_slab(*args, k, **jkw)
    ref_out = ref.event_scan_slab_ref(remaining, mips, pes, k, **kw)
    for got, name in ((xla_out, "xla"), (ref_out, "oracle")):
        np.testing.assert_allclose(
            np.asarray(pallas_out[0]), np.asarray(got[0]), rtol=2e-3,
            atol=1e-3, err_msg=f"t_wave vs {name}")
        assert np.array_equal(np.asarray(pallas_out[1]),
                              np.asarray(got[1])), f"col_wave vs {name}"


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 999))
def test_event_scan_slab_wave0_is_event_scan(seed):
    """Wave 0 of the slab is exactly the single scan's forecast -- the
    slab is a strict generalisation of event_scan."""
    remaining, mips, pes, kw = _random_slab_case(seed)
    jkw = {a: jnp.asarray(v) for a, v in kw.items()}
    args = (jnp.asarray(remaining), jnp.asarray(mips), jnp.asarray(pes))
    t_w, col_w = ops.event_scan_slab(*args, 3, **jkw)
    _, tmin, amin, _ = ops.event_scan(*args, **jkw)
    np.testing.assert_allclose(np.asarray(t_w[:, 0]), np.asarray(tmin),
                               rtol=1e-5)
    assert np.array_equal(np.asarray(col_w[:, 0]), np.asarray(amin))
    # waves are non-decreasing in time per row (BIG pads stay last)
    tw = np.asarray(t_w)
    assert np.all(np.diff(tw, axis=1) >= -1e-3)


def test_event_scan_slab_lowers_for_tpu_shapes():
    """The slab kernel must trace at fleet scale (R=256, J=128, k=8).
    Interpret-mode shape tracing only: Mosaic never sees it (the
    compiles for a described TPU are in tests/test_tpu_compile.py)."""
    r, j = 256, 128
    rem = jax.ShapeDtypeStruct((r, j), jnp.float32)
    v = jax.ShapeDtypeStruct((r,), jnp.float32)
    jax.eval_shape(lambda a, m, p: ops.event_scan_slab(
        a, m, p, 8, interpret=True), rem, v, v)


# ------------------------------------------------------------------
# rank output, lane tiling and the bitonic large-J path
# ------------------------------------------------------------------
from repro.kernels import event_scan as event_scan_mod


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999), j=st.sampled_from([8, 64, 512, 1024]))
def test_bitonic_rank_matches_lexsort(seed, j):
    """The in-kernel O(J log^2 J) bitonic rank agrees with the stable
    lexsort rank on every valid slot (invalid-slot ranks are
    uncontractual), at power-of-two widths up to past the crossover."""
    rng = np.random.RandomState(seed)
    rem = rng.exponential(50.0, (8, j)).astype(np.float32)
    rem[rng.rand(8, j) < 0.4] = 0.0
    if seed % 2:  # integer remainings force ties broken by the tie key
        rem = np.where(rem > 0,
                       rng.randint(1, 4, (8, j)).astype(np.float32), 0.0)
    tie = rng.permutation(8 * j).reshape(8, j).astype(np.float32)
    valid = (rem > 0) & (rem < event_scan_mod.BIG)
    rb, _, _ = jax.jit(event_scan_mod._bitonic_rank)(
        jnp.asarray(rem), jnp.asarray(tie), jnp.asarray(valid))
    rl, _, _ = event_scan_mod._lexsort_rank(
        jnp.asarray(rem), jnp.asarray(tie), jnp.asarray(valid))
    assert np.array_equal(np.asarray(rb)[valid], np.asarray(rl)[valid])


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 999), j=st.sampled_from([12, 130, 600]))
def test_event_scan_rank_output_and_lane_padding(seed, j):
    """``with_rank=True`` agrees across Pallas interpret (lane-padded;
    J=600 pads to 1024 and exercises the bitonic in-kernel path), the
    XLA fallback and the oracle -- on valid slots, with identical
    rate/forecast/argmin/occupancy outputs at the caller's original J.
    """
    rng = np.random.RandomState(seed)
    r = 8
    rem = rng.exponential(50.0, (r, j)).astype(np.float32)
    rem[rng.rand(r, j) < 0.4] = 0.0
    mips = rng.uniform(1.0, 500.0, (r,)).astype(np.float32)
    pes = rng.randint(1, 9, (r,)).astype(np.int32)
    tie = rng.permutation(r * j).reshape(r, j).astype(np.float32)
    pol = rng.randint(0, 2, (r,)).astype(np.int32)
    args = (jnp.asarray(rem), jnp.asarray(mips), jnp.asarray(pes))
    kw = dict(tie=jnp.asarray(tie), policy=jnp.asarray(pol))
    p = ops.event_scan(*args, **kw, interpret=True, with_rank=True)
    x = event_scan_mod.event_scan_xla(*args, **kw, with_rank=True)
    o = ref.event_scan_ref(rem, mips, pes, tie=tie, policy=pol,
                           with_rank=True)
    valid = rem > 0
    for got, name in ((x, "xla"), (o, "oracle")):
        np.testing.assert_allclose(np.asarray(p[0]), np.asarray(got[0]),
                                   rtol=1e-4, atol=1e-4, err_msg=name)
        np.testing.assert_allclose(np.asarray(p[1]), np.asarray(got[1]),
                                   rtol=1e-4, err_msg=name)
        assert np.array_equal(np.asarray(p[3]), np.asarray(got[3])), name
        assert np.array_equal(np.asarray(p[4])[valid],
                              np.asarray(got[4])[valid]), f"rank {name}"
    assert np.array_equal(np.asarray(p[2]), np.asarray(x[2]))
    assert p[0].shape == (r, j) and p[4].shape == (r, j)
    assert int(np.asarray(p[2]).max()) <= j   # sentinel remapped to J


def test_event_scan_rank_injection_is_bitwise_identical():
    """Injecting the fresh rank back into the XLA path (the engine's
    slab-fed sort-free micro-step scan) reproduces every output
    bitwise."""
    rng = np.random.RandomState(7)
    r, j = 8, 40
    rem = rng.exponential(50.0, (r, j)).astype(np.float32)
    rem[rng.rand(r, j) < 0.3] = 0.0
    mips = rng.uniform(1.0, 500.0, (r,)).astype(np.float32)
    pes = rng.randint(1, 9, (r,)).astype(np.int32)
    kw = dict(tie=jnp.asarray(
        rng.permutation(r * j).reshape(r, j).astype(np.float32)))
    base = event_scan_mod.event_scan_xla(
        jnp.asarray(rem), jnp.asarray(mips), jnp.asarray(pes), **kw,
        with_rank=True)
    again = event_scan_mod.event_scan_xla(
        jnp.asarray(rem), jnp.asarray(mips), jnp.asarray(pes), **kw,
        with_rank=True, rank=base[4])
    for a, b in zip(base, again):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------------
# event frontier (fused 8-source fan-in)
# ------------------------------------------------------------------
def _random_frontier_case(rng, n_src=None, seg_hi=7):
    sizes = tuple(int(v) for v in rng.randint(
        0, seg_hi, size=n_src or rng.randint(1, 9)))
    c = sum(sizes)
    cand = np.where(rng.rand(c) < 0.35, np.inf,
                    rng.uniform(0.0, 100.0, c)).astype(np.float32)
    if c and rng.rand() < 0.5:      # force exact duplicates of the min
        cand[rng.randint(c)] = np.nanmin(
            np.where(np.isfinite(cand), cand, np.nan)) \
            if np.isfinite(cand).any() else np.inf
    cuts = (rng.rand(c) < 0.5).astype(np.float32)
    return cand, sizes, cuts


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 9999))
def test_event_frontier_paths_agree(seed):
    """Pallas interpret, the XLA fallback and the oracle agree exactly
    (t*, fired, counts, t_safe, per-source mins) on random segment
    layouts including empty segments and all-inf sources."""
    rng = np.random.RandomState(seed)
    cand, sizes, cuts = _random_frontier_case(rng)
    fp = event_scan_mod.event_frontier(jnp.asarray(cand), sizes,
                                       cuts=jnp.asarray(cuts),
                                       interpret=True)
    fx = event_scan_mod.event_frontier_xla(jnp.asarray(cand), sizes,
                                           cuts=jnp.asarray(cuts))
    fr = ref.event_frontier_ref(cand, sizes, cuts=cuts)
    for a, b, c in zip(fp, fx, fr):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), np.asarray(c))


def test_event_frontier_tpu_lane_shapes():
    """The engine's real layout -- per-row completion forecasts,
    per-resource failure/recovery streams, [N]-sized RETURN/ARRIVAL
    segments, a scalar broker -- padded across TPU lane boundaries.
    Runs in interpret mode: Mosaic never sees it (the compiles for a
    described TPU are in tests/test_tpu_compile.py)."""
    rng = np.random.RandomState(0)
    sizes = (16, 11, 11, 6, 2000, 2000, 11, 1)
    c = sum(sizes)
    cand = np.where(rng.rand(c) < 0.6, np.inf,
                    rng.uniform(0.0, 500.0, c)).astype(np.float32)
    cuts = np.concatenate([
        np.zeros(16, np.float32),           # COMPLETION: spec-safe
        np.ones(11, np.float32), np.ones(11, np.float32),
        np.ones(6, np.float32),
        np.zeros(2000, np.float32),         # RETURN: spec-safe
        np.ones(2000, np.float32), np.ones(11, np.float32),
        np.ones(1, np.float32)])
    fp = event_scan_mod.event_frontier(jnp.asarray(cand), sizes,
                                       cuts=jnp.asarray(cuts),
                                       interpret=True)
    fr = ref.event_frontier_ref(cand, sizes, cuts=cuts)
    for a, b in zip(fp, fr):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # t_safe only sees horizon-cutting candidates
    t_star, fired, counts, t_safe, mins = fr
    assert float(t_safe) >= float(t_star)


# ------------------------------------------------------------------
# associative-scan slab: operator property, 3-way agreement, lowering
# ------------------------------------------------------------------
def _random_wave_matrix(rng, k, dtype):
    """A random wave-compose operand: identity except one row, like the
    matrices _wave_matrices emits (last row stays [0..0 1])."""
    m = np.eye(k + 1, dtype=dtype)
    p = rng.randint(0, k)
    m[p, :] = 0.0
    m[p, :p] = rng.uniform(-3.0, 0.0, p)
    m[p, k] = rng.uniform(0.0, 50.0)
    return m


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999), k=st.sampled_from([2, 4, 8]))
def test_wave_compose_operator_is_associative(seed, k):
    """The wave-compose operator (matrix product of homogeneous wave
    updates) is exactly associative in f64 and associative to matmul
    rounding in f32 -- the property jax.lax.associative_scan and the
    in-kernel product tree rely on to regroup the k waves freely."""
    rng = np.random.RandomState(seed)
    a64, b64, c64 = (_random_wave_matrix(rng, k, np.float64)
                     for _ in range(3))
    # exact-precision leg: the operator's definition (compose(a, b) =
    # b @ a) mirrored in float64 numpy -- jnp would demote to f32
    left = c64 @ (b64 @ a64)
    right = (c64 @ b64) @ a64
    np.testing.assert_allclose(left, right, rtol=1e-12, atol=1e-12)
    comp = event_scan_mod._compose_waves
    a, b, c = (x.astype(np.float32) for x in (a64, b64, c64))
    np.testing.assert_allclose(
        np.asarray(comp(comp(jnp.asarray(a), jnp.asarray(b)),
                        jnp.asarray(c))),
        np.asarray(comp(jnp.asarray(a),
                        comp(jnp.asarray(b), jnp.asarray(c)))),
        rtol=2e-4, atol=1e-4)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 999), j=st.sampled_from([100, 512, 1024]),
       k=st.sampled_from([1, 4, 8]))
def test_event_scan_slab_assoc_three_way_agreement(seed, j, k):
    """Associative slab tri-implementation at engine widths: Pallas
    interpret (balanced product tree), the XLA associative_scan path,
    the sequential recurrence and the float64 forward-substitution
    oracle all agree -- J = 512/1024 route the rank through the bitonic
    network, J = 100 through the pairwise path."""
    remaining, mips, pes, kw = _random_slab_case(seed, j=j)
    jkw = {a: jnp.asarray(v) for a, v in kw.items()}
    args = (jnp.asarray(remaining), jnp.asarray(mips), jnp.asarray(pes))
    pallas_out = ops.event_scan_slab(*args, k, **jkw, interpret=True,
                                     assoc=True)
    xla_out = ops.event_scan_slab(*args, k, **jkw, assoc=True)
    seq_out = ops.event_scan_slab(*args, k, **jkw, assoc=False)
    ref_out = ref.event_scan_slab_assoc_ref(remaining, mips, pes, k,
                                            **kw)
    for got, name in ((xla_out, "xla-assoc"), (seq_out, "sequential"),
                      (ref_out, "oracle")):
        np.testing.assert_allclose(
            np.asarray(pallas_out[0]), np.asarray(got[0]), rtol=2e-3,
            atol=1e-3, err_msg=f"t_wave vs {name}")
        assert np.array_equal(np.asarray(pallas_out[1]),
                              np.asarray(got[1])), f"col_wave vs {name}"


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 999))
def test_event_scan_slab_assoc_wave0_bitwise(seed):
    """Wave 0 must be BITWISE identical between the associative and
    sequential paths (identity prefix rows compose exactly), which is
    what lets the engine treat the two as interchangeable for the
    single-wave forecasts its micro-steps consume."""
    remaining, mips, pes, kw = _random_slab_case(seed)
    jkw = {a: jnp.asarray(v) for a, v in kw.items()}
    args = (jnp.asarray(remaining), jnp.asarray(mips), jnp.asarray(pes))
    t_a, col_a = ops.event_scan_slab(*args, 6, **jkw, assoc=True)
    t_s, col_s = ops.event_scan_slab(*args, 6, **jkw, assoc=False)
    assert np.array_equal(np.asarray(t_a[:, 0]), np.asarray(t_s[:, 0]))
    assert np.array_equal(np.asarray(col_a), np.asarray(col_s))
    # later waves agree to compose rounding; padding stays exact BIG/J
    np.testing.assert_allclose(np.asarray(t_a), np.asarray(t_s),
                               rtol=2e-3, atol=1e-3)


def test_event_scan_slab_assoc_lowers_for_tpu_shapes():
    """Both slab formulations trace at fleet scale (R=256, J=128, k=8)
    and at the wide bitonic widths J = 512/1024.  Interpret-mode shape
    tracing only: Mosaic never sees it."""
    for j in (128, 512, 1024):
        rem = jax.ShapeDtypeStruct((256, j), jnp.float32)
        v = jax.ShapeDtypeStruct((256,), jnp.float32)
        for assoc in (True, False):
            jax.eval_shape(
                lambda a, m, p, assoc=assoc: ops.event_scan_slab(
                    a, m, p, 8, interpret=True, assoc=assoc),
                rem, v, v)
