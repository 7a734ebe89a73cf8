"""Pallas TPU kernel for the GridSim inner loop: Fig 8 PE-share
allocation + earliest-completion forecast, batched over resources.

This is the simulator's hot spot at fleet scale: the superstep engine
(repro.core.engine) evaluates it once per while-loop iteration over the
resource-major ``[R, J]`` job-slot table.  Per resource row:

  rank_j  = |{j' : (rem_j', tie_j') < (rem_j, tie_j)}|  (within the row)
  P_eff   = num_pe - pe_blocked                      (reservation windows)
  k       = g // P_eff,  extra = g % P_eff,  msc = (P_eff - extra) * k
  rate_j  = eff_mips / (k + [rank_j >= msc])        (Fig 8 shares; a
            space-shared row instead grants every job a whole PE)
  t_j     = remaining_j / rate_j
  t_min   = min_j t_j                               (forecast event)
  argmin  = col of the earliest completion, ties broken by tie key
  occ     = number of occupied job slots (space-shared PE occupancy)

Shape/dtype conventions: ``remaining``/``tie``/``rate`` are f32[R, J]
(J = job slots per resource, R padded to the block size); ``mips_eff``,
``num_pe``, ``policy``, ``pe_blocked``, ``row_ok`` are per-row [R]
vectors; ``t_min`` is f32[R], ``argmin_col``/``occupancy`` i32[R].

Masking inputs (both optional, identity when omitted):

  ``pe_blocked`` [R] f32 -- PEs held by advance-reservation windows.
      Time-shared rows compute Fig 8 shares over the remaining
      ``num_pe - pe_blocked`` PEs; a fully-reserved time-shared row
      contributes nothing (rate 0, excluded from argmin/occupancy).
      Space-shared rows are unaffected here: the engine enforces
      reservations at admission and never preempts residents.
  ``row_ok``     [R] bool -- resource up/registered mask (failures).
      A down row's slots are masked out of the rate, argmin and
      occupancy outputs entirely.

The per-row argmin and occupancy outputs exist so the engine needs no
second pass over the state to locate the completing job or to count busy
PEs for queue admission.

The ``tie`` input carries the engine's FIFO tie-break priority (the flat
gridlet index): equal-remaining jobs must receive MaxShare in submission
order for the Fig 9 / Table 1 trace to be reproduced exactly.  (Across
event *kinds* the engine orders same-time batches COMPLETION > FAILURE >
RECOVERY > RESERVATION > NETWORK > RETURN > ARRIVAL > CALENDAR_STEP >
BROKER; this
kernel only produces the COMPLETION forecasts.)

Tiling: grid over resource blocks; each block holds [block_r, J_pad]
state in VMEM.  The job-slot axis is **lane-tiled**: the Pallas wrappers
pad J up to a multiple of LANE = 128 (and, when the bitonic rank is
selected, to the next power of two) so every row maps cleanly onto the
8x128 VPU registers; outputs are sliced back to the caller's J and the
argmin/col sentinels re-mapped.  In-kernel ranking picks between two
exact algorithms by the *static* padded width:

  * J_pad <= RANK_BITONIC_MIN_J: the explicit [J, J] pairwise
    comparison -- O(J^2) VPU work, fully data-parallel, no lane
    shuffles, unbeatable for short rows;
  * J_pad >  RANK_BITONIC_MIN_J: an O(J log^2 J) **bitonic rank**
    (:func:`_bitonic_rank`): a compare-exchange network on (remaining,
    tie, col) triples built from static lane rolls, followed by a
    second network inverting the permutation -- the classic
    sorting-network formulation that keeps all traffic in registers.

Both produce the identical integer ranks for every valid slot (ranks of
empty slots are unused and may differ).  The crossover constant is
re-measured by ``benchmarks/engine_bench.py`` (``rank_crossover`` rows;
see docs/PERFORMANCE.md).  On CPU hosts the engine routes through
:func:`event_scan_xla`, an equivalent vectorised jnp implementation
whose per-row sort is one O(J log J) stable lexsort (the "reference
fallback" -- the Pallas path in interpret mode is reserved for kernel
tests); it optionally *accepts a precomputed rank* so the engine's
slab-fed speculative micro-steps can reuse the committing superstep's
ranking and run entirely sort-free.  Oracle:
repro.kernels.ref.event_scan_ref.

:func:`event_frontier` is the second fused primitive here: one
min/mask pass over the concatenated per-source candidate-time vectors
of the superstep engine's event sources, returning the earliest
pending instant t*, the per-source fired mask and due counts, and the
speculation horizon t_safe -- replacing a stack of 8 separate scalar
reductions per superstep.  Same three-way split (Pallas kernel / XLA
fallback / ref.event_frontier_ref oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.0e38
INF = float("inf")
LANE = 128               # TPU lane width: job-slot axis padded to it
# Padded widths above this use the bitonic rank.  Measured (XLA CPU,
# benchmarks/engine_bench.py "_rank_crossover"): pairwise wins through
# J = 512 (1.5ms vs 5.4ms at 512) and loses decisively at 1024 (32ms
# vs 11.5ms) -- the ROADMAP's "J > 256" guess was one octave early.
# The TPU bound is also capacity: the pairwise path materialises a
# [block_r, J, J] comparison cube, which at block_r = 8, J = 1024
# is 32 MB -- past VMEM -- so the bitonic is mandatory there anyway.
RANK_BITONIC_MIN_J = 512


def _pad_j_for_kernel(j: int) -> int:
    """Lane-tiled job-slot width for the Pallas path: the next multiple
    of LANE, bumped to the next power of two once the bitonic rank is
    selected (the compare-exchange network needs a pow2 width)."""
    j_pad = -(-j // LANE) * LANE
    if j_pad > RANK_BITONIC_MIN_J:
        p = 1
        while p < j_pad:
            p *= 2
        j_pad = p
    return j_pad


def _row_masks(rem, npe, pol, blk, ok):
    """Shared masking prologue of every scan variant.

    Reservation windows shrink the PE pool of time-shared rows; a down
    (row_ok == 0) row, or a fully-reserved time-shared row, is dead:
    every slot masked out of all outputs.  Returns (npe_e [R,1] f32
    effective PE pool, valid [R,J] bool, g [R,1] f32 job count).
    """
    npe_e = jnp.maximum(npe - blk, 0.0)
    dead = (ok < 0.5) | ((pol < 0.5) & (npe_e < 0.5))
    valid = (rem > 0.0) & (rem < BIG) & ~dead
    g = jnp.sum(valid.astype(jnp.float32), axis=1, keepdims=True)
    return npe_e, valid, g


def _pairwise_rank(rem, tie, valid):
    """Within-row (remaining, tie) rank via the [J, J] comparison matrix
    -- the Pallas-side ranking (O(J^2) VPU work, fully data-parallel).
    Returns (rank [R,J] f32, key, tkey) with invalid slots keyed BIG."""
    key = jnp.where(valid, rem, BIG)
    tkey = jnp.where(valid, tie, BIG)
    lt = key[:, :, None] > key[:, None, :]         # j strictly after j'
    tie_lt = (key[:, :, None] == key[:, None, :]) & \
        (tkey[:, :, None] > tkey[:, None, :])
    rank = jnp.sum((lt | tie_lt) & valid[:, None, :],
                   axis=2).astype(jnp.float32)
    return rank, key, tkey


def _lexsort_rank(rem, tie, valid):
    """Same rank contract as :func:`_pairwise_rank` via one stable
    O(J log J) lexsort -- the XLA-fallback ranking."""
    key = jnp.where(valid, rem, BIG)
    tkey = jnp.where(valid, tie, BIG)
    order = jnp.lexsort((tkey, key), axis=-1)       # cols by (rem, tie)
    rank = jnp.argsort(order, axis=-1).astype(jnp.float32)  # inverse perm
    return rank, key, tkey


def _bitonic_exchange(arrays, lane, stride, size):
    """One compare-exchange stage of the bitonic network, lexicographic
    on ``(arrays[0], arrays[1])``; the rest ride along as payload.

    Element ``i`` pairs with ``i ^ stride`` -- reached with two lane
    rotations (``pltpu.roll``: Mosaic's ``tpu.dynamic_rotate`` in a
    kernel, ``jnp.roll`` elsewhere) and a select, so the whole network
    lowers to VPU register traffic (no gathers).  ``stride`` and
    ``size`` (the current bitonic block length, ascending where
    ``i & size == 0``) are static ints.  Which rotation brings in the
    partner is read off the rotated lane index itself, so the stage
    holds whichever way the hardware rotates.
    """
    n = lane.shape[-1]
    axis = lane.ndim - 1
    fwd = pltpu.roll(lane, stride, axis)
    from_fwd = fwd == (lane ^ stride)     # this rotation brings my partner
    partner = [jnp.where(from_fwd, pltpu.roll(a, stride, axis),
                         pltpu.roll(a, n - stride, axis)) for a in arrays]
    k, tk, pk, ptk = arrays[0], arrays[1], partner[0], partner[1]
    mine_gt = (k > pk) | ((k == pk) & (tk > ptk))
    partner_gt = (pk > k) | ((pk == k) & (ptk > tk))
    # lower lane of an ascending pair (or upper of a descending one)
    # keeps the smaller element: take the partner's when it is smaller
    keep_min = ((lane & stride) == 0) == ((lane & size) == 0)
    take = (keep_min & mine_gt) | (~keep_min & partner_gt)
    return [jnp.where(take, p, a) for a, p in zip(arrays, partner)]


def _bitonic_sort(arrays):
    """Bitonic-sort ``arrays`` (lex keys ``arrays[0], arrays[1]`` +
    payload) along the last axis, which must be a power of two.

    The O(log^2 J) stage schedule is unrolled at trace time, so every
    rotation has a static shift (Mosaic lowers no traced-shift
    ``jnp.roll``): 55 stages at J = 1024, 66 at J = 2048.
    """
    n = arrays[0].shape[-1]
    assert n & (n - 1) == 0, "bitonic width must be a power of two"
    lane = jax.lax.broadcasted_iota(jnp.int32, arrays[0].shape,
                                    arrays[0].ndim - 1)
    size = 2
    while size <= n:
        stride = size // 2
        while stride:
            arrays = _bitonic_exchange(arrays, lane, stride, size)
            stride //= 2
        size *= 2
    return arrays


def _bitonic_rank(rem, tie, valid):
    """Same valid-slot rank contract as :func:`_pairwise_rank` /
    :func:`_lexsort_rank` in O(J log^2 J) compare-exchanges.

    Two network passes: sort ``(key, tie, col)`` triples, then sort the
    resulting column permutation back against a position payload --
    sorting a permutation by value *is* its inverse, i.e. the rank.
    Ranks of invalid slots (all keyed (BIG, BIG)) are an arbitrary
    permutation of the tail positions -- unused by every consumer, but
    note they differ from the other two implementations' tail ranks.
    Requires a power-of-two J (the wrappers pad).
    """
    key = jnp.where(valid, rem, BIG)
    tkey = jnp.where(valid, tie, BIG)
    # int32 iota cast after: Mosaic has no float iota
    col = jax.lax.broadcasted_iota(jnp.int32, rem.shape,
                                   rem.ndim - 1).astype(jnp.float32)
    _, _, scol = _bitonic_sort([key, tkey, col])
    zero = jnp.zeros_like(scol)
    _, _, rank = _bitonic_sort([scol, zero, col])
    return rank, key, tkey


def _kernel_rank(rem, tie, valid):
    """Static-shape rank selection for the Pallas kernels: pairwise
    O(J^2) below the crossover, bitonic O(J log^2 J) above it."""
    if rem.shape[-1] > RANK_BITONIC_MIN_J:
        return _bitonic_rank(rem, tie, valid)
    return _pairwise_rank(rem, tie, valid)


def _fig8_rates(rem, rank, valid, g, mips, npe_e, pol):
    """Fig 8 share divisor -> per-slot rate, shared by all variants."""
    k = jnp.floor(g / jnp.maximum(npe_e, 1.0))     # [R,1] min jobs per PE
    extra = g - k * jnp.maximum(npe_e, 1.0)
    msc = (npe_e - extra) * k                      # max-share count
    divisor = k + (rank >= msc).astype(jnp.float32)
    # g <= P_eff: everyone gets a full PE
    divisor = jnp.where(g <= npe_e, 1.0, divisor)
    # space-shared rows: every resident job owns a whole PE
    divisor = jnp.where(pol > 0.5, 1.0, divisor)
    return jnp.where(valid, mips / jnp.maximum(divisor, 1.0), 0.0)


def _kernel(remaining_ref, tie_ref, mips_ref, pe_ref, policy_ref,
            blocked_ref, ok_ref, rate_ref, tmin_ref, amin_ref, occ_ref,
            *maybe_rank_ref):
    rem = remaining_ref[...]                       # [R, J] f32
    tie = tie_ref[...]                             # [R, J] f32
    mips = mips_ref[...]                           # [R, 1]
    npe = pe_ref[...]                              # [R, 1] f32
    pol = policy_ref[...]                          # [R, 1] f32 (1 = space)
    blk = blocked_ref[...]                         # [R, 1] f32 reserved PEs
    ok = ok_ref[...]                               # [R, 1] f32 (1 = up)
    r, j = rem.shape

    npe_e, valid, g = _row_masks(rem, npe, pol, blk, ok)
    rank, key, tkey = _kernel_rank(rem, tie, valid)
    rate = _fig8_rates(rem, rank, valid, g, mips, npe_e, pol)
    rate_ref[...] = rate

    t = jnp.where(valid, rem / jnp.maximum(rate, 1e-30), BIG)
    tmin = jnp.min(t, axis=1, keepdims=True)
    tmin_ref[...] = tmin

    # per-row argmin col, FIFO ties broken by the tie key
    at_min = (t <= tmin) & valid
    cand = jnp.where(at_min, tkey, BIG)
    tie_min = jnp.min(cand, axis=1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, j), 1)
    amin_ref[...] = jnp.min(
        jnp.where(at_min & (cand <= tie_min), col, j),
        axis=1, keepdims=True)
    occ_ref[...] = g.astype(jnp.int32)
    if maybe_rank_ref:
        maybe_rank_ref[0][...] = rank


def _default_inputs(remaining, tie, policy, pe_blocked, row_ok):
    r, j = remaining.shape
    if tie is None:
        tie = jnp.broadcast_to(
            jnp.arange(j, dtype=jnp.float32)[None, :], (r, j))
    if policy is None:
        policy = jnp.zeros((r,), jnp.float32)
    if pe_blocked is None:
        pe_blocked = jnp.zeros((r,), jnp.float32)
    if row_ok is None:
        row_ok = jnp.ones((r,), jnp.float32)
    return (remaining.astype(jnp.float32), jnp.asarray(tie, jnp.float32),
            jnp.asarray(policy, jnp.float32).reshape(r),
            jnp.asarray(pe_blocked, jnp.float32).reshape(r),
            jnp.asarray(row_ok, jnp.float32).reshape(r))


def _lane_pad(remaining, tie, j: int):
    """Pad the job-slot axis for the Pallas path (see module docstring);
    padded slots are empty (remaining 0) with BIG tie keys."""
    j_pad = _pad_j_for_kernel(j)
    if j_pad == j:
        return remaining, tie, j_pad
    pad = ((0, 0), (0, j_pad - j))
    return (jnp.pad(remaining, pad),
            jnp.pad(tie, pad, constant_values=BIG), j_pad)


def event_scan(remaining, mips_eff, num_pe, tie=None, policy=None,
               pe_blocked=None, row_ok=None, *,
               block_r: int = 8, interpret: bool = False,
               with_rank: bool = False):
    """remaining: [R, J] (<=0 or >=BIG marks empty slots); tie: [R, J]
    FIFO tie-break priority (defaults to the col index); mips_eff,
    num_pe, policy: [R] (policy 0 = time-shared, 1 = space-shared);
    pe_blocked: [R] reservation-held PEs (default 0); row_ok: [R]
    up-mask (default all-up).  Returns (rate [R, J], t_min [R],
    argmin_col [R] i32, occupancy [R] i32); argmin_col is J for empty
    (or dead) rows.  ``with_rank=True`` appends the per-row (remaining,
    tie) rank table f32[R, J] (ranks of empty slots are arbitrary).

    The job-slot axis is lane-tiled internally (padded to LANE
    multiples, pow2 once the bitonic rank engages) and outputs sliced
    back, so callers never see the padding.
    """
    r, j = remaining.shape
    remaining, tie, policy, pe_blocked, row_ok = _default_inputs(
        remaining, tie, policy, pe_blocked, row_ok)
    remaining, tie, j_pad = _lane_pad(remaining, tie, j)
    block_r = min(block_r, r)
    assert r % block_r == 0, "pad the resource axis upstream"

    out_specs = [
        pl.BlockSpec((block_r, j_pad), lambda i: (i, 0)),
        pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((r, j_pad), jnp.float32),
        jax.ShapeDtypeStruct((r, 1), jnp.float32),
        jax.ShapeDtypeStruct((r, 1), jnp.int32),
        jax.ShapeDtypeStruct((r, 1), jnp.int32),
    ]
    if with_rank:
        out_specs.append(pl.BlockSpec((block_r, j_pad), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((r, j_pad), jnp.float32))
    out = pl.pallas_call(
        _kernel,
        grid=(r // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, j_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, j_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(remaining, tie,
      mips_eff.astype(jnp.float32).reshape(r, 1),
      num_pe.astype(jnp.float32).reshape(r, 1),
      policy.reshape(r, 1),
      pe_blocked.reshape(r, 1),
      row_ok.reshape(r, 1))
    rate, tmin, amin, occ = out[:4]
    # un-pad: padded slots never win the argmin, so the only out-of-J
    # value is the empty/dead-row sentinel j_pad -> remap to J.
    amin = jnp.minimum(amin[:, 0], j)
    res = (rate[:, :j], tmin[:, 0], amin, occ[:, 0])
    if with_rank:
        res = res + (out[4][:, :j],)
    return res


def event_scan_xla(remaining, mips_eff, num_pe, tie=None, policy=None,
                   pe_blocked=None, row_ok=None, *, with_rank=False,
                   rank=None):
    """Vectorised jnp fallback with identical semantics to the kernel.

    The per-row O(J log J) lexsort replaces the kernel's O(J^2) pairwise
    rank, which makes it the right path for CPU hosts where Pallas would
    run interpreted.  Bitwise-identical share arithmetic to ``_kernel``.

    ``with_rank=True`` appends the rank table to the outputs.  ``rank``
    (f32[R, J]) injects a precomputed rank and skips the lexsort
    entirely -- the engine's slab-fed speculative micro-steps pass the
    committing superstep's rank (shifted by the departed heads), making
    the whole scan sort-free.  The caller owns the proof that the
    injected rank equals the fresh lexsort rank on every valid slot
    (engine._partition_ok); everything downstream of the rank is the
    identical arithmetic either way.
    """
    r, j = remaining.shape
    remaining, tie, policy, pe_blocked, row_ok = _default_inputs(
        remaining, tie, policy, pe_blocked, row_ok)
    mips = mips_eff.astype(jnp.float32)[:, None]
    npe = num_pe.astype(jnp.float32)[:, None]
    pol = policy[:, None]
    blk = pe_blocked[:, None]
    ok = row_ok[:, None]

    npe_e, valid, g = _row_masks(remaining, npe, pol, blk, ok)
    if rank is None:
        rank, key, tkey = _lexsort_rank(remaining, tie, valid)
    else:
        rank = jnp.asarray(rank, jnp.float32)
        tkey = jnp.where(valid, tie, BIG)
    rate = _fig8_rates(remaining, rank, valid, g, mips, npe_e, pol)

    t = jnp.where(valid, remaining / jnp.maximum(rate, 1e-30), BIG)
    tmin = jnp.min(t, axis=1, keepdims=True)
    at_min = (t <= tmin) & valid
    cand = jnp.where(at_min, tkey, BIG)
    tie_min = jnp.min(cand, axis=1, keepdims=True)
    col = jnp.broadcast_to(jnp.arange(j, dtype=jnp.int32)[None, :], (r, j))
    amin = jnp.min(jnp.where(at_min & (cand <= tie_min), col, j), axis=1)
    res = (rate, tmin[:, 0], amin,
           jnp.sum(valid, axis=1, dtype=jnp.int32))
    if with_rank:
        res = res + (rank,)
    return res


# ----------------------------------------------------------------------
# k-wave time-slab forecast: the next k completions per row in ONE pass.
# ----------------------------------------------------------------------
#
# The key fact making a whole slab computable from a single rank pass:
# within a row evolving under uninterrupted Fig 8 dynamics, jobs finish
# exactly in (remaining, tie) sort order.  The rank-0 job holds MaxShare
# and the smallest remaining, so it finishes first; after it leaves, the
# order among the survivors is preserved (smaller-remaining jobs always
# hold a rate at least as high, so gaps never close).  Ranks therefore
# never need re-sorting between waves -- wave w completes the rank-w job
# -- and the per-superstep cost of 3 segmented sorts collapses into one
# rank pass followed by k cheap analytic advance steps.

def _slab_waves(rem, rank, valid, g, mips, npe_e, pol, col, k):
    """Shared wave recurrence of the slab forecast (jnp ops only, so the
    Pallas kernel body and the XLA fallback run the same arithmetic).

    rem/rank [R, J] f32, valid [R, J] bool, col [R, J] i32 (col index);
    g/mips/npe_e/pol [R, 1] f32.  Returns (t_wave f32[R, k] -- time from
    now of the row's w-th completion, BIG-padded; col_wave i32[R, k] --
    completing column, J-padded).  Wave 0 equals event_scan's
    (t_min, argmin_col).
    """
    r, j = rem.shape
    t_acc = jnp.zeros((r, 1), jnp.float32)
    ts, cols = [], []
    for w in range(k):
        # wave w = the single-scan share formula over the survivors,
        # with job count and ranks shifted by the w departed heads
        active = valid & (rank >= w)
        rate = _fig8_rates(rem, rank - w, active, g - w, mips, npe_e,
                           pol)
        head = valid & (rank == w)
        has = jnp.sum(head.astype(jnp.float32), axis=1, keepdims=True) > 0
        dt = jnp.sum(jnp.where(head, rem / jnp.maximum(rate, 1e-30), 0.0),
                     axis=1, keepdims=True)
        t_acc = t_acc + jnp.where(has, dt, 0.0)
        ts.append(jnp.where(has, t_acc, BIG))
        cols.append(jnp.where(
            has, jnp.sum(jnp.where(head, col, 0), axis=1, keepdims=True),
            j).astype(jnp.int32))
        # advance the survivors; the head leaves the table (a tied
        # neighbour may round below 0 -- clamped, it emits a dt=0 wave)
        rem = jnp.where(head, 0.0, jnp.where(
            active, jnp.maximum(rem - rate * dt, 0.0), rem))
    return jnp.concatenate(ts, axis=1), jnp.concatenate(cols, axis=1)


# --- associative-scan formulation of the same slab -------------------
#
# The sequential recurrence above has a hidden linear structure: the
# Fig 8 rate of a job depends only on its *rank*, the wave index and
# the row statics -- never on the remaining work.  So the whole slab is
# a lower-triangular linear system.  Let A[w, p] be the rate the rank-p
# job runs at during wave w (zero once p < w or p >= g), and srem[p]
# the remaining MI of the rank-p job at wave 0.  The wave-p head
# interval then satisfies the forward substitution
#
#   dt_p = (srem_p - sum_{v<p} A[v, p] * dt_v) / A[p, p]
#
# and each wave is one homogeneous (k+1)x(k+1) matrix acting on the
# state vector (dt_0 .. dt_{k-1}, 1): identity everywhere except row p,
# which holds (-A[v, p]/A[p, p] for v < p, 0, srem_p/A[p, p]).  Matrix
# product is associative, so the composite of all k waves -- whose last
# column IS the dt vector -- evaluates in O(log k) dependent steps via
# ``jax.lax.associative_scan`` (XLA path) or a balanced static product
# tree (Pallas path), instead of k dependent wave steps.  Within-row
# completion order never inverts under Fig 8 (see the note above), so
# in exact arithmetic every dt_p is nonnegative and the sequential
# path's per-wave clamp only ever fires on exact ties; one final
# clamp ``max(dt, 0)`` reproduces it to rounding.  Wave 0's row
# composes through untouched identity rows, so t_wave[:, 0] stays
# *bitwise* equal to the sequential path (and to ``event_scan``).

def _mats_mul(b, a):
    """Batched (k+1)x(k+1) matrix product ``b @ a`` written as a
    broadcast-multiply-sum so the Pallas kernel body lowers to plain
    VPU ops (no dot_general on tiny non-tile shapes)."""
    return jnp.sum(b[..., :, :, None] * a[..., None, :, :], axis=-2)


def _compose_waves(a, b):
    """The associative wave-compose operator: ``b`` after ``a``.

    Operands are stacks of homogeneous wave matrices [..., k+1, k+1];
    composing later-wave ``b`` onto earlier-prefix ``a`` is the matrix
    product ``b @ a``, which is associative -- the property test in
    tests/test_kernels.py checks it on random wave matrices.
    """
    return _mats_mul(b, a)


def _slab_assoc_inputs(rem, rank, valid, g, mips, npe_e, pol, col, k):
    """Rank-indexed slab inputs: per-wave rate table A f32[R, k, k]
    (A[:, w, p] = wave-w rate of the rank-p job), head remaining
    srem f32[R, k], head column scol i32[R, k], wave-exists mask
    has bool[R, k] (rank p exists iff p < g)."""
    r, j = rem.shape
    w_i = jax.lax.broadcasted_iota(jnp.float32, (1, k, k), 1)
    p_i = jax.lax.broadcasted_iota(jnp.float32, (1, k, k), 2)
    g3 = g[:, :, None]                                  # [R, 1, 1]
    act = (p_i >= w_i) & (p_i < g3)
    a_mat = _fig8_rates(p_i, p_i - w_i, act, g3 - w_i, mips[:, :, None],
                        npe_e[:, :, None], pol[:, :, None])
    p1 = jax.lax.broadcasted_iota(jnp.float32, (r, k), 1)
    has = p1 < g                                        # [R, k]
    srems, scols = [], []
    for p in range(k):
        head = valid & (rank == p)
        srems.append(jnp.sum(jnp.where(head, rem, 0.0), axis=1,
                             keepdims=True))
        scols.append(jnp.sum(jnp.where(head, col, 0), axis=1,
                             keepdims=True))
    return (a_mat, jnp.concatenate(srems, axis=1),
            jnp.concatenate(scols, axis=1), has)


def _wave_matrices(a_mat, srem, k):
    """The k homogeneous wave matrices as a list of [R, k+1, k+1].

    Entries are clipped to the finite +-BIG range: a zero-rate head
    (mips 0 under full calendar load) divides by the 1e-30 guard like
    the sequential path, and an inf entry would poison unrelated rows
    of the product with 0 * inf = nan.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, (1, k + 1, k + 1), 1)
    colx = jax.lax.broadcasted_iota(jnp.int32, (1, k + 1, k + 1), 2)
    eye = (row == colx).astype(jnp.float32)
    v_i = jax.lax.broadcasted_iota(jnp.float32, (1, k), 1)
    mats = []
    for p in range(k):
        d = jnp.maximum(a_mat[:, p, p], 1e-30)[:, None]      # [R, 1]
        coeff = jnp.where(v_i < p, -a_mat[:, :, p] / d, 0.0)  # [R, k]
        rowvals = jnp.clip(
            jnp.concatenate([coeff, srem[:, p:p + 1] / d], axis=1),
            -BIG, BIG)                                       # [R, k+1]
        mats.append(jnp.where(row == p, rowvals[:, None, :], eye))
    return mats


def _slab_waves_assoc(rem, rank, valid, g, mips, npe_e, pol, col, k,
                      *, tree=False):
    """Associative-scan evaluation of :func:`_slab_waves` -- same
    signature and (t_wave, col_wave) contract, O(log k) dependent
    steps.  ``tree=True`` composes via a balanced static product tree
    (the Pallas kernel body); the default routes through
    ``jax.lax.associative_scan``.
    """
    r, j = rem.shape
    a_mat, srem, scol, has = _slab_assoc_inputs(
        rem, rank, valid, g, mips, npe_e, pol, col, k)
    mats = _wave_matrices(a_mat, srem, k)
    if tree:
        # balanced static product tree; identity padding keeps pairs
        # whole (built from broadcasted_iota -- Mosaic-safe, no 1D iota)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, k + 1, k + 1), 1)
        colx = jax.lax.broadcasted_iota(jnp.int32, (1, k + 1, k + 1), 2)
        eye = (row == colx).astype(jnp.float32)
        while len(mats) > 1:
            if len(mats) % 2:
                mats.append(eye)
            mats = [_compose_waves(mats[i], mats[i + 1])
                    for i in range(0, len(mats), 2)]
        comp = mats[0]
    else:
        stacked = jnp.stack(mats, axis=0)        # [k, R, k+1, k+1]
        comp = jax.lax.associative_scan(_compose_waves, stacked)[-1]
    dt = jnp.maximum(jnp.where(has, comp[:, :k, k], 0.0), 0.0)
    t_wave = jnp.where(has, jnp.cumsum(dt, axis=1), BIG)
    col_wave = jnp.where(has, scol, j).astype(jnp.int32)
    return t_wave, col_wave


def _slab_kernel(remaining_ref, tie_ref, mips_ref, pe_ref, policy_ref,
                 blocked_ref, ok_ref, t_ref, col_ref, *, k, assoc):
    rem = remaining_ref[...]
    tie = tie_ref[...]
    mips = mips_ref[...]
    npe = pe_ref[...]
    pol = policy_ref[...]
    blk = blocked_ref[...]
    ok = ok_ref[...]
    r, j = rem.shape

    npe_e, valid, g = _row_masks(rem, npe, pol, blk, ok)
    # one (remaining, tie) rank pass for the whole slab -- pairwise or
    # bitonic by the static padded width (see _kernel_rank)
    rank, _, _ = _kernel_rank(rem, tie, valid)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, j), 1)
    if assoc:
        t_w, col_w = _slab_waves_assoc(rem, rank, valid, g, mips, npe_e,
                                       pol, col, k, tree=True)
    else:
        t_w, col_w = _slab_waves(rem, rank, valid, g, mips, npe_e, pol,
                                 col, k)
    t_ref[...] = t_w
    col_ref[...] = col_w


def event_scan_slab(remaining, mips_eff, num_pe, k, tie=None, policy=None,
                    pe_blocked=None, row_ok=None, live=None, *,
                    block_r: int = 8, interpret: bool = False,
                    assoc: bool = True):
    """Forecast each row's next ``k`` completions in one kernel call.

    Same inputs/masking as :func:`event_scan` plus the static slab depth
    ``k`` and an optional scalar ``live`` gate: ``live=False`` turns the
    whole call into a masked no-op superstep -- every row is treated as
    masked off, so all k waves come back as the (BIG, J) empty-wave
    sentinel, bitwise identical to passing ``row_ok=False`` everywhere.
    The sweep engine commits slabs unconditionally and relies on this
    (one traced computation, no cond/select pair; see
    engine.step_sweep).  Returns ``(t_wave f32[R, k], col_wave i32[R, k])``: the time
    from now (NOT absolute time) and column of the row's w-th completion
    under uninterrupted Fig 8 dynamics -- shares recomputed in-register
    after every wave -- with BIG / J padding past the row's job count.
    Wave 0 is exactly ``event_scan``'s ``(t_min, argmin_col)``; wave
    ``w`` equals ``event_scan`` re-applied after removing the previous
    heads and advancing the survivors (the oracle iterates exactly
    that).  Space-shared rows free their PE on completion but admit
    nothing (queue admission is engine policy, not kernel math), so for
    them the slab is a forecast, not a commitment, as soon as a queue
    exists.  The [R_pad, J] state stays resident in VMEM across all k
    waves -- one rank pass amortised over the slab, instead of 3
    segmented sorts per superstep.

    ``assoc`` (static, default True) evaluates the waves through the
    associative wave-compose operator (O(log k) dependent steps, a
    balanced product tree in-kernel); ``assoc=False`` keeps the
    sequential k-step recurrence as the reference path.  Wave 0 is
    bitwise identical between the two; later waves agree to rounding
    (the same final values through a different summation order).
    """
    r, j = remaining.shape
    remaining, tie, policy, pe_blocked, row_ok = _default_inputs(
        remaining, tie, policy, pe_blocked, row_ok)
    if live is not None:
        row_ok = jnp.where(jnp.asarray(live, bool), row_ok, 0.0)
    remaining, tie, j_pad = _lane_pad(remaining, tie, j)
    block_r = min(block_r, r)
    assert r % block_r == 0, "pad the resource axis upstream"
    assert k >= 1

    t_w, col_w = pl.pallas_call(
        functools.partial(_slab_kernel, k=k, assoc=assoc),
        grid=(r // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, j_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, j_pad), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, k), lambda i: (i, 0)),
            pl.BlockSpec((block_r, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, k), jnp.float32),
            jax.ShapeDtypeStruct((r, k), jnp.int32),
        ],
        interpret=interpret,
    )(remaining, tie,
      mips_eff.astype(jnp.float32).reshape(r, 1),
      num_pe.astype(jnp.float32).reshape(r, 1),
      policy.reshape(r, 1),
      pe_blocked.reshape(r, 1),
      row_ok.reshape(r, 1))
    # un-pad the wave columns: the only out-of-J value is the padded
    # empty-wave sentinel j_pad -> remap to the caller's J.
    return t_w, jnp.minimum(col_w, j)


def event_scan_slab_xla(remaining, mips_eff, num_pe, k, tie=None,
                        policy=None, pe_blocked=None, row_ok=None,
                        live=None, *, assoc: bool = True):
    """Vectorised jnp fallback for :func:`event_scan_slab` -- identical
    wave arithmetic, with the kernel's O(J^2) pairwise rank replaced by
    one O(J log J) lexsort.  ``assoc`` (default True) evaluates the
    waves through ``jax.lax.associative_scan`` over the homogeneous
    wave matrices; ``assoc=False`` runs the sequential recurrence
    (shared ``_slab_waves``)."""
    r, j = remaining.shape
    remaining, tie, policy, pe_blocked, row_ok = _default_inputs(
        remaining, tie, policy, pe_blocked, row_ok)
    if live is not None:
        row_ok = jnp.where(jnp.asarray(live, bool), row_ok, 0.0)
    mips = mips_eff.astype(jnp.float32)[:, None]
    npe = num_pe.astype(jnp.float32)[:, None]
    pol = policy[:, None]
    blk = pe_blocked[:, None]
    ok = row_ok[:, None]

    npe_e, valid, g = _row_masks(remaining, npe, pol, blk, ok)
    rank, _, _ = _lexsort_rank(remaining, tie, valid)
    col = jnp.broadcast_to(jnp.arange(j, dtype=jnp.int32)[None, :], (r, j))
    waves = _slab_waves_assoc if assoc else _slab_waves
    return waves(remaining, rank, valid, g, mips, npe_e, pol, col, k)


# ----------------------------------------------------------------------
# Link scan: fair-share transfer forecast per link row, the network
# analogue of the Fig 8 event scan.
# ----------------------------------------------------------------------
#
# The network subsystem (repro.core.network / the engine's NETWORK event
# source) keeps in-flight transfers in a resource-major ``[L, T]``
# transfer-slot table exactly mirroring the ``[R, J]`` job-slot table:
# ``remaining`` holds bytes instead of MI, and the per-row "policy" is
# fixed -- every concurrent transfer on a link receives an equal
# **fair share** of the link's baud rate.  With ``m`` active transfers
# and ``bg`` phantom background flows riding the same link:
#
#   rate_i = baud / (m + bg)        for every active transfer i
#   t_i    = remaining_i / rate_i
#   t_min  = min_i t_i              (the link's next transfer completion)
#
# which is Fig 8 with P = 1 PE (min_jobs = g, everyone in the MaxShare
# set) plus the background-traffic offset on the divisor.  Because the
# share is uniform there is no rank to compute, so the scan is sort-free
# by construction on every backend -- the engine's piecewise-constant
# transfer integration needs no slab carry on the link side.
#
# Three-way split like event_scan: Pallas kernel (job/transfer axis
# lane-tiled to LANE multiples), vectorised XLA fallback, numpy oracle
# (ref.link_scan_ref); all share _link_math for bitwise-identical
# arithmetic.

def _link_math(rem, baud, bg, tie, cap=None):
    """Shared fair-share arithmetic (jnp only -- runs inside the Pallas
    kernel body and as the XLA fallback).

    rem/tie [L, T] f32 (rem <= 0 or >= BIG marks a free slot);
    baud/bg [L, 1] f32.  A link with non-positive or non-finite baud is
    dead: the engine's ``network.link_tabled`` predicate never routes a
    transfer onto one, but the row is masked here too so the outputs
    stay well-defined.  ``cap`` [L, 1] f32 is an optional per-row
    fair-share rate ceiling -- the shared-trunk divisor: rows behind a
    common WAN trunk get ``trunk_baud / (M + trunk_bg)`` with M the
    trunk-wide occupancy (computed by the caller across rows, since a
    row-blocked kernel grid cannot gather cross-row; see
    core/network.trunk_rate_cap).  ``cap=None`` is the private-link
    topology, bitwise-identical to the pre-trunk kernel.  Returns
    (rate [L, T], t_min [L, 1], argmin_col [L, 1] i32, occupancy
    [L, 1] i32).
    """
    l, t_n = rem.shape
    live = (baud > 0.0) & (baud < BIG)
    valid = (rem > 0.0) & (rem < BIG) & live
    m = jnp.sum(valid.astype(jnp.float32), axis=1, keepdims=True)
    rate = jnp.where(valid, baud / jnp.maximum(m + bg, 1.0), 0.0)
    if cap is not None:
        rate = jnp.where(valid, jnp.minimum(rate, cap), 0.0)
    t = jnp.where(valid, rem / jnp.maximum(rate, 1e-30), BIG)
    tmin = jnp.min(t, axis=1, keepdims=True)
    tkey = jnp.where(valid, tie, BIG)
    at_min = (t <= tmin) & valid
    cand = jnp.where(at_min, tkey, BIG)
    tie_min = jnp.min(cand, axis=1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, t_n), 1)
    amin = jnp.min(jnp.where(at_min & (cand <= tie_min), col, t_n),
                   axis=1, keepdims=True)
    return rate, tmin, amin, m.astype(jnp.int32)


def _link_kernel(rem_ref, tie_ref, baud_ref, bg_ref, rate_ref,
                 tmin_ref, amin_ref, occ_ref):
    rate, tmin, amin, occ = _link_math(rem_ref[...], baud_ref[...],
                                       bg_ref[...], tie_ref[...])
    rate_ref[...] = rate
    tmin_ref[...] = tmin
    amin_ref[...] = amin
    occ_ref[...] = occ


def _link_kernel_cap(rem_ref, tie_ref, baud_ref, bg_ref, cap_ref,
                     rate_ref, tmin_ref, amin_ref, occ_ref):
    rate, tmin, amin, occ = _link_math(rem_ref[...], baud_ref[...],
                                       bg_ref[...], tie_ref[...],
                                       cap=cap_ref[...])
    rate_ref[...] = rate
    tmin_ref[...] = tmin
    amin_ref[...] = amin
    occ_ref[...] = occ


def _link_defaults(remaining, tie, bg):
    l, t_n = remaining.shape
    if tie is None:
        tie = jnp.broadcast_to(
            jnp.arange(t_n, dtype=jnp.float32)[None, :], (l, t_n))
    if bg is None:
        bg = jnp.zeros((l,), jnp.float32)
    return (remaining.astype(jnp.float32), jnp.asarray(tie, jnp.float32),
            jnp.asarray(bg, jnp.float32).reshape(l))


def link_scan(remaining, baud, bg=None, tie=None, cap=None, *,
              block_l: int = 8, interpret: bool = False):
    """Fair-share link scan over the [L, T] transfer-slot table.

    remaining: [L, T] bytes still to move (<= 0 or >= BIG marks a free
    slot); baud: [L] link capacity in bytes/time-unit; bg: [L] phantom
    background flows sharing each link (default 0; may be fractional);
    tie: [L, T] FIFO tie-break key for the argmin (defaults to the col
    index; the engine passes the flat gridlet index); cap: optional
    [L] per-row fair-share rate ceiling -- the shared-trunk divisor
    (see ``_link_math``; None = private-link topology, bitwise-frozen
    legacy kernel).  Returns (rate [L, T], t_min [L], argmin_col [L]
    i32, occupancy [L] i32); argmin_col is T for empty (or dead) rows.
    The transfer axis is lane-tiled internally (padded to LANE
    multiples, outputs sliced back) -- no power-of-two bump: fair
    shares need no rank network.
    """
    l, t_n = remaining.shape
    remaining, tie, bg = _link_defaults(remaining, tie, bg)
    t_pad = max(-(-t_n // LANE) * LANE, LANE)
    if t_pad != t_n:
        pad = ((0, 0), (0, t_pad - t_n))
        remaining = jnp.pad(remaining, pad)
        tie = jnp.pad(tie, pad, constant_values=BIG)
    block_l = min(block_l, l)
    assert l % block_l == 0, "pad the link axis upstream"

    row_spec = pl.BlockSpec((block_l, t_pad), lambda i: (i, 0))
    col_spec = pl.BlockSpec((block_l, 1), lambda i: (i, 0))
    in_specs = [row_spec, row_spec, col_spec, col_spec]
    inputs = [remaining, tie,
              jnp.asarray(baud, jnp.float32).reshape(l, 1),
              bg.reshape(l, 1)]
    kernel = _link_kernel
    if cap is not None:
        kernel = _link_kernel_cap
        in_specs = in_specs + [col_spec]
        inputs = inputs + [jnp.asarray(cap, jnp.float32).reshape(l, 1)]

    rate, tmin, amin, occ = pl.pallas_call(
        kernel,
        grid=(l // block_l,),
        in_specs=in_specs,
        out_specs=[
            row_spec,
            col_spec,
            col_spec,
            col_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((l, t_pad), jnp.float32),
            jax.ShapeDtypeStruct((l, 1), jnp.float32),
            jax.ShapeDtypeStruct((l, 1), jnp.int32),
            jax.ShapeDtypeStruct((l, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*inputs)
    # un-pad: the only out-of-T value is the empty/dead-row sentinel
    # t_pad -> remap to the caller's T.
    return (rate[:, :t_n], tmin[:, 0], jnp.minimum(amin[:, 0], t_n),
            occ[:, 0])


def link_scan_xla(remaining, baud, bg=None, tie=None, cap=None):
    """Vectorised jnp fallback with identical semantics to the link
    kernel (shared ``_link_math``) -- the CPU hot path the engine's
    NETWORK source routes through off-TPU."""
    l, t_n = remaining.shape
    remaining, tie, bg = _link_defaults(remaining, tie, bg)
    cap = (None if cap is None
           else jnp.asarray(cap, jnp.float32).reshape(l, 1))
    rate, tmin, amin, occ = _link_math(
        remaining, jnp.asarray(baud, jnp.float32).reshape(l, 1),
        bg.reshape(l, 1), tie, cap=cap)
    return rate, tmin[:, 0], amin[:, 0], occ[:, 0]


# ----------------------------------------------------------------------
# Fused event frontier: the superstep engine's whole source fan-in in ONE
# min/mask pass.
# ----------------------------------------------------------------------
#
# Every event source exposes its pending instants as an f32 candidate
# vector (+inf = nothing pending; see repro.core.des).  The engine used
# to reduce each source separately and jnp.stack the 8 scalars -- twice
# per committing superstep (once for t*, once for the speculation
# horizon).  The frontier op takes the *concatenated* candidate vector
# plus a static segment layout and answers everything at once.  min is
# exactly associative, so the fused reductions are bitwise-identical to
# the stacked per-source ones.

def _frontier_math(cand, seg, cuts):
    """Shared frontier arithmetic (jnp only -- runs inside the Pallas
    kernel body and as the XLA fallback).

    cand [1, C] f32 candidate instants; seg [S, C] f32 0/1 membership;
    cuts [1, C] f32 0/1 horizon-cut mask.  Returns (mins [S, 1] f32
    per-source earliest instant, counts [S, 1] i32 candidates due at
    t*, safe [S, 1] f32 per-source earliest *horizon-cutting* instant).
    """
    member = seg > 0.5
    mins = jnp.min(jnp.where(member, cand, INF), axis=1, keepdims=True)
    t_star = jnp.min(mins)
    due = (cand <= t_star) & (cand < INF)
    counts = jnp.sum(jnp.where(member & due, 1.0, 0.0), axis=1,
                     keepdims=True).astype(jnp.int32)
    safe = jnp.min(jnp.where(member & (cuts > 0.5), cand, INF),
                   axis=1, keepdims=True)
    return mins, counts, safe


def _frontier_kernel(cand_ref, seg_ref, cuts_ref, mins_ref, counts_ref,
                     safe_ref):
    mins, counts, safe = _frontier_math(cand_ref[...], seg_ref[...],
                                        cuts_ref[...])
    mins_ref[...] = mins
    counts_ref[...] = counts
    safe_ref[...] = safe


def _frontier_layout(sizes, s_pad, c_pad):
    """Static [S_pad, C_pad] 0/1 membership matrix for a segment layout
    (baked as a compile-time constant)."""
    import numpy as np
    seg = np.zeros((s_pad, c_pad), np.float32)
    off = 0
    for i, n in enumerate(sizes):
        seg[i, off:off + n] = 1.0
        off += n
    return jnp.asarray(seg)


def _frontier_finish(mins, counts, safe, n_src):
    mins = mins[:n_src, 0]
    t_star = mins.min() if n_src else INF
    fired = jnp.isfinite(mins) & (mins <= t_star)
    t_safe = safe[:n_src, 0].min() if n_src else INF
    return t_star, fired, counts[:n_src, 0], t_safe, mins


def event_frontier(cand, sizes, cuts=None, *, interpret: bool = False):
    """Fused event frontier over per-source candidate instants.

    cand: f32[C] -- concatenation of every source's candidate-time
        vector (absolute instants, +inf where nothing is pending);
    sizes: static tuple of per-source segment lengths (sum == C; zero
        lengths allowed -- e.g. an empty reservation table);
    cuts: bool/f32[C] -- True where the candidate cuts the k-step
        speculation horizon (defaults to all True).  This is the
        op-level **source-aware horizon** input for callers that mix
        cut and uncut candidates in one pass; the engine instead
        expresses safety by *selection* -- its horizon frontier is fed
        only `horizon_candidates` (speculation-safe sources contribute
        none; never-firing streams are +inf) with cuts left all-True,
        which is the authoritative mechanism there.

    Returns ``(t_star f32[], fired bool[S], counts i32[S], t_safe
    f32[], per_source_min f32[S])``: the earliest pending instant
    across all sources, which sources have a candidate due at it, how
    many candidates per source are due, and the earliest
    horizon-cutting instant.  All reductions are pure mins/sums, so the
    Pallas, XLA and oracle paths agree bitwise.
    """
    n_src = len(sizes)
    c = cand.shape[0]
    assert sum(sizes) == c, "segment layout out of sync with candidates"
    if cuts is None:
        cuts = jnp.ones((c,), jnp.float32)
    s_pad = max(-(-n_src // 8) * 8, 8)
    c_pad = max(-(-c // LANE) * LANE, LANE)
    seg = _frontier_layout(sizes, s_pad, c_pad)
    cand2 = jnp.full((1, c_pad), INF).at[0, :c].set(
        cand.astype(jnp.float32))
    cuts2 = jnp.zeros((1, c_pad)).at[0, :c].set(
        jnp.asarray(cuts, jnp.float32))

    mins, counts, safe = pl.pallas_call(
        _frontier_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1, c_pad), lambda i: (0, 0)),
            pl.BlockSpec((s_pad, c_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, c_pad), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((s_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((s_pad, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((s_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((s_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(cand2, seg, cuts2)
    return _frontier_finish(mins, counts, safe, n_src)


def event_frontier_xla(cand, sizes, cuts=None):
    """Vectorised jnp fallback for :func:`event_frontier` (identical
    arithmetic via the shared ``_frontier_math``)."""
    n_src = len(sizes)
    c = cand.shape[0]
    assert sum(sizes) == c, "segment layout out of sync with candidates"
    if cuts is None:
        cuts = jnp.ones((c,), jnp.float32)
    seg = _frontier_layout(sizes, max(n_src, 1), max(c, 1))
    cand2 = jnp.full((1, max(c, 1)), INF).at[0, :c].set(
        cand.astype(jnp.float32))
    cuts2 = jnp.zeros((1, max(c, 1))).at[0, :c].set(
        jnp.asarray(cuts, jnp.float32))
    mins, counts, safe = _frontier_math(cand2, seg, cuts2)
    return _frontier_finish(mins, counts, safe, n_src)
