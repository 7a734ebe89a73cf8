"""Jitted public wrappers for the Pallas kernels.

The simulator's kernels (``event_scan``, ``event_scan_slab``,
``link_scan``, ``event_frontier``) route by ``interpret``:

* ``None`` (what the engine passes): the compiled Pallas kernel on a
  TPU backend, the vectorised XLA fallback on any other backend -- so
  on the CPU the engine never runs the Pallas interpreter;
* ``True``: the Pallas kernel in interpret mode (the kernel tests);
* ``False``: the compiled Pallas kernel.

The backend test is :func:`_on_tpu`, the one place the choice is made.
tests/test_tpu_compile.py compiles the kernels, and the engine through
them, for a described TPU v5e.

Every wrapper body runs under a ``jax.named_scope`` carrying the
kernel's public name, so device profiles (``jax.profiler.trace`` /
XProf) attribute time to ``event_scan`` / ``event_scan_slab`` /
``link_scan`` / ``event_frontier`` by name instead of a soup of fused
HLO ops -- see docs/OBSERVABILITY.md for the capture recipe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import event_scan as _event
from . import flash_attention as _flash
from . import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _auto_interpret(interpret):
    if interpret is not None:
        return interpret
    return not _on_tpu()


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                    block_q=512, block_kv=1024, interpret=None):
    """q: [B, Hq, Sq, d]; k, v: [B, Hkv, Skv, d] -> [B, Hq, Sq, d]."""
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window, cap=cap, block_q=block_q,
        block_kv=block_kv, interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "chunk", "block_h", "interpret"))
def ssd_scan(x, dt, a, b_mat, c_mat, *, chunk=256, block_h=8,
             interpret=None):
    """Mamba-2 SSD over chunks; see kernels.ssd_scan for shapes."""
    return _ssd.ssd_scan(x, dt, a, b_mat, c_mat, chunk=chunk,
                         block_h=block_h,
                         interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_r", "interpret",
                                             "with_rank"))
def event_scan(remaining, mips_eff, num_pe, tie=None, policy=None,
               pe_blocked=None, row_ok=None, rank=None, *, block_r=8,
               interpret=None, with_rank=False):
    """GridSim Fig 8 share allocation + completion forecast.

    ``pe_blocked`` [R] masks reservation-held PEs out of the share pool;
    ``row_ok`` [R] masks failed resources out of every output (see
    kernels.event_scan).  Returns (rate [R, J], t_min [R], argmin_col
    [R], occupancy [R]); ``with_rank=True`` appends the per-row
    (remaining, tie) rank table f32[R, J].
    Routing: compiled Pallas on TPU (interpret=None/False); the
    vectorised XLA fallback on non-TPU hosts (interpret=None), so the
    engine hot path stays fast on CPU; Pallas interpret mode only when
    explicitly requested (interpret=True, used by the kernel tests).
    ``rank`` injects a precomputed rank table and always routes to the
    (then sort-free, purely elementwise) XLA implementation -- the
    engine's slab-fed speculative micro-steps use it on every backend.
    """
    with jax.named_scope("event_scan"):
        if rank is not None:
            return _event.event_scan_xla(remaining, mips_eff, num_pe,
                                         tie=tie, policy=policy,
                                         pe_blocked=pe_blocked,
                                         row_ok=row_ok,
                                         with_rank=with_rank, rank=rank)
        if interpret is None and not _on_tpu():
            return _event.event_scan_xla(remaining, mips_eff, num_pe,
                                         tie=tie, policy=policy,
                                         pe_blocked=pe_blocked,
                                         row_ok=row_ok,
                                         with_rank=with_rank)
        return _event.event_scan(remaining, mips_eff, num_pe, tie=tie,
                                 policy=policy, pe_blocked=pe_blocked,
                                 row_ok=row_ok, block_r=block_r,
                                 interpret=bool(interpret),
                                 with_rank=with_rank)


@functools.partial(jax.jit, static_argnames=("k", "block_r", "interpret",
                                             "assoc"))
def event_scan_slab(remaining, mips_eff, num_pe, k=8, tie=None,
                    policy=None, pe_blocked=None, row_ok=None,
                    live=None, *, block_r=8, interpret=None,
                    assoc=True):
    """Next-k completion forecast per resource row in one fused call
    (the TPU-target primitive behind the engine's k-step superstep
    batching; see kernels.event_scan.event_scan_slab for semantics).

    ``live`` (scalar bool, optional) is the masked no-op gate:
    ``live=False`` returns all-sentinel waves, bitwise identical to
    masking every row off -- the sweep engine's unconditional slab
    commit relies on it.  Returns (t_wave [R, k] f32 -- time from now
    of each row's w-th completion, BIG-padded; col_wave [R, k] i32,
    J-padded).  Routing mirrors :func:`event_scan`: compiled Pallas on
    TPU, the vectorised XLA fallback on CPU hosts, Pallas interpret
    mode only on request.

    ``assoc`` (static, default True) evaluates the k waves through the
    associative wave-compose operator -- ``jax.lax.associative_scan``
    on the XLA path, a balanced product tree in-kernel -- for O(log k)
    dependent steps; ``assoc=False`` keeps the sequential k-step
    recurrence (the reference path the differential tests pin the scan
    against).  Wave 0 is bitwise identical either way.
    """
    with jax.named_scope("event_scan_slab"):
        if interpret is None and not _on_tpu():
            return _event.event_scan_slab_xla(remaining, mips_eff,
                                              num_pe, k, tie=tie,
                                              policy=policy,
                                              pe_blocked=pe_blocked,
                                              row_ok=row_ok, live=live,
                                              assoc=assoc)
        return _event.event_scan_slab(remaining, mips_eff, num_pe, k,
                                      tie=tie, policy=policy,
                                      pe_blocked=pe_blocked,
                                      row_ok=row_ok, live=live,
                                      block_r=block_r,
                                      interpret=bool(interpret),
                                      assoc=assoc)


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def link_scan(remaining, baud, bg=None, tie=None, cap=None, *,
              block_l=8, interpret=None):
    """Fair-share link transfer forecast (the network analogue of
    :func:`event_scan`; see kernels.event_scan.link_scan).

    ``remaining`` [L, T] bytes in flight per transfer slot, ``baud``
    [L] link capacity, ``bg`` [L] phantom background flows sharing each
    link, ``cap`` optional [L] per-row rate ceiling (the shared-trunk
    fair share computed across rows; None = private-link topology,
    bitwise-frozen legacy path).  Returns (rate [L, T], t_min [L],
    argmin_col [L], occupancy [L]).  Routing mirrors
    :func:`event_scan`: compiled Pallas on TPU, the vectorised XLA
    fallback on CPU hosts (the engine's NETWORK event source hot
    path), Pallas interpret mode only on request.
    """
    with jax.named_scope("link_scan"):
        if interpret is None and not _on_tpu():
            return _event.link_scan_xla(remaining, baud, bg=bg, tie=tie,
                                        cap=cap)
        return _event.link_scan(remaining, baud, bg=bg, tie=tie,
                                cap=cap, block_l=block_l,
                                interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("sizes", "interpret"))
def event_frontier(cand, sizes, cuts=None, *, interpret=None):
    """Fused superstep event frontier: one min/mask pass over the
    concatenated per-source candidate-time vectors.

    ``cand`` f32[C] (+inf = nothing pending), ``sizes`` the static
    per-source segment lengths, ``cuts`` bool[C] marking candidates
    that cut the k-step speculation horizon (source-aware horizons; see
    kernels.event_scan.event_frontier).  Returns (t_star, fired
    bool[S], counts i32[S], t_safe, per_source_min f32[S]).  Routing
    mirrors :func:`event_scan`: compiled Pallas on TPU, the vectorised
    XLA fallback on CPU hosts, Pallas interpret mode on request.
    """
    with jax.named_scope("event_frontier"):
        if interpret is None and not _on_tpu():
            return _event.event_frontier_xla(cand, sizes, cuts=cuts)
        return _event.event_frontier(cand, sizes, cuts=cuts,
                                     interpret=bool(interpret))
