"""The economy layer: deadline/budget determination (paper 4.2.3) and
the dynamic pricing models of the Buyya thesis (cs/0204048, ch. 4).

Deadline / budget from D- and B-factors:

    Deadline = T_MIN + D_FACTOR * (T_MAX - T_MIN)        (Eq 1)
    Budget   = C_MIN + B_FACTOR * (C_MAX - C_MIN)        (Eq 2)

Interpretations (documented because the paper defines the terms in prose):
  T_MIN: all jobs processed in parallel with the fastest resources given
         priority == ideal makespan lower bound total_MI / sum(peak rates).
  T_MAX: all jobs processed serially on the slowest resource
         == total_MI / min(per-PE MIPS).
  C_MIN: every job on the cheapest G$-per-MI resource.
  C_MAX: every job on the costliest G$-per-MI resource.

D<0 / B<0 never complete; D>=1 / B>=1 always complete while resources
remain available -- both properties are asserted in tests.

Pricing models
--------------
``fleet.cost_per_mi`` (the Table 2 G$/MI trading metric) is the
*base* (advertised) price; the engine carries the *posted* per-MI price
in ``SimState.price`` and the MARKET / AUCTION event sources
(engine._make_sources) move it.  Prices live in per-MI units so the
broker reads them directly -- re-deriving the metric in-loop from a
carried cost_per_sec would divide a loop-carried array by an invariant,
which XLA may compile differently per execution path (reciprocal
rewrites), breaking the engine's bitwise cross-path contract:

  * :func:`commodity_reprice` -- the commodity-market model: a
    posted-price adjustment driven by excess demand (resident jobs vs
    PE capacity), clamped to ``[floor, cap] * base``.  Deterministic:
    no RNG, so the source is naturally maskable.
  * :func:`auction_round` -- one sealed-bid tender round: every
    resource owner submits an asking-price factor drawn from its PRNG
    stream and the posted price becomes ``base * bid``.  Rounds are
    deterministic given the key (the engine consumes one split per
    fired round, with the masked-contract select-back on declined
    lanes -- see docs/ARCHITECTURE.md).

The broker prices everything off the posted price (``state.price`` IS
the G$/MI trading metric), so a repriced grid shifts which resources
the DBC strategies buy without touching the Fig 8 rate arithmetic --
pricing rounds therefore carry NO slab-invalidation duty.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# SimParams.pricing_model codes (kept here: pricing is economy policy,
# the engine only routes them).
PRICE_STATIC = 0     # fleet.cost_per_sec, never repriced (the default)
PRICE_COMMODITY = 1  # commodity-market posted-price adjustment
PRICE_AUCTION = 2    # periodic sealed-bid auction/tender rounds

_PRICING_NAMES = {"static": PRICE_STATIC, "commodity": PRICE_COMMODITY,
                  "auction": PRICE_AUCTION}


def as_pricing_model(model) -> int:
    """Normalise a Scenario pricing knob ("commodity", "auction",
    "static", an int code, or None) to a PRICE_* int."""
    if model is None:
        return PRICE_STATIC
    if isinstance(model, str):
        return _PRICING_NAMES[model]
    return int(model)


def commodity_reprice(price, base, demand, gain, floor, cap):
    """One commodity-market posted-price adjustment.

    ``demand`` is resident jobs per PE (1.0 = exactly subscribed);
    excess demand raises the posted price by ``gain`` per unit, idle
    capacity lowers it, and the result is clamped to
    ``[floor * base, cap * base]`` -- which also keeps every repriced
    cost positive and finite for any finite inputs (property-tested).
    """
    newp = price * (1.0 + gain * (demand - 1.0))
    return jnp.clip(newp, base * floor, base * cap)


def auction_round(key, base, floor, cap):
    """One sealed-bid auction/tender round: per-resource asking-price
    factors drawn uniformly from ``[floor, cap)``; the posted price
    becomes ``base * bid``.  Deterministic given ``key``."""
    bids = jax.random.uniform(key, base.shape, minval=floor, maxval=cap)
    return base * bids


def t_min(fleet, total_mi, registered=None):
    rate = fleet.peak_rate()
    if registered is not None:
        rate = jnp.where(registered, rate, 0.0)
    return total_mi / jnp.maximum(rate.sum(), 1e-30)


def t_max(fleet, total_mi, registered=None):
    mips = fleet.mips_per_pe
    if registered is not None:
        mips = jnp.where(registered, mips, jnp.inf)
    return total_mi / jnp.maximum(mips.min(), 1e-30)


def c_min(fleet, total_mi, registered=None):
    cpm = fleet.cost_per_mi
    if registered is not None:
        cpm = jnp.where(registered, cpm, jnp.inf)
    return total_mi * cpm.min()


def c_max(fleet, total_mi, registered=None):
    cpm = fleet.cost_per_mi
    if registered is not None:
        cpm = jnp.where(registered, cpm, -jnp.inf)
    return total_mi * cpm.max()


def deadline_from_factor(fleet, total_mi, d_factor, registered=None):
    lo = t_min(fleet, total_mi, registered)
    hi = t_max(fleet, total_mi, registered)
    return lo + d_factor * (hi - lo)


def budget_from_factor(fleet, total_mi, b_factor, registered=None):
    lo = c_min(fleet, total_mi, registered)
    hi = c_max(fleet, total_mi, registered)
    return lo + b_factor * (hi - lo)
