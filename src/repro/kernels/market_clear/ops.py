"""Jitted wrapper: hierarchical clearing via the Pallas kernel (TPU) or
the pure-jnp oracle (CPU / differentiability).

Both backends consume the SAME sorted-book view (``state["order"] /
["sorted_gseg"] / ["seg_start"]`` plus the current bid-table columns)
through ONE aggregate producer — ``ref._prefix_aggregates``'s
segment-major (n_seg, k) ranked slabs + distinct-second-tenant
fall-backs — and run the hierarchical 2-way path merge down the tree
(``ref.clear_sorted_from_aggs`` in jnp; ``kernel.clear_pallas`` per
VMEM leaf block).  The normalized contract (docs/DESIGN.md §3), from
both backends, is::

    (rate, best_level, cand_slots, truncated, evict)

with ``cand_slots`` LEAF-MAJOR (n_leaves, k+1), ranked (price desc,
seq asc) along the last axis with -1 holes at excluded/sub-floor ranks
— no transposes or backend special-casing for callers.

``interpret=None`` inherits the package default
(``repro.kernels.common``); ``BatchEngine`` always passes its
constructor-resolved setting explicitly, so an engine built for
compiled mode can never be silently dropped into the interpreter by a
callee default (lcheck rule LC001, the PR 4 bug class).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels.common import resolve_interpret
from repro.kernels.market_clear import ref as R
from repro.kernels.market_clear.kernel import clear_pallas


def clear(order, sorted_gseg, seg_start, prices, tenants, seqs,
          level_floor, level_off: Tuple[int, ...],
          strides: Tuple[int, ...], owner, limit, k: int, *,
          health=None, use_pallas: bool = False,
          interpret: Optional[bool] = None, block: int = 1024):
    return _clear(order, sorted_gseg, seg_start, prices, tenants, seqs,
                  level_floor, level_off, strides, owner, limit, k,
                  health=health, use_pallas=use_pallas,
                  interpret=resolve_interpret(interpret), block=block)


@functools.partial(jax.jit, static_argnames=(
    "level_off", "strides", "k", "use_pallas", "interpret", "block"))
def _clear(order, sorted_gseg, seg_start, prices, tenants, seqs,
           level_floor, level_off: Tuple[int, ...],
           strides: Tuple[int, ...], owner, limit, k: int, *,
           health, use_pallas: bool, interpret: bool, block: int):
    n_seg = seg_start.shape[0] - 1
    # one scope over the whole pass on both backends, so the trace
    # charges the same work to it whatever implements it
    with jax.named_scope("market_clear"):
        aggs = R._prefix_aggregates(order, sorted_gseg, seg_start, prices,
                                    tenants, seqs, n_seg, k)
        if use_pallas:
            out = clear_pallas(*aggs, tuple(level_floor), level_off,
                               strides, owner, limit, block=block,
                               interpret=interpret)
        else:
            out = R.clear_sorted_from_aggs(aggs, tuple(level_floor),
                                           level_off, strides, owner,
                                           limit, k)
        if health is not None:
            # One shared mask AFTER backend dispatch: non-up leaves get
            # all-hole slates, floor-only rates, and floor-pressure-only
            # evicts — identical on both backends by construction.
            out = R.apply_health_mask(health, *out, tuple(level_floor),
                                      strides, owner, limit)
    return out
