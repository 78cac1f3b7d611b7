"""Batch market engine: the paper's full renegotiation loop as fixed-shape
array ops (beyond-paper scale path; the event-driven ``repro.core.market``
is the paper-faithful reference, and tests/test_differential.py pins the
two against each other on random traces).

One type-tree with regular strides (leaf ancestor at level d = leaf //
stride[d]). The engine holds a bounded bid table (a ring buffer of OCO
scoped orders) plus per-leaf ownership state and per-tenant bills, and the
jitted ``step`` runs one complete market epoch:

  step(state, t, new_bids, floor_updates, relinquish)
      -> (state, transfers, bills)

  1. **Billing accrual** — every owned leaf accrues ``rate * dt`` into its
     owner's bill (``bill = ∫ rate dt``), where ``rate`` is the cached
     charged rate from the end of the previous step (rates only change at
     step boundaries, so the integral is exact).
  2. **Deferred evictions** — retention-limit crossings deferred by
     ``min_holding_s`` fire once the holding window has elapsed.
  3. **Operator floor updates** — per-level proposals (-1 = no change);
     drops are bounded by ``floor_fall_rate`` per hour since that node's
     last update.
  4. **Bid admission** — incoming bids are clipped to ``max_bid_multiple``
     x the scope's reference price (max of path floors, top of the scope's
     book, charged rates under the scope) and inserted into the table.
     Insertion skips over live resting orders (a full table drops the
     overflow and counts it in ``state["dropped"]`` instead of silently
     overwriting the book).
  5. **Clear / evict / transfer cascade** — repeat until fixpoint:
     recompute the per-level ranked aggregates from the sorted book view
     and the clearing pass (jnp oracle or Pallas kernel: per-leaf charged
     rate, ranked owner-excluded top-K candidate slate, eviction mask);
     evict owners whose rate exceeds their retention limit (outside the
     min-holding window); hand each evicted / explicitly relinquished /
     idle leaf to its best covering bid meeting the path floor.  One wave
     runs K in-wave claim rounds: a winning order is consumed everywhere
     atomically (OCO) and wins at most one leaf per round (lowest leaf
     index), and a contested leaf falls through to its slate runner-up
     *within the wave* instead of waiting for the next one — a cold-start
     flood of M marketable bids resolves in O(ceil(M/K)) waves instead of
     O(M).  Fall-through stays bit-identical to the K=1 cascade: an
     evicted leaf re-checks its retention limit against each fall-through
     price (pressure that was consumed no longer evicts), and a leaf that
     exhausts a possibly truncated slate freezes in-wave resolution and
     waits for the next full re-clear.  Leaves nobody covers fall back to
     the operator.  The loop is a ``lax.while_loop`` (wave count
     observable via ``state["waves"]``, its claim rounds via
     ``state["rounds"]`` and its frozen waves via ``state["frozen"]``)
     so the whole step stays jitted.  The rounds carry each slate
     entry's liveness and drop the few slots a round claims from it;
     a round that claims more than ``TRACKED_CLAIMS`` slots re-gathers
     it from the bid table (counted in ``state["liveness_gathers"]``).

**Sorted-book invariant.**  The engine maintains a segment-sorted view
of the bid table — ``state["order"]`` (slot permutation),
``state["sorted_gseg"]`` (segment key per sorted position) and
``state["seg_start"]`` (per-segment start offsets) — sorted by
``(segment asc, price desc, seq asc)`` where a segment is one
(level, node) book, globally indexed ``level_off[level] + node``, and
dead slots carry the sentinel segment ``n_seg_total``.  ``place``
maintains the view *incrementally* (docs/DESIGN.md §10): it sorts only
the incoming ``(b_max,)`` batch and 2-way merges it into the live
prefix, falling back to a full lexsort only when the dead fraction of
the old span exceeds ``resort_dead_frac`` (``state["resorts"]`` counts
those full sorts).  Every other mutation (cancel, OCO consumption
inside cascade waves) only KILLS entries — never moves, re-prices or
revives them — so between merges each live
slot still sits inside its segment's ``[seg_start[g], seg_start[g+1])``
range in (price desc, seq asc) order.  Killed entries are skipped via a
liveness cumsum, making per-wave aggregate maintenance O(capacity) flat
(contiguous-prefix gathers + two scatters) instead of K scatter-sweeps
per level (``ref.sorted_segment_aggregates``).

**Seq-stamp semantics.**  ``state["seq"]`` carries a per-order arrival
stamp from the monotone counter ``state["next_seq"]``, assigned in
batch-position order by ``place``.  All equal-price tie-breaks — the
ranked per-segment aggregates, the clearing kernel's candidate merge and
the prefix-safety bounds — use (price desc, seq asc), i.e. TRUE arrival
order, bit-identical to the event engine's ``Order.seq`` priority even
after the ring allocator laps the table and slot order stops matching
arrival order.  (The stamp is int32; it wraps after ~2.1e9 orders —
re-init the engine before that.)

``transfers`` reports per-leaf {moved, old, new} owner ids for the step;
``bills`` is the cumulative per-tenant bill vector. Tenants are dense int
ids (< n_tenants); ``repro.market_jax.bridge`` maps the simulator's string
tenants and Topology node ids onto this layout.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.market import VolatilityControls
from repro.kernels.common import resolve_interpret
from repro.kernels.market_clear import ref as R
from repro.kernels.market_clear import ops as clear_ops

NEG = R.NEG
EPSF = R.EPSF
HEALTH_UP = R.HEALTH_UP
HEALTH_DRAINING = R.HEALTH_DRAINING
HEALTH_DOWN = R.HEALTH_DOWN

# claims per round that the cascade drops from the carried slate
# liveness by compare (the max and the min of the claimed slots); a
# round that claims more re-gathers the liveness from the consumed-slot
# table (every round of the benchmark's arrival and steady traffic
# claims one slot)
TRACKED_CLAIMS = 2


@dataclass(frozen=True)
class TreeSpec:
    """Regular type-tree: strides per level, leaf->root order.
    E.g. (1, 8, 32, 128, n_leaves) = instance/host/rack/zone/root."""
    n_leaves: int
    strides: Tuple[int, ...]

    @property
    def n_levels(self) -> int:
        return len(self.strides)

    def nodes_at(self, d: int) -> int:
        return -(-self.n_leaves // self.strides[d])


class BatchEngine:
    def __init__(self, tree: TreeSpec, capacity: int = 1 << 16,
                 use_pallas: bool = False, n_tenants: int = 1024,
                 controls: Optional[VolatilityControls] = None,
                 interpret: Optional[bool] = None, k: int = 8,
                 incremental_sort: bool = True,
                 resort_dead_frac: float = 0.5) -> None:
        self.tree = tree
        self.capacity = capacity
        self.use_pallas = use_pallas
        self.n_tenants = n_tenants
        self.controls = controls or VolatilityControls()
        # None = the package default (interpret off-TPU, compiled on
        # TPU) — resolved once here; every clearing entry point then
        # inherits the resolved constructor setting (lcheck LC001)
        self.interpret = resolve_interpret(interpret)
        self.k = max(1, int(k))   # contested claims resolved per wave
        # sorted-view maintenance policy: with incremental_sort, place()
        # sorts only the incoming batch and 2-way merges it into the
        # live view; the full-table lexsort runs only when the dead
        # fraction of the live span exceeds resort_dead_frac (hole
        # compaction amortized across epochs).  False = always lexsort
        # (the pre-incremental behaviour; kept for differential tests).
        self.incremental_sort = bool(incremental_sort)
        self.resort_dead_frac = float(resort_dead_frac)
        # global segment layout: segment id of (level d, node i) is
        # level_off[d] + i; n_seg_total is the dead-slot sentinel
        off, acc = [], 0
        for d in range(tree.n_levels):
            off.append(acc)
            acc += tree.nodes_at(d)
        self.level_off = tuple(off)
        self.n_seg_total = acc

    def init_state(self) -> Dict[str, jax.Array]:
        t = self.tree
        cap = self.capacity
        return {
            # bid table (ring buffer of OCO scoped orders)
            "price": jnp.full((cap,), NEG, jnp.float32),
            "blimit": jnp.full((cap,), jnp.inf, jnp.float32),
            "level": jnp.zeros((cap,), jnp.int32),
            "node": jnp.zeros((cap,), jnp.int32),
            "tenant": jnp.full((cap,), -1, jnp.int32),
            "seq": jnp.zeros((cap,), jnp.int32),    # arrival stamps
            "next_seq": jnp.zeros((), jnp.int32),   # monotone counter
            "head": jnp.zeros((), jnp.int32),       # ring-buffer cursor
            "dropped": jnp.zeros((), jnp.int32),    # overflow drop count
            # sorted book view (see module docstring): slot permutation,
            # per-position segment key, per-segment start offsets
            "order": jnp.arange(cap, dtype=jnp.int32),
            "sorted_gseg": jnp.full((cap,), self.n_seg_total, jnp.int32),
            "seg_start": jnp.zeros((self.n_seg_total + 1,), jnp.int32),
            # per-leaf ownership
            "owner": jnp.full((t.n_leaves,), -1, jnp.int32),
            "limit": jnp.full((t.n_leaves,), jnp.inf, jnp.float32),
            "acq_t": jnp.zeros((t.n_leaves,), jnp.float32),
            "rate": jnp.zeros((t.n_leaves,), jnp.float32),
            # per-leaf failure-domain health (docs/DESIGN.md §11):
            # 0 up, 1 draining (no new owners, retention honored),
            # 2 down (excluded from slates, owner force-evicted)
            "health": jnp.zeros((t.n_leaves,), jnp.int32),
            # billing
            "bills": jnp.zeros((self.n_tenants,), jnp.float32),
            "t": jnp.zeros((), jnp.float32),
            # cascade instrumentation: cumulative clear/evict/transfer
            # wave count (each while_loop iteration, incl. the final
            # fixpoint-check wave)
            "waves": jnp.zeros((), jnp.int32),
            # cumulative in-wave claim rounds run, and cumulative waves
            # whose round loop froze (a truncated slate exhausted, or a
            # reclaim) and left the rest to the next full re-clear
            "rounds": jnp.zeros((), jnp.int32),
            "frozen": jnp.zeros((), jnp.int32),
            # cumulative claim rounds that claimed more than
            # TRACKED_CLAIMS slots and re-gathered the slate liveness
            "liveness_gathers": jnp.zeros((), jnp.int32),
            # sorted-view instrumentation: cumulative FULL lexsort count
            # (incremental merges don't count — see place)
            "resorts": jnp.zeros((), jnp.int32),
            # operator floors (+ per-node last-update time for the
            # floor_fall_rate bound); lists so callers can seed floors
            # by item assignment — step normalizes to tuples
            "floor": [jnp.zeros((t.nodes_at(d),), jnp.float32)
                      for d in range(t.n_levels)],
            "floor_t": [jnp.zeros((t.nodes_at(d),), jnp.float32)
                        for d in range(t.n_levels)],
        }

    # ------------------------------------------------------------------
    def _gseg(self, state):
        """Current global segment id per slot (sentinel where dead)."""
        off = jnp.array(self.level_off, jnp.int32)
        nd = jnp.array([self.tree.nodes_at(d)
                        for d in range(self.tree.n_levels)], jnp.int32)
        lvl = jnp.clip(state["level"], 0, self.tree.n_levels - 1)
        node = jnp.clip(state["node"], 0, nd[lvl] - 1)
        live = (state["price"] > NEG / 2) & (state["tenant"] >= 0)
        return jnp.where(live, off[lvl] + node,
                         jnp.int32(self.n_seg_total))

    def _resort(self, state):
        """The full-table lexsort: rebuild the sorted book view from
        scratch and bump the ``resorts`` counter.

        Called only where live entries APPEAR or change key (``place``
        — and there only when the dead fraction crossed
        ``resort_dead_frac``, or ``incremental_sort`` is off); kills
        (cancel / OCO consumption) keep the view valid."""
        order, sg = R.sort_book(self._gseg(state), state["price"],
                                state["seq"])
        state["order"] = order
        state["sorted_gseg"] = sg
        state["seg_start"] = jnp.searchsorted(
            sg, jnp.arange(self.n_seg_total + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32)
        state["resorts"] = state["resorts"] + 1
        return state

    def _merged_view(self, state, old_order, old_sg, old_live_s,
                     bs_gseg, bs_slot, n_new):
        """Incremental sorted-view maintenance: 2-way merge of the
        (already sorted) live book and a sorted incoming batch.

        ``(old_order, old_sg)`` is the pre-place view; ``old_live_s``
        marks positions whose slot was live BEFORE this place (killed
        and reused holes excluded).  ``(bs_gseg, bs_slot)`` is the
        accepted batch sorted by (gseg asc, price desc, arrival asc)
        with its first ``n_new`` entries live.  Because seq stamps are
        monotone, every live resting order predates every batch entry,
        so cross-side (segment, price) ties resolve old-first and the
        merged position of each entry is computable by counting the
        other side's strictly-preceding entries — two vectorized
        lexicographic binary searches, no table-wide sort.  Holes are
        compacted out as a side effect (the live prefix of the merged
        view is dense), which is what keeps the view's dead fraction
        from ratcheting between full lexsorts.

        Returns (order, sorted_gseg, seg_start) upholding every
        schema.py sorted-view invariant.
        """
        cap = self.capacity
        b = bs_gseg.shape[0]
        slot = jnp.arange(cap, dtype=jnp.int32)
        price = state["price"]          # post-place table columns
        tenant = state["tenant"]
        # compact the surviving live entries to the front (stable —
        # preserves the sorted order among live entries)
        r_old = jnp.cumsum(old_live_s.astype(jnp.int32)) - 1
        n_old = jnp.sum(old_live_s.astype(jnp.int32))
        comp_idx = jnp.where(old_live_s, r_old, cap)
        comp_order = jnp.zeros((cap,), jnp.int32).at[comp_idx].set(
            old_order, mode="drop")
        comp_gseg = jnp.full((cap,), self.n_seg_total, jnp.int32).at[
            comp_idx].set(old_sg, mode="drop")
        comp_price = jnp.full((cap,), NEG, jnp.float32).at[comp_idx].set(
            price[old_order], mode="drop")
        # reused holes carry NEW prices at their old (dead) positions —
        # but old_live_s is False there, so the scatter drops them.
        bs_price = price[bs_slot]

        # merged rank of each side's entries: own-side rank + count of
        # other-side entries ordered before it.  Equal (gseg, price)
        # across sides is old-first (monotone seq stamps): an old
        # entry precedes new[j] on strictly-greater keys OR ties.
        # One vectorized lexicographic lower bound over the b batch
        # entries gives cnt_old[j]; the reverse count needs NO search —
        # new[j] precedes old rank i iff cnt_old[j] <= i, so the
        # per-old-rank count is the inclusive cumsum of cnt_old's
        # histogram (O(cap + b), vs a cap-wide bisection's log(cap)
        # dependent gather rounds).
        lo = jnp.zeros((b,), jnp.int32)
        hi = jnp.full((b,), cap, jnp.int32)
        for _ in range(int(cap).bit_length() + 1):
            act = lo < hi
            mid = jnp.clip((lo + hi) >> 1, 0, cap - 1)
            kg, kp = comp_gseg[mid], comp_price[mid]
            before = (kg < bs_gseg) | ((kg == bs_gseg)
                                       & (kp >= bs_price))
            lo = jnp.where(act & before, mid + 1, lo)
            hi = jnp.where(act & ~before, mid, hi)
        cnt_old = lo
        j = jnp.arange(b, dtype=jnp.int32)
        pos_new = j + cnt_old
        valid_old = slot < n_old
        hist = jnp.zeros((cap + 1,), jnp.int32).at[
            jnp.where(j < n_new, cnt_old, cap + 1)].add(1, mode="drop")
        cnt_new = jnp.cumsum(hist)[:cap]
        pos_old = slot + cnt_new                      # rank i = position
        n_total = n_old + n_new
        # dead slots fill the tail in slot order
        live_after = (price > NEG / 2) & (tenant >= 0)
        dead = ~live_after
        pos_dead = n_total + jnp.cumsum(dead.astype(jnp.int32)) - 1
        order = jnp.zeros((cap,), jnp.int32)
        order = order.at[jnp.where(valid_old, pos_old, cap)].set(
            comp_order, mode="drop")
        order = order.at[jnp.where(j < n_new, pos_new, cap)].set(
            bs_slot, mode="drop")
        order = order.at[jnp.where(dead, pos_dead, cap)].set(
            slot, mode="drop")
        sg = jnp.full((cap,), self.n_seg_total, jnp.int32)
        sg = sg.at[jnp.where(valid_old, pos_old, cap)].set(
            comp_gseg, mode="drop")
        sg = sg.at[jnp.where(j < n_new, pos_new, cap)].set(
            bs_gseg, mode="drop")
        seg_start = jnp.searchsorted(
            sg, jnp.arange(self.n_seg_total + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32)
        return order, sg, seg_start

    # ------------------------------------------------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def place(self, state, prices, levels, nodes, tenants, limits=None):
        """Insert a batch of scoped bids into free table slots.

        Slots are allocated in ring order starting at ``head``, skipping
        over live resting orders (a wrapped cursor must not overwrite the
        book). Bids that do not fit — the table holds ``capacity`` live
        orders — are dropped and counted in ``state["dropped"]``.

        Each accepted bid is stamped with the next monotone ``seq`` (in
        batch-position order), so equal-price ties clear in TRUE arrival
        order even after the wrapped cursor starts reusing freed holes
        (slot order then no longer equals arrival order).  The sorted
        book view is rebuilt here — the one lexsort per epoch.

        NOTE: this low-level insert skips volatility clipping and does
        not re-clear; use ``step`` for full semantics."""
        if limits is None:
            limits = prices
        cap = self.capacity
        slot = jnp.arange(cap, dtype=jnp.int32)
        live_tab = (state["price"] > NEG / 2) & (state["tenant"] >= 0)
        # free slots in ring order from the cursor, SORT-FREE: rank the
        # free slots along the ring via one cumsum, then invert
        # rank -> ring offset with one scatter
        live_r = live_tab[(state["head"] + slot) % cap]
        free_rank = jnp.cumsum((~live_r).astype(jnp.int32)) - 1
        ring_of_rank = jnp.full((cap,), cap, jnp.int32).at[
            jnp.where(~live_r, free_rank, cap)].set(slot, mode="drop")
        n_free = cap - jnp.sum(live_tab.astype(jnp.int32))
        live_in = tenants >= 0
        j = jnp.cumsum(live_in.astype(jnp.int32)) - 1   # rank among live
        ok = live_in & (j < n_free)
        dest_ring = ring_of_rank[jnp.clip(j, 0, cap - 1)]
        dest = (state["head"] + jnp.clip(dest_ring, 0, cap - 1)) % cap
        idx = jnp.where(ok, dest, cap)
        old_order = state["order"]
        old_sg = state["sorted_gseg"]
        old_span = state["seg_start"][self.n_seg_total]
        old_live_s = live_tab[old_order]
        state = dict(state)
        state["price"] = state["price"].at[idx].set(prices, mode="drop")
        state["blimit"] = state["blimit"].at[idx].set(
            jnp.maximum(prices, limits), mode="drop")
        state["level"] = state["level"].at[idx].set(levels, mode="drop")
        state["node"] = state["node"].at[idx].set(nodes, mode="drop")
        state["tenant"] = state["tenant"].at[idx].set(tenants, mode="drop")
        state["seq"] = state["seq"].at[idx].set(
            state["next_seq"] + j, mode="drop")
        state["next_seq"] = state["next_seq"] + \
            jnp.sum(live_in.astype(jnp.int32))
        n_used = jnp.sum(ok.astype(jnp.int32))
        state["dropped"] = state["dropped"] + \
            jnp.sum(live_in.astype(jnp.int32)) - n_used
        state["head"] = jnp.where(
            n_used > 0,
            (state["head"] + jnp.max(jnp.where(ok, dest_ring, -1)) + 1)
            % cap, state["head"])
        if not self.incremental_sort:
            return self._resort(state)
        # ---- sorted-view maintenance (docs/DESIGN.md §10) ----
        # sort ONLY the incoming batch by (gseg asc, price desc,
        # arrival asc) and 2-way merge it into the live view; fall back
        # to the full lexsort when the view's dead fraction (holes from
        # kills since the last full sort) crossed resort_dead_frac —
        # compaction amortized across epochs.
        off = jnp.array(self.level_off, jnp.int32)
        nd = jnp.array([self.tree.nodes_at(d)
                        for d in range(self.tree.n_levels)], jnp.int32)
        lvl_b = jnp.clip(levels, 0, self.tree.n_levels - 1)
        node_b = jnp.clip(nodes, 0, nd[lvl_b] - 1)
        live_b = ok & (prices > NEG / 2)
        gseg_b = jnp.where(live_b, off[lvl_b] + node_b,
                           jnp.int32(self.n_seg_total))
        bpos = jnp.arange(prices.shape[0], dtype=jnp.int32)
        bs_gseg, _, _, bs_slot = lax.sort(
            (gseg_b, jnp.negative(jnp.where(live_b, prices, NEG)),
             bpos, jnp.where(live_b, dest, 0)), num_keys=3)
        n_new = jnp.sum(live_b.astype(jnp.int32))
        n_live_pre = jnp.sum(live_tab.astype(jnp.int32))
        dead_frac = (old_span - n_live_pre).astype(jnp.float32) \
            / jnp.maximum(old_span, 1).astype(jnp.float32)

        def full(st):
            order, sg = R.sort_book(self._gseg(st), st["price"],
                                    st["seq"])
            ss = jnp.searchsorted(
                sg, jnp.arange(self.n_seg_total + 1, dtype=jnp.int32),
                side="left").astype(jnp.int32)
            return order, sg, ss, jnp.int32(1)

        def incremental(st):
            order, sg, ss = self._merged_view(
                st, old_order, old_sg, old_live_s, bs_gseg, bs_slot,
                n_new)
            return order, sg, ss, jnp.int32(0)

        state["order"], state["sorted_gseg"], state["seg_start"], \
            did_full = lax.cond(dead_frac > self.resort_dead_frac,
                                full, incremental, state)
        state["resorts"] = state["resorts"] + did_full
        return state

    @functools.partial(jax.jit, static_argnums=0)
    def cancel_all(self, state):
        """Kill EVERY resting order in one sweep — the vectorized
        fleet's fresh-book-each-epoch policy (mirroring the
        EconAdapter's cancel-stale-orders-every-step behaviour) without
        materializing a slot-id list.  The sorted view is reset to the
        canonical empty view (identical to ``init_state``'s): a fully
        dead book has NO live span, so leaving the stale span in place
        would read as 100% dead fraction and trigger a pointless full
        lexsort at the next ``place`` — the reset keeps the
        cancel-all-each-epoch fleet loop on the incremental-merge path.
        The next ``step`` re-clears."""
        state = dict(state)
        state["price"] = jnp.full_like(state["price"], NEG)
        state["tenant"] = jnp.full_like(state["tenant"], -1)
        state["order"] = jnp.arange(self.capacity, dtype=jnp.int32)
        state["sorted_gseg"] = jnp.full(
            (self.capacity,), self.n_seg_total, jnp.int32)
        state["seg_start"] = jnp.zeros_like(state["seg_start"])
        return state

    @functools.partial(jax.jit, static_argnums=0)
    def cancel(self, state, bid_ids):
        """Deactivate bid slots. Follow with a zero-event ``step`` at the
        same timestamp so cached rates refresh before billing resumes.
        A kill keeps the sorted book view valid (dead entries are
        skipped by live-rank), so no re-sort happens here."""
        state = dict(state)
        state["price"] = state["price"].at[bid_ids].set(NEG)
        state["tenant"] = state["tenant"].at[bid_ids].set(-1)
        return state

    @functools.partial(jax.jit, static_argnums=0)
    def set_health(self, state, levels, nodes, values):
        """Batched failure-domain health update — ONE scatter over each
        domain's leaf range.  ``levels``/``nodes``/``values`` are (m,)
        int32: the failure domain is node ``nodes[i]`` at tree level
        ``levels[i]`` (0 = leaf … n_levels-1 = root) and every leaf
        under it gets ``values[i]`` (HEALTH_UP/DRAINING/DOWN).
        ``values[i] < 0`` is padding.  Events must be ordered: when two
        domains overlap, the LATER entry wins — so applying a sorted
        event batch is equivalent to applying the events one at a time,
        which is what makes recovery's fast-forward re-apply idempotent.

        Eviction of owners on newly-down leaves happens in the next
        ``step`` (billed up to that step's tick), not here — this is a
        pure metadata scatter and stays valid mid-epoch.
        """
        m = levels.shape[0]      # static under jit: batch width
        if m == 0:
            return state
        tree = self.tree
        leaf = jnp.arange(tree.n_leaves, dtype=jnp.int32)
        strides = jnp.array(tree.strides, jnp.int32)
        live = values >= 0
        lvl = jnp.clip(levels, 0, tree.n_levels - 1)
        anc = leaf[None, :] // strides[lvl][:, None]     # (m, n_leaves)
        cover = live[:, None] & (anc == nodes[:, None])
        idx = jnp.arange(m, dtype=jnp.int32)
        last = jnp.max(jnp.where(cover, idx[:, None], -1), axis=0)
        health = jnp.where(
            last >= 0,
            values[jnp.clip(last, 0, m - 1)],
            state["health"]).astype(jnp.int32)
        state = dict(state)
        state["health"] = health
        return state

    # ------------------------------------------------------------------
    def _clear_arrays(self, state, interpret: Optional[bool] = None):
        """Clearing pass (jnp oracle or Pallas kernel — ONE shared
        aggregate producer over the sorted book view, see ops.clear).
        Both backends return the normalized leaf-major (n_leaves, k+1)
        slate with -1 holes at excluded/sub-floor ranks.

        ``interpret=None`` inherits the constructor's ``self.interpret``
        — a compiled-mode engine stays compiled through every clearing
        entry point (clear/clear_topk/step)."""
        return clear_ops.clear(
            state["order"], state["sorted_gseg"], state["seg_start"],
            state["price"], state["tenant"], state["seq"],
            tuple(state["floor"]), self.level_off, self.tree.strides,
            state["owner"], state["limit"], self.k,
            health=state["health"], use_pallas=self.use_pallas,
            interpret=self.interpret if interpret is None else interpret)

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def clear(self, state, interpret: Optional[bool] = None):
        """Full clearing pass: per-leaf charged rate, winning level, and
        winning (owner-excluded, floor-gated) bid slot — the best live
        entry of the ranked candidate slate (use ``clear_topk`` for all
        of it).  ``interpret=None`` (default) inherits the engine's
        constructor setting."""
        rate, best_level, cands, _, _ = self._clear_arrays(
            state, interpret)
        first = jnp.argmax(cands >= 0, axis=-1)
        winner = jnp.take_along_axis(cands, first[:, None], axis=-1)[:, 0]
        return rate, best_level, winner

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def clear_topk(self, state, interpret: Optional[bool] = None):
        """Full clearing pass with the ranked (K', n_leaves) candidate
        slate (rank-ordered; -1 entries are padding or excluded holes)
        and the slate-truncation flag.  ``interpret=None`` (default)
        inherits the engine's constructor setting."""
        rate, best_level, cands, trunc, _ = self._clear_arrays(
            state, interpret)
        return rate, best_level, cands.T, trunc

    # ------------------------------------------------------------------
    def _clip_bids(self, state, prices, levels, nodes):
        """Volatility control: clip each incoming bid to max_bid_multiple
        x its scope's reference price (max of path floors, top of the
        scope's own book, charged rates under the scope); a zero reference
        disables clipping, mirroring the event engine."""
        mult = self.controls.max_bid_multiple
        if mult <= 0:
            return prices
        tree = self.tree
        strides = jnp.array(tree.strides, jnp.int32)
        first_leaf = nodes * strides[levels]
        leaf_ids = jnp.arange(tree.n_leaves, dtype=jnp.int32)
        live = (state["price"] > NEG / 2) & (state["tenant"] >= 0)
        ref = jnp.zeros(prices.shape, jnp.float32)
        # all O(capacity + n_leaves + n_bids) per level: segment maxima
        # per node, gathered per incoming bid
        for d2, s2 in enumerate(tree.strides):
            n_d = tree.nodes_at(d2)
            anc = jnp.clip(first_leaf // s2, 0, n_d - 1)
            # path floors (ancestors of the scope, i.e. levels >= scope's)
            f = state["floor"][d2][anc]
            ref = jnp.maximum(ref, jnp.where(d2 >= levels, f, 0.0))
            # top of the scope's own book
            seg = jnp.clip(state["node"], 0, n_d - 1)
            at_d2 = live & (state["level"] == d2)
            top_d2 = jnp.full((n_d,), NEG, jnp.float32).at[seg].max(
                jnp.where(at_d2, state["price"], NEG))
            top = top_d2[jnp.clip(nodes, 0, n_d - 1)]
            ref = jnp.maximum(ref, jnp.where(
                (d2 == levels) & (top > NEG / 2), top, 0.0))
            # max charged rate among leaves under the scope
            rmax_d2 = jnp.zeros((n_d,), jnp.float32).at[
                leaf_ids // s2].max(state["rate"])
            ref = jnp.maximum(ref, jnp.where(
                d2 == levels, rmax_d2[jnp.clip(nodes, 0, n_d - 1)], 0.0))
        return jnp.where(ref > 0, jnp.minimum(prices, ref * mult), prices)

    # ------------------------------------------------------------------
    def _cascade(self, state, t, release):
        """Clear / evict / transfer to fixpoint (see module docstring).

        Each wave resolves up to K contested OCO claims via in-wave
        fall-through rounds.  Aggregates are recomputed per wave from
        the maintained sorted book view — a flat O(capacity)
        prefix-gather (consumption only kills entries, which the
        liveness cumsum skips), replacing the pre-PR-3 per-level
        ``lax.cond``-gated K-sweep rebuilds."""
        tree = self.tree
        n_leaves = tree.n_leaves
        K = self.k
        cap = self.capacity
        leafid = jnp.arange(n_leaves, dtype=jnp.int32)
        min_hold = self.controls.min_holding_s
        # path floors are cascade-invariant: hoist the per-leaf combine
        floor_leaf = jnp.zeros((n_leaves,), jnp.float32)
        for d, s in enumerate(tree.strides):
            floor_leaf = jnp.maximum(floor_leaf,
                                     state["floor"][d][leafid // s])

        def body(carry):
            st, rel, _ = carry
            rate, _lvl, cands, trunc, evict_p = self._clear_arrays(st)
            st = dict(st)
            st["rate"] = rate
            st["waves"] = st["waves"] + 1
            owner = st["owner"]
            evict = evict_p != 0
            if min_hold > 0:
                evict = evict & ((t - st["acq_t"]) >= min_hold)
            trunc_b = trunc != 0
            # the slate may contain -1 HOLES at excluded/sub-floor ranks
            # (jnp path) — "has a candidate" is any(>= 0), not entry 0
            has_cand = jnp.any(cands >= 0, axis=-1)
            sell = (owner < 0) & has_cand        # idle supply matching
            # idle supply FIRST (matching Market._try_immediate_match):
            # while any marketable bid can still fill an idle leaf, its
            # pressure must not evict anyone — it will be consumed
            sell_pending = jnp.any(sell)
            evict = evict & ~sell_pending
            releasing = rel & (owner >= 0) & ~sell_pending
            unresolved0 = evict | releasing | sell
            # an exhausted slate is conclusive when it was complete
            # (not truncated) OR empty at wave start (the clear's top-1
            # is exact for the wave book, and consumption only removes
            # orders); otherwise the leaf needs a full re-clear
            conclusive = ~trunc_b | ~has_cand
            price_tab = st["price"]
            tenant_tab = st["tenant"]
            blimit_tab = st["blimit"]
            cexp = jnp.clip(cands, 0, cap - 1)      # (n_leaves, K')
            rank = jnp.arange(cands.shape[-1], dtype=jnp.int32)

            def next_alive(alive, consumed, win, prop, gathers):
                # the slots this round claimed (one winner each) leave
                # every slate they sit in.  Up to TRACKED_CLAIMS of
                # them are the max and the min of the winners' slots,
                # compared against the slate (the pads -2 and cap
                # match no entry); a round that claimed more
                # re-gathers the liveness from the consumed table
                hi = jnp.max(jnp.where(win, prop, -2))
                lo = jnp.min(jnp.where(win, prop, cap))
                tracked = jnp.sum(win) <= TRACKED_CLAIMS

                def drop_claimed(live):
                    return live & (cands != hi) & (cands != lo)

                def regather(_):
                    return (cands >= 0) & ~consumed[cexp]

                alive = lax.cond(tracked, drop_claimed, regather, alive)
                return alive, gathers + (~tracked).astype(jnp.int32)

            def round_one(rc):
                (owner_c, limit_c, acq_c, consumed, alive, unresolved,
                 moved, go, r, gathers) = rc
                # proposal: each unresolved leaf's best live slate entry
                # (exact fall-through: ``alive`` is the slate entries
                # no earlier round of the wave consumed) — a vectorized
                # first-hit over the leaf-major slate, read by compare
                # and max over its ranks (a gather here costs more)
                found = jnp.any(alive, axis=-1)
                first = jnp.argmax(alive, axis=-1)
                prop = jnp.where(
                    unresolved & found,
                    jnp.max(jnp.where(rank == first[:, None], cands, -1),
                            axis=-1),
                    -1)
                ps = jnp.clip(prop, 0, cap - 1)
                # an evicted leaf re-checks its limit against the
                # fall-through price: pressure that another leaf
                # consumed no longer evicts (exactly what a K=1
                # re-clear would decide)
                floor_evicts = floor_leaf > limit_c + EPSF
                evict_still = floor_evicts | \
                    ((prop >= 0) & (price_tab[ps] > limit_c + EPSF))
                lapsed_raw = unresolved & evict & ~releasing & ~sell \
                    & (prop >= 0) & ~evict_still
                active = unresolved & ~lapsed_raw
                exhausted = active & (prop < 0)
                # a leaf that exhausts a truncated slate needs a full
                # re-clear: freeze the whole round (and the rest of the
                # wave) — K=1 waves resolve everything simultaneously,
                # so letting ANY action slip past the freeze would
                # reorder it against the frozen leaf's deferred claim
                go = go & ~jnp.any(exhausted & ~conclusive)
                lapsed = lapsed_raw & go
                act = active & (prop >= 0) & go
                # OCO within a round: one order wins at most one leaf —
                # the lowest-index claiming leaf takes the slot;
                # contested leaves fall to their runner-up next round
                claimer = jnp.full((cap,), n_leaves, jnp.int32).at[
                    jnp.where(act, prop, cap)].min(
                    jnp.where(act, leafid, n_leaves), mode="drop")
                win = act & (claimer[ps] == leafid)
                # every claimed slot is consumed by its (unique, minimal)
                # claimer, so the claimer array doubles as this round's
                # consumption set — no second scatter needed
                consumed = consumed | (claimer < n_leaves)
                # movers with a conclusively exhausted slate fall back
                # to the operator (releases always; evictions only
                # while the floor itself still exceeds the limit)
                done = exhausted & conclusive & go
                recl = done & (releasing | (evict & floor_evicts))
                moved_r = win | recl
                owner_c = jnp.where(
                    win, tenant_tab[ps], jnp.where(recl, -1, owner_c))
                limit_c = jnp.where(
                    win, blimit_tab[ps],
                    jnp.where(recl, jnp.inf, limit_c))
                acq_c = jnp.where(moved_r, t, acq_c)
                # a reclaim creates NEW idle supply mid-wave: under the
                # idle-supply-first rule the freshly idle leaf's sells
                # (including the old owner's now-unexcluded bids) must
                # gate the next resolution — only a full re-clear sees
                # them, so freeze the remaining rounds
                go = go & ~jnp.any(recl)
                # K == 1 runs one round per wave: no next round reads it
                if K > 1:
                    alive, gathers = next_alive(alive, consumed, win,
                                                prop, gathers)
                return (owner_c, limit_c, acq_c, consumed, alive,
                        unresolved & ~moved_r & ~lapsed & ~done,
                        moved | moved_r, go, r + 1, gathers)

            # early-exit round loop: identical to running all K rounds
            # (a round with nothing unresolved or a frozen wave is a
            # no-op by construction), but steady-state waves resolve in
            # 1-2 active rounds, so skipping the idle tail saves whole
            # rounds (each a scatter-min over the bid table and the
            # proposals' gathers).  K=1 keeps the single
            # statically-fused round (the loop machinery costs more
            # than the round it would skip).  Nothing is consumed at
            # wave start, so every slate entry is alive.
            rc = (st["owner"], st["limit"], st["acq_t"],
                  jnp.zeros((cap,), jnp.bool_), cands >= 0, unresolved0,
                  jnp.zeros((n_leaves,), jnp.bool_), jnp.asarray(True),
                  jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
            if K == 1:
                rc = round_one(rc)
            else:
                rc = lax.while_loop(
                    lambda rc: rc[7] & jnp.any(rc[5]) & (rc[8] < K),
                    round_one, rc)
            st["owner"], st["limit"], st["acq_t"], consumed, _, _, \
                moved, go, r, gathers = rc
            # r counts round_one calls (1 when K == 1); a wave that ends
            # with go False was frozen
            st["rounds"] = st["rounds"] + r
            st["frozen"] = st["frozen"] + (~go).astype(jnp.int32)
            st["liveness_gathers"] = st["liveness_gathers"] + gathers
            # consume winning orders (each OCO set dissolves atomically);
            # a kill keeps the sorted book view valid
            st["price"] = jnp.where(consumed, NEG, st["price"])
            st["tenant"] = jnp.where(consumed, -1, st["tenant"])
            return st, rel & ~moved, jnp.any(moved)

        def cond(carry):
            return carry[2]

        with jax.named_scope("engine_cascade"):
            state, release, _ = lax.while_loop(
                cond, body, (state, release, jnp.asarray(True)))
        return state

    # ------------------------------------------------------------------
    @functools.partial(jax.jit, static_argnums=0)
    def step(self, state, t, new_bids=None, floor_updates=None,
             relinquish=None, limits=None):
        """One market epoch at time ``t`` — see module docstring.

        new_bids: optional dict with (k,) arrays ``price``, ``limit``,
            ``level``, ``node``, ``tenant`` (tenant -1 = padding).
        floor_updates: optional per-level sequence of proposal arrays
            (value < 0 = no change for that node).
        relinquish: optional (m,) int32 leaf ids to explicitly release
            (-1 = padding).
        limits: optional (n_leaves,) float32 retention-limit refresh
            (NaN = leave that leaf's limit unchanged) — the fleet's
            batched ``set_retention_limit``, applied after matured
            deferred evictions and before this step's events.
        Returns (state, transfers, bills) where transfers is a dict of
        per-leaf {moved, old, new} owner ids and bills the cumulative
        per-tenant vector.
        """
        tree = self.tree
        state = dict(state)
        state["floor"] = tuple(state["floor"])
        state["floor_t"] = tuple(state["floor_t"])
        t = jnp.asarray(t, jnp.float32)
        # 1) integral billing accrual at the previous step's rates
        dt_h = jnp.maximum(t - state["t"], 0.0) / 3600.0
        owner0 = state["owner"]
        bill_idx = jnp.where(owner0 >= 0, owner0, self.n_tenants)
        state["bills"] = state["bills"].at[bill_idx].add(
            jnp.where(owner0 >= 0, state["rate"] * dt_h, 0.0),
            mode="drop")
        state["t"] = t
        # 1b) failure-domain revocation: owners on DOWN leaves are
        #     force-evicted now — AFTER the accrual above, so the owner
        #     is billed up to the failure tick and not a second past it.
        #     Down leaves then stay idle (apply_health_mask blanks their
        #     slates), draining leaves keep owners but accept no new
        #     ones; repairs just flip health back and the next clear
        #     re-admits the leaf.
        fault_evict = (state["health"] == HEALTH_DOWN) & (owner0 >= 0)
        state["owner"] = jnp.where(fault_evict, -1, state["owner"])
        state["limit"] = jnp.where(fault_evict, jnp.inf, state["limit"])
        no_release = jnp.zeros((tree.n_leaves,), jnp.bool_)
        # 2) deferred min-holding evictions matured by time passage fire
        #    BEFORE this step's events (matching Market.advance_to)
        if self.controls.min_holding_s > 0:
            state = self._cascade(state, t, no_release)
        # 2b) batched retention-limit refresh (NaN = no change), before
        #     this step's events so the subsequent cascade sees them.
        #     Masked to owned leaves: Market.set_retention_limit asserts
        #     ownership, and unowned leaves must keep limit = +inf
        if limits is not None:
            state["limit"] = jnp.where(
                jnp.isnan(limits) | (state["owner"] < 0),
                state["limit"], limits)
        # 3) operator floor updates, drops bounded by floor_fall_rate
        if floor_updates is not None:
            fall = self.controls.floor_fall_rate
            floors, floor_ts = [], []
            for d in range(tree.n_levels):
                prop = floor_updates[d]
                old = state["floor"][d]
                upd = prop >= 0.0
                if fall > 0:
                    dt_node = jnp.maximum(
                        t - state["floor_t"][d], 0.0) / 3600.0
                    min_allowed = old * jnp.maximum(
                        0.0, 1.0 - fall * dt_node)
                    val = jnp.where(prop < old,
                                    jnp.maximum(prop, min_allowed), prop)
                else:
                    val = prop
                floors.append(jnp.where(upd, val, old))
                floor_ts.append(jnp.where(upd, t, state["floor_t"][d]))
            state["floor"] = tuple(floors)
            state["floor_t"] = tuple(floor_ts)
        # 4) admit new bids (clipped)
        if new_bids is not None:
            with jax.named_scope("engine_place"):
                prices = self._clip_bids(state, new_bids["price"],
                                         new_bids["level"],
                                         new_bids["node"])
                state = dict(self.place(state, prices, new_bids["level"],
                                        new_bids["node"],
                                        new_bids["tenant"],
                                        new_bids.get("limit")))
        # 5) explicit relinquishments + clear/evict/transfer cascade
        release = no_release
        if relinquish is not None:
            hits = jnp.zeros((tree.n_leaves,), jnp.int32).at[
                jnp.where(relinquish >= 0, relinquish,
                          tree.n_leaves)].add(1, mode="drop")
            release = hits > 0
        state = self._cascade(state, t, release)
        transfers = {"moved": owner0 != state["owner"], "old": owner0,
                     "new": state["owner"],
                     "revoked_by_fault": fault_evict}
        return state, transfers, state["bills"]


def build_tree(n_leaves: int, gpus_per_host: int = 8,
               hosts_per_rack: int = 4, racks_per_zone: int = 4) -> TreeSpec:
    s_host = gpus_per_host
    s_rack = s_host * hosts_per_rack
    s_zone = s_rack * racks_per_zone
    return TreeSpec(n_leaves=n_leaves,
                    strides=(1, s_host, s_rack, s_zone, n_leaves))
