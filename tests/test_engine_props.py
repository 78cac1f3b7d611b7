"""Property tests for the JAX batch market engine (beyond-paper scale
path): random bid tables must clear identically to a brute-force oracle,
and step() transfers must respect OCO semantics.

Requires hypothesis (see requirements-dev.txt); the deterministic batch
engine tests live in tests/test_engine_step.py and always run.
"""
# lcheck: file-disable=LC007 — property tests compare every step
# against a host-side oracle, so the per-event sync IS the test
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st

from repro.market_jax.engine import BatchEngine, build_tree, NEG


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_bids=st.integers(1, 300))
def test_clear_matches_bruteforce(seed, n_bids):
    rng = np.random.default_rng(seed)
    tree = build_tree(512)
    eng = BatchEngine(tree, capacity=1024)
    state = eng.init_state()
    state["floor"][-1] = state["floor"][-1].at[0].set(1.0)
    levels = rng.integers(0, tree.n_levels, n_bids).astype(np.int32)
    nodes = np.array([rng.integers(0, tree.nodes_at(d)) for d in levels],
                     np.int32)
    prices = rng.uniform(0.5, 9.0, n_bids).astype(np.float32)
    tenants = rng.integers(0, 20, n_bids).astype(np.int32)
    state = eng.place(state, jnp.array(prices), jnp.array(levels),
                      jnp.array(nodes), jnp.array(tenants))
    rate, lvl, winner = eng.clear(state)
    for leaf in rng.integers(0, 512, 6):
        best = 1.0
        for i in range(n_bids):
            if nodes[i] == leaf // tree.strides[levels[i]]:
                best = max(best, prices[i])
        assert abs(best - float(rate[int(leaf)])) < 1e-4


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_bids=st.integers(1, 200))
def test_clear_owner_exclusion_matches_bruteforce(seed, n_bids):
    """With random ownership, the charged rate must exclude ALL of the
    owner's bids (not just the top one)."""
    rng = np.random.default_rng(seed)
    tree = build_tree(256)
    eng = BatchEngine(tree, capacity=1024)
    state = eng.init_state()
    levels = rng.integers(0, tree.n_levels, n_bids).astype(np.int32)
    nodes = np.array([rng.integers(0, tree.nodes_at(d)) for d in levels],
                     np.int32)
    prices = rng.uniform(0.5, 9.0, n_bids).astype(np.float32)
    tenants = rng.integers(0, 6, n_bids).astype(np.int32)
    owners = rng.integers(-1, 6, 256).astype(np.int32)
    state = eng.place(state, jnp.array(prices), jnp.array(levels),
                      jnp.array(nodes), jnp.array(tenants))
    state["owner"] = jnp.array(owners)
    rate, lvl, winner = eng.clear(state)
    for leaf in rng.integers(0, 256, 8):
        best = 0.0
        for i in range(n_bids):
            if nodes[i] == leaf // tree.strides[levels[i]] \
                    and tenants[i] != owners[leaf]:
                best = max(best, prices[i])
        assert abs(best - float(rate[int(leaf)])) < 1e-4


_EQ_TREE = build_tree(256)
# module-level so the jitted step graphs compile once across examples
# (the jit cache is keyed on the engine instance)
_EQ_ENGINES = {k: BatchEngine(_EQ_TREE, capacity=1024, n_tenants=16, k=k)
               for k in (1, 8)}


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_topk_fixpoint_identical_to_k1(seed):
    """K>1 in-wave fall-through must reach the exact same cascade
    fixpoint as the sequential K=1 cascade on random traces (owners,
    rates, limits and bills all bit-identical after every step)."""
    tree = _EQ_TREE

    def run(k):
        rng = np.random.default_rng(seed)
        eng = _EQ_ENGINES[k]
        state = eng.init_state()
        state["floor"][-1] = state["floor"][-1].at[0].set(1.0)
        t = 0.0
        outs = []
        for _ in range(6):
            t += float(rng.uniform(0.0, 900.0))
            n = int(rng.integers(1, 64))
            levels = rng.integers(0, tree.n_levels, n).astype(np.int32)
            nodes = np.array([rng.integers(0, tree.nodes_at(d))
                              for d in levels], np.int32)
            # few tenants -> heavy same-tenant shadowing in the ranked
            # per-node candidate lists (the hard case for fall-through)
            bids = {"price": jnp.array(rng.uniform(0.5, 9.0, n),
                                       jnp.float32),
                    "limit": jnp.array(rng.uniform(0.5, 12.0, n),
                                       jnp.float32),
                    "level": jnp.array(levels), "node": jnp.array(nodes),
                    "tenant": jnp.array(rng.integers(0, 5, n),
                                        jnp.int32)}
            # padded to one batch shape (tenant -1 = padding), so each
            # engine compiles its step once, not once per batch size
            bids = {key: jnp.pad(v, (0, 63 - n), constant_values=-1)
                    for key, v in bids.items()}
            rel = jnp.array(rng.integers(-1, 256, 6), jnp.int32)
            state, _, bills = eng.step(state, t, bids, None, rel)
            outs.append((np.asarray(state["owner"]).copy(),
                         np.asarray(state["rate"]).copy(),
                         np.asarray(state["limit"]).copy(),
                         np.asarray(bills).copy()))
        return outs

    for a, b in zip(run(1), run(8)):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def check_sorted_book(eng, state):
    """The sorted-book invariant (see engine.py module docstring):

    * ``state["order"]`` is a permutation of the table slots;
    * segment CONTIGUITY — every live slot sits at a position inside its
      current segment's ``[seg_start[g], seg_start[g+1])`` range, with
      the matching sort-time segment key;
    * within each segment, live entries appear in (price desc, seq asc)
      order.
    Holds for STALE views too: kills since the last sort leave holes but
    never move or re-key live entries.
    """
    order = np.asarray(state["order"])
    sg = np.asarray(state["sorted_gseg"])
    ss = np.asarray(state["seg_start"])
    price = np.asarray(state["price"])
    tenant = np.asarray(state["tenant"])
    seq = np.asarray(state["seq"])
    level = np.asarray(state["level"])
    node = np.asarray(state["node"])
    cap = order.size
    assert sorted(order.tolist()) == list(range(cap))
    pos_of = np.empty(cap, np.int64)
    pos_of[order] = np.arange(cap)
    live = (price > NEG / 2) & (tenant >= 0)
    for s in np.nonzero(live)[0]:
        g = eng.level_off[level[s]] + node[s]
        p = pos_of[s]
        assert sg[p] == g, (s, p, sg[p], g)
        assert ss[g] <= p < ss[g + 1], (s, p, ss[g], ss[g + 1])
    for g in range(eng.n_seg_total):
        ent = [(float(price[order[p]]), int(seq[order[p]]))
               for p in range(ss[g], ss[g + 1]) if live[order[p]]]
        assert ent == sorted(ent, key=lambda e: (-e[0], e[1])), (g, ent)


_INV_TREE = build_tree(64)
_INV_ENGINE = BatchEngine(_INV_TREE, capacity=96, n_tenants=8, k=4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_sorted_book_invariant_under_interleavings(seed):
    """Segment contiguity and (price desc, seq asc) in-segment order
    hold after arbitrary interleavings of place / cancel / evict /
    transfer waves — including equal-price ties, ring-allocator laps
    over freed holes, and stale (post-kill) views."""
    rng = np.random.default_rng(seed)
    tree = _INV_TREE
    eng = _INV_ENGINE
    state = eng.init_state()
    state["floor"][-1] = state["floor"][-1].at[0].set(1.0)
    t = 0.0
    for _ in range(8):
        op = rng.choice(["place", "cancel", "step"], p=[0.45, 0.25, 0.3])
        if op == "place":
            n = int(rng.integers(1, 24))
            levels = rng.integers(0, tree.n_levels, n).astype(np.int32)
            nodes = np.array([rng.integers(0, tree.nodes_at(d))
                              for d in levels], np.int32)
            # few discrete prices -> heavy equal-price ties; few
            # tenants -> same-tenant shadowing
            prices = rng.choice([2.0, 3.0, 5.0, 8.0], n).astype(
                np.float32)
            state = eng.place(
                state, jnp.array(prices), jnp.array(levels),
                jnp.array(nodes),
                jnp.array(rng.integers(0, 5, n), jnp.int32),
                jnp.array(prices * 1.5))
        elif op == "cancel":
            ids = rng.integers(0, eng.capacity, 6).astype(np.int32)
            state = eng.cancel(state, jnp.array(ids))
        else:
            t += float(rng.uniform(0.0, 600.0))
            rel = jnp.array(rng.integers(-1, 64, 4), jnp.int32)
            state, _, _ = eng.step(state, t, None, None, rel)
        check_sorted_book(eng, state)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_step_oco_one_win_per_order(seed):
    """A single order must win at most one leaf in a batched step."""
    rng = np.random.default_rng(seed)
    tree = build_tree(512)
    eng = BatchEngine(tree, capacity=1024)
    state = eng.init_state()
    # root-scoped bids from distinct tenants; all leaves idle -> every
    # bid is marketable, yet each may win at most ONE leaf (OCO)
    n = 20
    bids = {"price": jnp.array(rng.uniform(1.0, 5.0, n), jnp.float32),
            "limit": jnp.full((n,), 99.0, jnp.float32),
            "level": jnp.full((n,), tree.n_levels - 1, jnp.int32),
            "node": jnp.zeros((n,), jnp.int32),
            "tenant": jnp.arange(n, dtype=jnp.int32)}
    state, transfers, bills = eng.step(state, 0.0, bids)
    owners = np.asarray(state["owner"])
    winners = [o for o in owners if o >= 0]
    assert len(winners) == len(set(winners))   # one leaf per order
    assert len(winners) == n                   # every bid filled
