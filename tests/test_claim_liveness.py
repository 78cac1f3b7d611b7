"""The cascade's carried slate liveness: each claim round drops the slots
it claimed from the liveness it carries, and re-gathers the liveness
from the consumed-slot table only when a round claims more than
``TRACKED_CLAIMS`` slots (counted in ``state["liveness_gathers"]``).
Either way the decisions are the K = 1 cascade's, bit for bit."""
# lcheck: file-disable=LC007 — the tests read the end state of each
# step; the host read IS the test
import jax.numpy as jnp
import numpy as np
import pytest

from repro.market_jax import engine as E
from repro.market_jax import schema
from repro.market_jax.engine import BatchEngine, build_tree

COUNTERS = ("waves", "rounds", "frozen", "liveness_gathers")
BACKENDS = [pytest.param(False, id="jnp"), pytest.param(True, id="pallas")]
TREE = build_tree(256)      # 32 hosts of 8 leaves
BATCH = 64                  # every batch is padded to one shape
_ENGINES = {}


def _engine(k, use_pallas=False):
    """One engine per (k, backend, TRACKED_CLAIMS), so each compiles its
    step once per process; the tracked count is read when the step is
    traced, so a patched value needs an engine of its own."""
    key = (k, use_pallas, E.TRACKED_CLAIMS)
    if key not in _ENGINES:
        _ENGINES[key] = BatchEngine(TREE, capacity=1 << 10,
                                    n_tenants=512, k=k,
                                    use_pallas=use_pallas)
    return _ENGINES[key]


def _batch(prices, levels, nodes, tenants, limits):
    """One bid batch, padded to ``BATCH`` (tenant -1 = padding)."""
    pad = BATCH - len(prices)
    return {"price": jnp.array(np.pad(prices, (0, pad)), jnp.float32),
            "limit": jnp.array(np.pad(limits, (0, pad)), jnp.float32),
            "level": jnp.array(np.pad(levels, (0, pad)), jnp.int32),
            "node": jnp.array(np.pad(nodes, (0, pad)), jnp.int32),
            "tenant": jnp.array(np.pad(tenants, (0, pad),
                                       constant_values=-1), jnp.int32)}


def _root_flood(m, seed):
    """``m`` marketable root bids onto the idle pool: every leaf's slate
    is the same root book, so each round claims one slot."""
    rng = np.random.default_rng(seed)
    prices = rng.uniform(3.0, 9.0, m).astype(np.float32)
    return [_batch(prices, np.full(m, TREE.n_levels - 1), np.zeros(m),
                   rng.integers(0, 300, m), prices * 1.5)]


def _host_scoped(n_hosts, seed):
    """One host-scoped bid on each of the first ``n_hosts`` of the 32
    idle hosts, above 24 root bids: the hosts' slates differ, so the
    first round claims one slot per scoped host, and one root bid for
    the rest of the pool."""
    rng = np.random.default_rng(seed)
    prices = np.concatenate([rng.uniform(6.0, 9.0, n_hosts),
                             rng.uniform(3.0, 5.0, 24)]).astype(np.float32)
    levels = np.array([1] * n_hosts + [TREE.n_levels - 1] * 24)
    nodes = np.array(list(range(n_hosts)) + [0] * 24)
    return [_batch(prices, levels, nodes,
                   rng.integers(0, 40, prices.size), prices * 1.5)]


def _mixed(seed, steps=4):
    """Random bids at every scope level, as in the engine's property
    tests, with releases between steps: slates differ across the
    tree."""
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(steps):
        n = int(rng.integers(16, BATCH + 1))
        levels = rng.integers(0, TREE.n_levels, n)
        nodes = np.array([rng.integers(0, TREE.nodes_at(d))
                          for d in levels])
        prices = rng.uniform(0.5, 9.0, n).astype(np.float32)
        limits = rng.uniform(0.5, 12.0, n).astype(np.float32)
        batches.append(_batch(prices, levels, nodes,
                              rng.integers(0, 5, n), limits))
    return batches


def _run(batches, k, use_pallas=False, releases=False):
    """The end state after one step per batch, and its counters."""
    eng = _engine(k, use_pallas)
    st = eng.init_state()
    st["floor"][-1] = st["floor"][-1].at[0].set(1.0)
    rng = np.random.default_rng(7)
    outs = []
    for i, nb in enumerate(batches):
        rel = (rng.integers(-1, TREE.n_leaves, 6) if releases
               else np.full(6, -1))
        st, _, bills = eng.step(st, 60.0 * (i + 1), nb, None,
                                jnp.array(rel, jnp.int32))
        outs.append([np.asarray(st[x]).copy()
                     for x in ("owner", "rate", "limit", "acq_t")]
                    + [np.asarray(bills).copy()])
    schema.validate_state(st, eng)
    return outs, {c: int(st[c]) for c in COUNTERS}


def _assert_same(a, b):
    for x, y in zip(a, b):
        for u, v in zip(x, y):
            np.testing.assert_array_equal(u, v)


@pytest.mark.parametrize("use_pallas", BACKENDS)
def test_root_flood_never_regathers(use_pallas):
    """One claim per round: the carried liveness alone serves every
    round, and the end state is the K = 1 cascade's."""
    batches = _root_flood(60, seed=3)
    k1, c1 = _run(batches, 1, use_pallas)
    k16, c16 = _run(batches, 16, use_pallas)
    assert c16["liveness_gathers"] == 0 and c1["liveness_gathers"] == 0
    assert c16["rounds"] > c16["waves"]
    _assert_same(k1, k16)


@pytest.mark.parametrize("use_pallas", BACKENDS)
def test_root_flood_waves_and_rounds_match_the_gather(use_pallas,
                                                      monkeypatch):
    """With no claims tracked every claiming round re-gathers, as the
    cascade did before it carried the liveness: the waves and rounds,
    and every decision, are the same either way."""
    batches = _root_flood(60, seed=4)
    carried, cc = _run(batches, 16, use_pallas)
    monkeypatch.setattr(E, "TRACKED_CLAIMS", 0)
    gathered, cg = _run(batches, 16, use_pallas)
    assert cc["liveness_gathers"] == 0
    assert 0 < cg["liveness_gathers"] <= cg["rounds"]
    assert {c: cc[c] for c in COUNTERS[:3]} == \
        {c: cg[c] for c in COUNTERS[:3]}
    _assert_same(carried, gathered)


@pytest.mark.parametrize("use_pallas", BACKENDS)
@pytest.mark.parametrize("n_hosts", [E.TRACKED_CLAIMS + 1, 16])
def test_multi_claim_round_regathers_exactly(use_pallas, n_hosts,
                                             monkeypatch):
    """A round that claims more slots than tracked re-gathers; the end
    state is the K = 1 cascade's, and the waves and rounds are those of
    a cascade that re-gathers every round."""
    batches = _host_scoped(n_hosts, seed=n_hosts)
    k1, _ = _run(batches, 1, use_pallas)
    k16, c16 = _run(batches, 16, use_pallas)
    assert 1 <= c16["liveness_gathers"] < c16["rounds"], c16
    _assert_same(k1, k16)
    monkeypatch.setattr(E, "TRACKED_CLAIMS", 0)
    gathered, cg = _run(batches, 16, use_pallas)
    assert {c: c16[c] for c in COUNTERS[:3]} == \
        {c: cg[c] for c in COUNTERS[:3]}
    _assert_same(k16, gathered)


def test_tracked_claims_at_the_limit_need_no_gather():
    """Exactly ``TRACKED_CLAIMS`` claims in one round (one per scoped
    host, and the root bid the other hosts' leaves share): they are the
    max and the min of the claimed slots, so nothing is re-gathered."""
    batches = _host_scoped(E.TRACKED_CLAIMS - 1, seed=5)
    k1, _ = _run(batches, 1)
    k16, c16 = _run(batches, 16)
    assert c16["liveness_gathers"] == 0 and c16["rounds"] > 1
    _assert_same(k1, k16)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_mixed_scopes_identical_to_k1_and_to_the_gather(seed,
                                                        monkeypatch):
    """Random scopes and releases over several steps: K = 8 with the
    carried liveness, K = 8 re-gathering every round, and K = 1 reach
    the same owners, rates, limits, acquisition times and bills."""
    batches = _mixed(seed)
    k1, _ = _run(batches, 1, releases=True)
    k8, c8 = _run(batches, 8, releases=True)
    _assert_same(k1, k8)
    monkeypatch.setattr(E, "TRACKED_CLAIMS", 0)
    gathered, cg = _run(batches, 8, releases=True)
    assert {c: c8[c] for c in COUNTERS[:3]} == \
        {c: cg[c] for c in COUNTERS[:3]}
    _assert_same(k8, gathered)


@pytest.mark.parametrize("tracked", [0, 2])
def test_k1_unchanged(tracked, monkeypatch):
    """K = 1 runs one round per wave: no next round reads the liveness,
    so it neither updates nor re-gathers it, whatever is tracked."""
    monkeypatch.setattr(E, "TRACKED_CLAIMS", tracked)
    batches = _host_scoped(16, seed=9)
    out, c = _run(batches, 1)
    assert c["liveness_gathers"] == 0 and c["rounds"] == c["waves"]
    assert c["frozen"] == 0
    monkeypatch.undo()
    ref, cr = _run(batches, 1)
    assert c == cr
    _assert_same(out, ref)


def test_counter_declared_and_starts_at_zero():
    eng = BatchEngine(build_tree(64), capacity=256, n_tenants=16)
    st = eng.init_state()
    assert int(st["liveness_gathers"]) == 0
    spec = schema.SCHEMA["liveness_gathers"]
    assert spec.dtype == "i32" and spec.shape == ()
    assert schema.check_state(st, eng) == []
    for name, eff in schema.EFFECTS.items():
        for kind in ("reads", "writes"):
            assert ("rounds" in eff[kind]) == \
                ("liveness_gathers" in eff[kind]), (name, kind)


def test_validate_rejects_more_gathers_than_rounds():
    eng = BatchEngine(build_tree(64), capacity=256, n_tenants=16)
    st = eng.init_state()
    st["liveness_gathers"] = jnp.ones((), jnp.int32)
    with pytest.raises(Exception, match="liveness gather count"):
        schema.validate_state(st, eng)
