"""The cascade's instrumentation counters: ``rounds`` (in-wave claim
rounds run) and ``frozen`` (waves whose claim rounds froze for a full
re-clear), beside ``waves``.  They count; they change no decision."""
# lcheck: file-disable=LC007 — the tests read the end state of one
# step per engine; the host read IS the test
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.market_jax import schema
from repro.market_jax.engine import BatchEngine, TreeSpec, build_tree

COUNTERS = ("waves", "rounds", "frozen")


def _counts(st):
    return {k: int(st[k]) for k in COUNTERS}


def _flood(n_leaves, m, k, seed=0):
    """``m`` marketable root bids onto an idle pool of ``n_leaves``."""
    tree = build_tree(n_leaves)
    rng = np.random.default_rng(seed)
    prices = rng.uniform(3.0, 9.0, m).astype(np.float32)
    nb = {"price": jnp.array(prices), "limit": jnp.array(prices * 1.5),
          "level": jnp.full((m,), tree.n_levels - 1, jnp.int32),
          "node": jnp.zeros((m,), jnp.int32),
          "tenant": jnp.array(rng.integers(0, 300, m), jnp.int32)}
    eng = BatchEngine(tree, capacity=1 << 10, n_tenants=512, k=k)
    st = eng.init_state()
    st["floor"][-1] = st["floor"][-1].at[0].set(2.0)
    return eng, st, nb


def _planted(k):
    """Four owned leaves, each evictable (limit 1), and three root bids
    a > b > c from the owners of leaves 3, 0 and 1.  With ``k = 2`` the
    root book's top two (a, b) make every slate, truncated: leaf 0 sees
    [a], leaves 1 and 2 [a, b], leaf 3 [b] (owners' bids are holes).
    Round 1: leaf 0 takes a, leaf 3 takes b; round 2: leaf 1 has
    exhausted a truncated slate, so the wave freezes and the next one
    re-clears (leaf 2 then takes c)."""
    eng = BatchEngine(TreeSpec(4, (1, 2, 4)), capacity=64, n_tenants=8,
                      k=k)
    st = eng.init_state()
    st["owner"] = jnp.arange(4, dtype=jnp.int32)
    st["limit"] = jnp.ones((4,), jnp.float32)
    p = jnp.array([10.0, 9.0, 8.0], jnp.float32)
    nb = {"price": p, "limit": p, "level": jnp.full((3,), 2, jnp.int32),
          "node": jnp.zeros((3,), jnp.int32),
          "tenant": jnp.array([3, 0, 1], jnp.int32)}
    st, _, _ = eng.step(st, 60.0, nb)
    return eng, st


def test_counters_start_at_zero_and_are_declared():
    eng = BatchEngine(build_tree(64), capacity=256, n_tenants=16)
    st = eng.init_state()
    assert _counts(st) == {"waves": 0, "rounds": 0, "frozen": 0}
    assert schema.check_state(st, eng) == []
    for k in ("rounds", "frozen"):
        assert schema.SCHEMA[k].dtype == "i32" and \
            schema.SCHEMA[k].shape == ()


def test_k1_runs_one_round_per_wave():
    eng, st, nb = _flood(512, 300, k=1)
    st, _, _ = eng.step(st, 30.0, nb)
    c = _counts(st)
    assert c["waves"] > 1 and c["rounds"] == c["waves"]
    assert c["frozen"] == 0
    schema.validate_state(st, eng)


def test_k16_rounds_bounded_by_waves_and_owners_unchanged():
    """A flood fills every bid in about m/16 waves of up to 16 rounds;
    the owners, rates and bills are the k = 1 cascade's."""
    out = {}
    for k in (1, 16):
        eng, st, nb = _flood(512, 300, k=k)
        st, _, bills = eng.step(st, 30.0, nb)
        out[k] = (_counts(st), np.asarray(st["owner"]),
                  np.asarray(st["rate"]), np.asarray(bills))
        schema.validate_state(st, eng)
    c = out[16][0]
    assert c["waves"] <= c["rounds"] <= 16 * c["waves"], c
    assert c["waves"] < out[1][0]["waves"]
    assert c["frozen"] == 0       # a shared slate outlasts 16 rounds
    for a, b in zip(out[1][1:], out[16][1:]):
        np.testing.assert_array_equal(a, b)


def test_rounds_equals_round_one_calls(monkeypatch):
    """``rounds`` against a count of ``round_one`` calls, with the round
    loop run eagerly (``disable_jit``) through a counting
    ``lax.while_loop``."""
    eng, st, nb = _flood(128, 40, k=16)
    jitted, _, _ = eng.step(st, 30.0, nb)
    calls = [0]
    real = lax.while_loop

    def counting(cond, body, init):
        if getattr(body, "__name__", "") != "round_one":
            return real(cond, body, init)

        def counted(rc):
            calls[0] += 1
            return body(rc)
        return real(cond, counted, init)

    monkeypatch.setattr(lax, "while_loop", counting)
    with jax.disable_jit():
        eager, _, _ = eng.step(st, 30.0, nb)
    assert calls[0] > 0
    assert int(eager["rounds"]) == calls[0] == int(jitted["rounds"])
    assert _counts(eager) == _counts(jitted)


@pytest.mark.parametrize("k,frozen", [(1, 0), (2, 1), (3, 1)])
def test_planted_truncated_slate_freezes_a_wave(k, frozen):
    eng, st = _planted(k)
    c = _counts(st)
    assert c["frozen"] == frozen, c
    assert 0 <= c["frozen"] <= c["waves"]
    # the freeze defers work, never changes where it lands
    assert np.asarray(st["owner"]).tolist() == [3, 1, 1, 0]
    schema.validate_state(st, eng)


def test_untruncated_flood_never_freezes():
    eng, st, nb = _flood(512, 12, k=16)
    st, _, _ = eng.step(st, 30.0, nb)
    c = _counts(st)
    assert c["frozen"] == 0 and c["rounds"] >= 12
