"""Declared state contract for the batch market engine — machine-checked.

The engine's state dict (``BatchEngine.init_state``) is a contract many
layers depend on: every jitted entry point (``step``/``clear``/
``place``/``cancel_all``/``_cascade``), both clearing backends, the
bridge's host views and the vectorized fleet all assume the same keys,
dtypes, shapes and semantic invariants.  Twice that contract broke
silently (the PR 2 book-slot overwrite, the PR 4 interpret-default
override) and only differential tests caught it late.  This module
makes the contract explicit and checkable at three costs:

* ``SCHEMA`` / ``LEVEL_SCHEMA`` — the declared key table: dtype, shape
  expression in the engine's dimensions, and the semantic invariant in
  prose (rendered in docs/DESIGN.md §9).
* ``check_state(state, engine)`` — STATIC verification (keys exactly,
  dtype, shape).  Works on concrete arrays *and* on the
  ``jax.ShapeDtypeStruct`` pytrees ``jax.eval_shape`` returns, so
  ``tools/lcheck`` verifies every public jitted entry point preserves
  the contract by abstract interpretation alone — dtype widening,
  shape drift or a key added on one path but not another fails CI
  without ever running the engine.
* ``validate_state(state, engine)`` — RUNTIME verification of the
  semantic invariants via ``jax.experimental.checkify`` (sorted-view
  validity, seq monotonicity, -1 hole conventions, owner/limit/rate
  consistency, bounded floors).  The differential and property suites
  call it on every replayed trace; ``maybe_validate`` is the env-gated
  production hook (``LAISSEZ_VALIDATE=1``) the bridge calls after each
  engine step.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import checkify

from repro.kernels.market_clear.ref import NEG

VALIDATE_ENV = "LAISSEZ_VALIDATE"

_DTYPES = {"f32": np.dtype(np.float32), "i32": np.dtype(np.int32)}


@dataclass(frozen=True)
class KeySpec:
    """One state key: dtype tag, shape expression (evaluated over the
    engine dims ``n_leaves/capacity/n_levels/n_seg_total/n_tenants``;
    ``()`` = scalar) and the semantic invariant in prose."""
    dtype: str
    shape: Tuple[str, ...]
    invariant: str


# ---------------------------------------------------------------------------
# the declared contract — ONE row per state key (docs/DESIGN.md §9)
# ---------------------------------------------------------------------------
SCHEMA: Dict[str, KeySpec] = {
    # ---- bid table (ring buffer of OCO scoped orders) ----
    "price": KeySpec("f32", ("capacity",),
                     "bid price; NEG sentinel when dead; finite when "
                     "live; live == (price > NEG/2) == (tenant >= 0)"),
    "blimit": KeySpec("f32", ("capacity",),
                      "retention limit the winner inherits; "
                      ">= price for live entries"),
    "level": KeySpec("i32", ("capacity",),
                     "scope level; in [0, n_levels) for live entries"),
    "node": KeySpec("i32", ("capacity",),
                    "scope node index; in [0, nodes_at(level)) for "
                    "live entries"),
    "tenant": KeySpec("i32", ("capacity",),
                      "-1 dead hole, else dense id < n_tenants (the "
                      "-1 hole convention: tenant < 0 iff price <= "
                      "NEG/2)"),
    "seq": KeySpec("i32", ("capacity",),
                   "monotone arrival stamp; 0 <= seq < next_seq for "
                   "live entries (equal-price ties clear seq asc)"),
    "next_seq": KeySpec("i32", (),
                        "monotone arrival counter, >= every live seq"),
    "head": KeySpec("i32", (),
                    "ring-buffer cursor, in [0, capacity)"),
    "dropped": KeySpec("i32", (),
                       "cumulative overflow drop count, >= 0"),
    # ---- sorted book view (engine.py module docstring) ----
    "order": KeySpec("i32", ("capacity",),
                     "slot permutation of arange(capacity): the "
                     "segment-sorted view, key (segment asc, price "
                     "desc, seq asc)"),
    "sorted_gseg": KeySpec("i32", ("capacity",),
                           "non-decreasing segment key per sorted "
                           "position, in [0, n_seg_total]; live slots "
                           "still sit at their sort-time position "
                           "(kills never move entries)"),
    "seg_start": KeySpec("i32", ("n_seg_total + 1",),
                         "per-segment start offsets == searchsorted("
                         "sorted_gseg, arange(n_seg_total + 1))"),
    # ---- per-leaf ownership ----
    "owner": KeySpec("i32", ("n_leaves",),
                     "owning tenant id, -1 = operator/idle; in "
                     "[-1, n_tenants)"),
    "limit": KeySpec("f32", ("n_leaves",),
                     "owner's retention limit; +inf where unowned"),
    "acq_t": KeySpec("f32", ("n_leaves",),
                     "acquisition time of the current owner, <= t"),
    "rate": KeySpec("f32", ("n_leaves",),
                    "charged rate cached from the last clearing pass; "
                    "finite, >= 0"),
    "health": KeySpec("i32", ("n_leaves",),
                      "failure-domain health: 0 up, 1 draining (no new "
                      "owners, retention honored), 2 down (excluded "
                      "from slates, owner force-evicted by step); no "
                      "owner on a down leaf post-step"),
    # ---- billing / clock / instrumentation ----
    "bills": KeySpec("f32", ("n_tenants",),
                     "cumulative per-tenant bill = integral rate dt; "
                     "finite, >= 0"),
    "t": KeySpec("f32", (), "engine clock, >= 0, monotone across steps"),
    "waves": KeySpec("i32", (),
                     "cumulative cascade wave count, >= 0"),
    "rounds": KeySpec("i32", (),
                      "cumulative in-wave claim rounds run, >= 0"),
    "frozen": KeySpec("i32", (),
                      "cumulative count of waves whose claim rounds "
                      "froze for a full re-clear, in [0, waves]"),
    "liveness_gathers": KeySpec("i32", (),
                                "cumulative claim rounds that claimed "
                                "more slots than the engine tracks and "
                                "re-gathered the slate liveness, in "
                                "[0, rounds]"),
    "resorts": KeySpec("i32", (),
                       "cumulative FULL lexsort count (incremental "
                       "view merges don't count), >= 0"),
}

# per-level keys: python lists (tuples inside jit) of n_levels arrays,
# level d shaped (nodes_at(d),)
LEVEL_SCHEMA: Dict[str, KeySpec] = {
    "floor": KeySpec("f32", ("nodes_at(d)",),
                     "operator floor price per node; finite, >= 0"),
    "floor_t": KeySpec("f32", ("nodes_at(d)",),
                       "last floor-update time per node (bounds "
                       "floor_fall_rate drops), <= t"),
}

# the bid-table columns place() scatters into; any *live* write to one
# of these obligates sorted-view maintenance (lcheck LC009)
BOOK_COLUMNS = ("price", "blimit", "level", "node", "tenant", "seq")

# the fused-epoch stat accumulators (sim/epoch.py threads these through
# the donated megastep; sim/recovery.py re-accumulates them on replay)
STAT_KEYS = ("orders", "transfers", "explicit_relinquish",
             "implicit_relinquish", "bids_clipped", "revoked_by_fault")

# the vectorized fleet's struct-of-arrays state (sim/fleet.py
# init_state) — declared here so the effect checker sees one closed
# universe of state keys across engine, fleet and stats namespaces
FLEET_STATE_KEYS = ("progress", "served", "demanded", "rate_ewma",
                    "reconfig_until", "last_checkpoint", "last_t",
                    "last_scale_down", "done_at", "cold_cnt",
                    "cold_until")

# ---------------------------------------------------------------------
# Declared per-function effects: which state keys each engine / fleet /
# epoch entry point may READ and WRITE.  ``tools/lcheck/effects.py``
# infers the true sets from the AST (through aliases and callees) and
# fails CI when inferred != declared; ``trace_effects`` below checks
# observed writes ⊆ declared at runtime.  Keep this a pure literal —
# the static checker parses it without importing jax.
# ---------------------------------------------------------------------
EFFECTS: Dict[str, Dict[str, tuple]] = {
    "repro.market_jax.engine.BatchEngine.step": {
        "reads": ("acq_t", "bills", "blimit", "dropped", "floor",
                  "floor_t", "frozen", "head", "health", "level", "limit",
                  "liveness_gathers", "next_seq", "node", "order",
                  "owner", "price", "rate", "resorts", "rounds",
                  "seg_start", "seq", "sorted_gseg", "t", "tenant",
                  "waves"),
        "writes": ("acq_t", "bills", "blimit", "dropped", "floor",
                   "floor_t", "frozen", "head", "level", "limit",
                   "liveness_gathers", "next_seq", "node", "order",
                   "owner", "price", "rate", "resorts", "rounds",
                   "seg_start", "seq", "sorted_gseg", "t", "tenant",
                   "waves"),
    },
    "repro.market_jax.engine.BatchEngine.place": {
        "reads": ("blimit", "dropped", "head", "level", "next_seq",
                  "node", "order", "price", "resorts", "seg_start",
                  "seq", "sorted_gseg", "tenant"),
        "writes": ("blimit", "dropped", "head", "level", "next_seq",
                   "node", "order", "price", "resorts", "seg_start",
                   "seq", "sorted_gseg", "tenant"),
    },
    "repro.market_jax.engine.BatchEngine.cancel": {
        "reads": ("price", "tenant"),
        "writes": ("price", "tenant"),
    },
    "repro.market_jax.engine.BatchEngine.cancel_all": {
        "reads": ("price", "seg_start", "tenant"),
        "writes": ("order", "price", "seg_start", "sorted_gseg",
                   "tenant"),
    },
    "repro.market_jax.engine.BatchEngine.set_health": {
        "reads": ("health",),
        "writes": ("health",),
    },
    "repro.market_jax.engine.BatchEngine._cascade": {
        "reads": ("acq_t", "blimit", "floor", "frozen", "health", "limit",
                  "liveness_gathers", "order", "owner", "price", "rounds",
                  "seg_start", "seq", "sorted_gseg", "tenant", "waves"),
        "writes": ("acq_t", "frozen", "limit", "liveness_gathers",
                   "owner", "price", "rate", "rounds", "tenant", "waves"),
    },
    "repro.market_jax.bridge.BatchMarket.set_retention_limit": {
        "reads": ("acq_t", "bills", "blimit", "dropped", "floor",
                  "floor_t", "frozen", "head", "health", "level", "limit",
                  "liveness_gathers", "next_seq", "node", "order",
                  "owner", "price", "rate", "resorts", "rounds",
                  "seg_start", "seq", "sorted_gseg", "t", "tenant",
                  "waves"),
        "writes": ("acq_t", "bills", "blimit", "dropped", "floor",
                   "floor_t", "frozen", "head", "level", "limit",
                   "liveness_gathers", "next_seq", "node", "order",
                   "owner", "price", "rate", "resorts", "rounds",
                   "seg_start", "seq", "sorted_gseg", "t", "tenant",
                   "waves"),
    },
    "repro.sim.epoch.EpochRunner.epoch": {
        "reads": ("acq_t", "bids_clipped", "bills", "blimit", "cold_cnt",
                  "cold_until", "demanded", "done_at", "dropped",
                  "explicit_relinquish", "floor", "floor_t", "frozen",
                  "head", "health", "implicit_relinquish",
                  "last_checkpoint", "last_scale_down", "last_t", "level",
                  "limit", "liveness_gathers", "next_seq", "node",
                  "order", "orders", "owner", "price", "progress", "rate",
                  "rate_ewma", "reconfig_until", "resorts",
                  "revoked_by_fault", "rounds", "seg_start", "seq",
                  "served", "sorted_gseg", "t", "tenant", "transfers",
                  "waves"),
        "writes": ("acq_t", "bids_clipped", "bills", "blimit", "cold_cnt",
                   "cold_until", "demanded", "done_at", "dropped",
                   "explicit_relinquish", "floor", "floor_t", "frozen",
                   "head", "implicit_relinquish", "last_checkpoint",
                   "last_scale_down", "last_t", "level", "limit",
                   "liveness_gathers", "next_seq", "node", "order",
                   "orders", "owner", "price", "progress", "rate",
                   "rate_ewma", "reconfig_until", "resorts",
                   "revoked_by_fault", "rounds", "seg_start", "seq",
                   "served", "sorted_gseg", "t", "tenant", "transfers",
                   "waves"),
    },
    "repro.sim.fleet.Fleet.policy": {
        "reads": ("done_at", "last_checkpoint", "last_scale_down",
                  "last_t", "progress", "rate_ewma", "reconfig_until"),
        "writes": ("last_scale_down",),
    },
    "repro.sim.fleet.Fleet.after_step": {
        "reads": ("cold_cnt", "cold_until", "done_at",
                  "last_checkpoint", "progress", "reconfig_until"),
        "writes": ("cold_cnt", "cold_until", "progress",
                   "reconfig_until"),
    },
    "repro.sim.fleet.Fleet.advance": {
        "reads": ("cold_cnt", "cold_until", "demanded", "done_at",
                  "last_checkpoint", "last_t", "progress", "rate_ewma",
                  "reconfig_until", "served"),
        "writes": ("cold_cnt", "demanded", "done_at", "last_checkpoint",
                   "last_t", "progress", "rate_ewma", "served"),
    },
    "repro.kernels.market_clear.ops.clear": {
        "reads": ("floor", "health", "limit", "order", "owner",
                  "price", "seg_start", "seq", "sorted_gseg", "tenant"),
        "writes": (),
    },
}


def dims_of(engine) -> Dict[str, int]:
    """The dimension bindings the shape expressions are evaluated in."""
    return {
        "n_leaves": engine.tree.n_leaves,
        "capacity": engine.capacity,
        "n_levels": engine.tree.n_levels,
        "n_seg_total": engine.n_seg_total,
        "n_tenants": engine.n_tenants,
    }


def _eval_shape(expr_tuple: Tuple[str, ...], dims: Dict[str, int]
                ) -> Tuple[int, ...]:
    return tuple(int(eval(e, {"__builtins__": {}}, dims))  # noqa: S307
                 for e in expr_tuple)


def expected_struct(engine) -> Dict[str, object]:
    """The contract as a pytree of ``jax.ShapeDtypeStruct`` (floors as
    tuples of per-level structs) — comparable leaf-by-leaf against
    ``jax.eval_shape`` output."""
    dims = dims_of(engine)
    out: Dict[str, object] = {}
    for key, spec in SCHEMA.items():
        out[key] = jax.ShapeDtypeStruct(_eval_shape(spec.shape, dims),
                                        _DTYPES[spec.dtype])
    for key, spec in LEVEL_SCHEMA.items():
        out[key] = tuple(
            jax.ShapeDtypeStruct((engine.tree.nodes_at(d),),
                                 _DTYPES[spec.dtype])
            for d in range(engine.tree.n_levels))
    return out


def check_state(state, engine, where: str = "state") -> List[str]:
    """STATIC contract check: exact key set, dtype and shape per key.

    ``state`` may hold concrete arrays or abstract
    ``jax.ShapeDtypeStruct``s (both expose ``.shape``/``.dtype``), so
    this runs identically on live engine state and on ``jax.eval_shape``
    results.  Returns a list of violation strings (empty = clean).
    """
    errors: List[str] = []
    want = expected_struct(engine)
    got_keys, want_keys = set(state), set(want)
    for k in sorted(want_keys - got_keys):
        errors.append(f"{where}: missing key {k!r}")
    for k in sorted(got_keys - want_keys):
        errors.append(f"{where}: undeclared key {k!r} (add it to "
                      f"market_jax/schema.py SCHEMA)")
    for k in sorted(got_keys & want_keys):
        exp, got = want[k], state[k]
        if k in LEVEL_SCHEMA:
            if len(got) != len(exp):
                errors.append(f"{where}[{k!r}]: {len(got)} levels, "
                              f"expected {len(exp)}")
                continue
            pairs = [(f"{k}[{d}]", e, g)
                     for d, (e, g) in enumerate(zip(exp, got))]
        else:
            pairs = [(k, exp, got)]
        for name, e, g in pairs:
            if tuple(g.shape) != tuple(e.shape):
                errors.append(f"{where}[{name!r}]: shape {tuple(g.shape)}"
                              f", expected {tuple(e.shape)}")
            if np.dtype(g.dtype) != np.dtype(e.dtype):
                errors.append(f"{where}[{name!r}]: dtype {g.dtype}, "
                              f"expected {np.dtype(e.dtype).name}")
    return errors


# ---------------------------------------------------------------------------
# runtime semantic invariants (checkify)
# ---------------------------------------------------------------------------
def _runtime_checks(engine, state) -> None:
    """Every semantic invariant as a ``checkify.check`` — called under
    ``checkify.checkify`` by ``validate_state``."""
    tree = engine.tree
    cap = engine.capacity
    n_seg = engine.n_seg_total
    eps = 1e-5
    price, tenant = state["price"], state["tenant"]
    live = price > NEG / 2
    # ---- -1 hole conventions on the bid table ----
    checkify.check(jnp.all(live == (tenant >= 0)),
                   "hole convention broken: (price > NEG/2) and "
                   "(tenant >= 0) disagree on some slot")
    checkify.check(jnp.all(~live | jnp.isfinite(price)),
                   "live entry with non-finite price")
    checkify.check(jnp.all(tenant < engine.n_tenants),
                   "tenant id out of range (>= n_tenants)")
    checkify.check(jnp.all(~live | (state["blimit"] >= price - eps)),
                   "live entry with blimit < price (place() stamps "
                   "blimit = max(price, limit))")
    nd = jnp.array([tree.nodes_at(d) for d in range(tree.n_levels)],
                   jnp.int32)
    lvl_ok = (state["level"] >= 0) & (state["level"] < tree.n_levels)
    checkify.check(jnp.all(~live | lvl_ok),
                   "live entry with scope level out of [0, n_levels)")
    lvl_c = jnp.clip(state["level"], 0, tree.n_levels - 1)
    node_ok = (state["node"] >= 0) & (state["node"] < nd[lvl_c])
    checkify.check(jnp.all(~live | node_ok),
                   "live entry with node index out of range for its "
                   "level")
    # ---- seq monotonicity ----
    checkify.check(state["next_seq"] >= 0, "next_seq negative")
    checkify.check(
        jnp.all(~live | ((state["seq"] >= 0)
                         & (state["seq"] < state["next_seq"]))),
        "live seq stamp outside [0, next_seq)")
    # ---- ring cursor / counters ----
    checkify.check((state["head"] >= 0) & (state["head"] < cap),
                   "ring cursor head out of [0, capacity)")
    checkify.check(state["dropped"] >= 0, "dropped count negative")
    checkify.check(state["waves"] >= 0, "wave count negative")
    checkify.check(state["rounds"] >= 0, "claim round count negative")
    checkify.check((state["frozen"] >= 0)
                   & (state["frozen"] <= state["waves"]),
                   "frozen wave count outside [0, waves]")
    checkify.check((state["liveness_gathers"] >= 0)
                   & (state["liveness_gathers"] <= state["rounds"]),
                   "liveness gather count outside [0, rounds]")
    checkify.check(state["resorts"] >= 0, "resort count negative")
    checkify.check(state["t"] >= 0, "engine clock negative")
    # ---- sorted book view validity ----
    order, sg = state["order"], state["sorted_gseg"]
    counts = jnp.zeros((cap,), jnp.int32).at[order].add(1, mode="drop")
    checkify.check(jnp.all(counts == 1),
                   "order is not a permutation of arange(capacity)")
    checkify.check(jnp.all((sg >= 0) & (sg <= n_seg)),
                   "sorted_gseg outside [0, n_seg_total]")
    checkify.check(jnp.all(sg[1:] >= sg[:-1]),
                   "sorted_gseg not non-decreasing")
    want_ss = jnp.searchsorted(
        sg, jnp.arange(n_seg + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)
    checkify.check(jnp.all(state["seg_start"] == want_ss),
                   "seg_start inconsistent with sorted_gseg "
                   "(searchsorted boundary mismatch)")
    # live slots must still sit inside their recorded segment (kills
    # only — mutations between sorts never move or re-scope an entry)
    off = jnp.array(engine.level_off, jnp.int32)
    node_c = jnp.clip(state["node"], 0, nd[lvl_c] - 1)
    gseg_now = jnp.where(live, off[lvl_c] + node_c, jnp.int32(n_seg))
    live_pos = live[order]
    checkify.check(jnp.all(~live_pos | (gseg_now[order] == sg)),
                   "sorted view stale: a live slot's current segment "
                   "differs from its sort-time segment key")
    # within a segment, live positions must run (price desc, seq asc):
    # compare each live position against the PREVIOUS live position
    # (dead holes in between are skipped via a running max)
    pos = jnp.arange(cap, dtype=jnp.int32)
    last_live = jax.lax.associative_scan(
        jnp.maximum, jnp.where(live_pos, pos, -1))
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32),
                            last_live[:-1]])
    prev_c = jnp.clip(prev, 0, cap - 1)
    cmp = live_pos & (prev >= 0) & (sg[prev_c] == sg)
    p_pos, q_pos = price[order], state["seq"][order]
    in_order = (p_pos[prev_c] > p_pos) | \
        ((p_pos[prev_c] == p_pos) & (q_pos[prev_c] < q_pos))
    checkify.check(jnp.all(~cmp | in_order),
                   "sorted view out of order: a segment's live entries "
                   "are not (price desc, seq asc)")
    # ---- per-leaf ownership ----
    owner = state["owner"]
    checkify.check(jnp.all((owner >= -1) & (owner < engine.n_tenants)),
                   "owner id outside [-1, n_tenants)")
    checkify.check(jnp.all((owner >= 0) | jnp.isinf(state["limit"])),
                   "unowned leaf with a finite retention limit "
                   "(reclaims must reset limit to +inf)")
    checkify.check(jnp.all(state["acq_t"] <= state["t"] + eps),
                   "acquisition time in the future")
    health = state["health"]
    checkify.check(jnp.all((health >= 0) & (health <= 2)),
                   "health outside the up/draining/down lattice [0, 2]")
    checkify.check(jnp.all((health != 2) | (owner < 0)),
                   "owner on a down leaf (step must force-evict before "
                   "any owner can persist on health == down)")
    checkify.check(
        jnp.all(jnp.isfinite(state["rate"]) & (state["rate"] >= 0)),
        "charged rate non-finite or negative")
    # ---- billing ----
    checkify.check(
        jnp.all(jnp.isfinite(state["bills"])
                & (state["bills"] >= -eps)),
        "bill vector non-finite or negative")
    # ---- operator floors ----
    for d in range(tree.n_levels):
        f, ft = state["floor"][d], state["floor_t"][d]
        checkify.check(jnp.all(jnp.isfinite(f) & (f >= 0)),
                       "floor non-finite or negative at some level")
        checkify.check(jnp.all(ft <= state["t"] + eps),
                       "floor update time in the future")


def validate_state(state, engine, where: str = "state") -> None:
    """Full contract check on concrete state: static (keys/dtypes/
    shapes) then the checkify'd semantic invariants.  Raises
    ``AssertionError`` / ``checkify.JaxRuntimeError`` on violation."""
    errors = check_state(state, engine, where=where)
    if errors:
        raise AssertionError("state schema violation:\n  "
                             + "\n  ".join(errors))
    canon = dict(state)
    canon["floor"] = tuple(state["floor"])
    canon["floor_t"] = tuple(state["floor_t"])
    err, _ = _checked_runtime(engine)(canon)
    err.throw()


@functools.lru_cache(maxsize=32)
def _checked_runtime(engine):
    """Jitted checkify'd invariant pass, cached per engine — trace
    replays call ``validate_state`` after every event, so retracing
    each call would dominate the suite."""
    return jax.jit(checkify.checkify(
        functools.partial(_runtime_checks, engine)))


def maybe_validate(state, engine, where: str = "state") -> None:
    """Env-gated hook (``LAISSEZ_VALIDATE=1``): the bridge calls this
    after every engine step so any trace replay — production debugging,
    benchmarks, the differential suites — can turn full invariant
    checking on without code changes."""
    if os.environ.get(VALIDATE_ENV, "0") not in ("", "0"):
        validate_state(state, engine, where=where)


def _flat_state_items(state):
    """(name, array) pairs with the per-level lists flattened —
    ``floor`` becomes ``floor[0]``, ``floor[1]``, ... so buffers diff
    positionally."""
    for k, v in state.items():
        if k in LEVEL_SCHEMA:
            for d, arr in enumerate(v):
                yield f"{k}[{d}]", arr
        else:
            yield k, v


def trace_effects(fn, state, *args, qualname: str, engine=None,
                  where: str = "call", **kwargs):
    """Runtime twin of the static effect checker: run
    ``fn(state, *args, **kwargs)``, diff every state buffer before vs
    after, and assert the observed write-set ⊆ the write-set declared
    for ``qualname`` in ``EFFECTS``.  Returns ``fn``'s result
    unchanged (functions returning tuples are diffed on element 0).

    When ``engine`` is given and the call touched the bid book or its
    sorted view, the full ``validate_state`` invariant pass runs on
    the result — the runtime counterpart of lcheck LC009 (a live book
    write that skips view maintenance trips the sorted_gseg/seg_start
    checks here even though its write-set looks declared).
    """
    declared = set(EFFECTS[qualname]["writes"])
    before = {k: np.array(v) for k, v in _flat_state_items(state)}
    out = fn(state, *args, **kwargs)
    new_state = out if isinstance(out, dict) else out[0]
    observed = set()
    for k, v in _flat_state_items(new_state):
        base = k.split("[", 1)[0]
        old = before.get(k)
        new = np.asarray(v)
        if old is None or old.shape != new.shape \
                or not np.array_equal(old, new):
            observed.add(base)
    undeclared = observed - declared
    if undeclared:
        raise AssertionError(
            f"effect trace ({where}): {qualname} wrote undeclared "
            f"state key(s) {sorted(undeclared)} — fix the function or "
            "update schema.EFFECTS")
    book_or_view = set(BOOK_COLUMNS) | {"order", "sorted_gseg",
                                        "seg_start"}
    if engine is not None and observed & book_or_view:
        validate_state(new_state, engine,
                       where=f"{where} (trace_effects)")
    return out
