"""Device self time under scopes nested inside the epoch's scopes.

``trace.reduce_events`` charges each op to ONE scope, the first of its
list found in the op's path.  The engine step's own scopes nest
(``market_clear`` runs inside ``engine_cascade``, which runs inside
``epoch_step``), so here each scope is reduced on its own: an op counts
toward a scope when the scope is a whole ``/``-separated component of
its path, or, failing that, when its parent op counts toward it.  A
nested scope is therefore charged to every scope around it, and the
outer attribution of ``reduce_events`` is left as it is.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from lcbench.trace import reduce_events

# the engine step's scopes (market_jax/engine.py, kernels/market_clear/ops.py)
INNER_SCOPES = ("engine_place", "engine_cascade", "market_clear")


def _on_path(scope: str, path: str) -> str:
    return scope if scope in path.split("/") else ""


def inner_scope_ns(ops: List[Tuple[float, float, str, str]],
                   spans: List[Tuple[float, float, str]],
                   scopes: Sequence[str] = INNER_SCOPES,
                   op_scopes: Optional[Dict[str, str]] = None
                   ) -> Dict[str, float]:
    """Self ns per scope of ``scopes`` found on some op's path, on the
    same ``(start_ns, end_ns, name, path)`` ops, harness spans and
    instruction -> path map as ``reduce_events``."""
    out: Dict[str, float] = {}
    for s in scopes:
        # each path becomes the scope's own name where it holds the
        # scope as a component, and "" elsewhere, so that the
        # reduction's substring match and parent inheritance apply to
        # whole components only
        marked = [(a, b, name, _on_path(s, st)) for a, b, name, st in ops]
        paths = {k: _on_path(s, v) for k, v in (op_scopes or {}).items()}
        ns = reduce_events(marked, spans, (s,), paths).scope_ns.get(s)
        if ns is not None:
            out[s] = ns
    return out


def scope_ms(ctx: dict, scope: str) -> Optional[float]:
    """Device ms per epoch under ``scope`` (nested scopes counted in
    it), from a metric reader's context: the traced window's raw events
    in ``ctx["trace"]`` over ``ctx["epochs"]``.  ``None`` where there is
    no trace or no op on that scope."""
    red = ctx.get("trace")
    if red is None or not red.ops or not ctx.get("epochs"):
        return None
    ns = inner_scope_ns(red.ops, red.spans, (scope,),
                        red.op_scopes).get(scope)
    return None if ns is None else ns * 1e-6 / ctx["epochs"]
