"""Device milliseconds per epoch of the fleet policy: the scopes
``epoch_policy``, ``epoch_after_step`` and ``epoch_advance``
(``sim/fleet.py`` ``policy``, ``after_step``, ``advance``), from the
trace."""

SCOPES = ("epoch_policy", "epoch_after_step", "epoch_advance")


def read(ctx):
    red = ctx.get("trace")
    if red is None or not ctx["epochs"]:
        return None
    found = [red.scope_ns[s] for s in SCOPES if s in red.scope_ns]
    if not found:
        return None
    return sum(found) * 1e-6 / ctx["epochs"]
