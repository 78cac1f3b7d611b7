"""Device milliseconds per epoch under the ``epoch_step`` scope: the
engine step (``BatchEngine.step``: place, clear/evict/transfer
cascade, bill), self time of the ops in that scope, from the trace."""


def read(ctx):
    red = ctx.get("trace")
    if red is None or "epoch_step" not in red.scope_ns or not ctx["epochs"]:
        return None
    return red.scope_ns["epoch_step"] * 1e-6 / ctx["epochs"]
