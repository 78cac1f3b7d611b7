"""Device milliseconds per epoch under the ``market_clear`` scope: the
clearing passes (``kernels/market_clear/ops.py`` ``_clear``: the
path aggregates and the health mask) of every cascade wave, self time
of the ops on that scope, from the trace.  This time counts in
``cascade_ms`` too."""
from lcbench.inner import scope_ms


def read(ctx):
    return scope_ms(ctx, "market_clear")
