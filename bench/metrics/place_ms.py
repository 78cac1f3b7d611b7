"""Device milliseconds per epoch under the ``engine_place`` scope: the
engine step's bid clipping and placement into the book
(``BatchEngine.step``: ``_clip_bids`` and ``place``), self time of the
ops on that scope, from the trace."""
from lcbench.inner import scope_ms


def read(ctx):
    return scope_ms(ctx, "engine_place")
