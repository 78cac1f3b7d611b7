"""Device milliseconds per epoch under the ``engine_cascade`` scope:
the engine's clear, evict and transfer waves run to their fixpoint
(``BatchEngine._cascade``), the clearing passes inside it included,
self time of the ops on that scope, from the trace."""
from lcbench.inner import scope_ms


def read(ctx):
    return scope_ms(ctx, "engine_cascade")
