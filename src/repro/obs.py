"""Host spans for set-up work, on the profiler's clock.

``span(name)`` opens a ``jax.profiler.TraceAnnotation("lc.<name>")``,
so under a running ``jax.profiler`` trace the span lands in the host
plane beside the device ops, and adds its elapsed host seconds to a
process-wide total that ``host_seconds()`` returns.  It is always on
and costs two clock reads; keep it off per-epoch paths.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

from jax.profiler import TraceAnnotation

PREFIX = "lc."

_seconds: Dict[str, float] = {}


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(PREFIX + name):
            yield
    finally:
        _seconds[name] = _seconds.get(name, 0.0) + \
            time.perf_counter() - t0


def host_seconds() -> Dict[str, float]:
    """Total host seconds per span name since the process started."""
    return dict(_seconds)
