"""Profiler-trace reduction: device busy time, device time per program
scope, the costliest device operations and the longest idle gaps.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes.  The device
plane (``/device:TPU:0``) carries an ``XLA Ops`` line whose events
nest: a ``while`` op spans the ops of its body.  So

* busy time is the union of the op intervals;
* each op is charged its SELF time (its duration less that of the ops
  nested in it), so nothing is counted twice;
* an op's scope is the first of ``scopes`` found in its op-name path,
  taken from ``op_scopes``, a map from HLO instruction name to op-name
  path built from the compiled program's HLO text (``hlo_op_scopes``):
  the TPU trace's op events carry no path of their own;
* an idle gap is a hole in the busy union inside the window, labelled
  with the harness span (``bench.*``, on the host plane) that overlaps
  it most.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."          # the harness's own host spans
WINDOW = "bench.window"         # the span around the measured window
TOP = 10                        # entries of each breakdown list

_META = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                   r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def hlo_op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op_name metadata, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _META.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def instr_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` or ``fusion.12`` ->
    ``fusion.12``."""
    s = event_name.strip().lstrip("%")
    return re.split(r"[\s=]", s, maxsplit=1)[0]


def _shape(event_name: str) -> str:
    m = re.search(r"=\s*(\(?[a-z0-9]+\[[^\]]*\])", event_name)
    return m.group(1) if m else ""


@dataclass
class Reduced:
    """The reduction, and the raw events and instruction -> path map it
    was made from (``lcbench.inner`` reduces those again per scope)."""
    window_ns: float
    busy_ns: float
    scope_ns: Dict[str, float]
    n_ops: int
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    ops: List[Tuple[float, float, str, str]] = field(default_factory=list)
    spans: List[Tuple[float, float, str]] = field(default_factory=list)
    op_scopes: Dict[str, str] = field(default_factory=dict)


def _merge(intervals: List[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _op_events(pd) -> List[Tuple[float, float, str, str]]:
    """(start_ns, end_ns, event name, "") of the first device plane's
    ``XLA Ops`` line."""
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            # the TPU's op events carry no scope in their stats, so
            # they are not read (reading them doubles the time)
            return [(e.start_ns, e.start_ns + e.duration_ns, e.name, "")
                    for e in line.events]
    return []


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def reduce_trace(path: str, scopes: Sequence[str],
                 op_scopes: Optional[Dict[str, str]] = None) -> Reduced:
    """Reduce one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops = _op_events(pd)
    if not ops:
        raise ValueError(f"{path}: no device 'XLA Ops' events")
    return reduce_events(ops, _host_spans(pd), scopes, op_scopes)


def reduce_events(ops: List[Tuple[float, float, str, str]],
                  spans: List[Tuple[float, float, str]],
                  scopes: Sequence[str],
                  op_scopes: Optional[Dict[str, str]] = None) -> Reduced:
    """The reduction proper, on ``(start_ns, end_ns, name, stats)``
    device ops and ``(start_ns, end_ns, name)`` harness spans.  The
    ``bench.window`` span bounds the window (the ops' extent where it
    is absent)."""
    raw = (ops, spans, op_scopes or {})
    wins = [s for s in spans if s[2] == WINDOW]
    if wins:
        w0 = min(s[0] for s in wins)
        w1 = max(s[1] for s in wins)
    else:
        w0 = min(o[0] for o in ops)
        w1 = max(o[1] for o in ops)
    ops = [o for o in ops if o[1] > w0 and o[0] < w1]
    op_scopes = op_scopes or {}

    def scope_of(name: str, stats: str) -> str:
        for path_ in (stats, op_scopes.get(instr_name(name), "")):
            for s in scopes:
                if s in path_:
                    return s
        return "other"

    # self time: walk ops in start order with a stack of open parents;
    # an op whose own path names no scope takes its parent's
    ops.sort(key=lambda o: (o[0], -o[1]))
    self_ns = [o[1] - o[0] for o in ops]
    scope = [""] * len(ops)
    stack: List[int] = []
    for i, (a, b, name, st) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= a:
            stack.pop()
        scope[i] = scope_of(name, st)
        if stack:
            self_ns[stack[-1]] -= (min(b, ops[stack[-1]][1]) - a)
            if scope[i] == "other":
                scope[i] = scope[stack[-1]]
        stack.append(i)
    scope_ns: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    for (a, b, name, st), sn, sc in zip(ops, self_ns, scope):
        scope_ns[sc] = scope_ns.get(sc, 0.0) + sn
        key = f"{sc}:{instr_name(name)} {_shape(name)}".strip()
        by_op[key] = by_op.get(key, 0.0) + sn
    busy = _merge([(max(a, w0), min(b, w1)) for a, b, _, _ in ops])
    busy_ns = sum(b - a for a, b in busy)
    gaps = []
    prev = w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    inner = [s for s in spans if s[2] != WINDOW]
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, cover = "host:outside bench spans", 0.0
        for s0, s1, nm in inner:
            ov = min(b, s1) - max(a, s0)
            if ov > cover:
                best, cover = nm, ov
        labelled.append((best, (b - a) * 1e-9))
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(window_ns=w1 - w0, busy_ns=busy_ns, scope_ns=scope_ns,
                   n_ops=len(ops),
                   top_ops=[(k, v * 1e-9) for k, v in top_ops],
                   idle_gaps=labelled, ops=raw[0], spans=raw[1],
                   op_scopes=raw[2])
