"""The trace reduction: busy union, self time per scope with nested
ops counted once, and idle gaps labelled by the harness span; a traced
run's readers."""
import os

import pytest

from bench_helpers import BENCH, bench_run, tiny_root  # noqa: F401

SCOPES = ("epoch_policy", "epoch_step", "epoch_after_step",
          "epoch_advance")


def _ops():
    # (start, end, name, stats): a while in epoch_step (10-60) with two
    # body ops, one of which carries no scope of its own; a policy op
    # before it; an advance op after a gap; one op outside the window
    return [
        (0, 5, "%fusion.1 = f32[8]{0} fusion(x)", "jit(epoch)/epoch_policy/add"),
        (10, 60, "%while.2 = (s32[4]) while(y)", "jit(epoch)/epoch_step/while"),
        (12, 30, "%fusion.3 = pred[170]{0} fusion(z)", "jit(epoch)/epoch_step/while/body/gather"),
        (35, 50, "%fusion.4 = s32[4]{0} fusion(w)", ""),
        (80, 90, "fusion.5", ""),
        (200, 210, "%copy.6 = f32[8]{0} copy(x)", ""),
    ]


def _spans():
    return [(0, 100, "bench.window"), (60, 80, "bench.counter_read"),
            (90, 100, "bench.dispatch"), (5, 10, "bench.dispatch")]


def test_self_time_per_scope_counts_nested_ops_once():
    from lcbench.trace import reduce_events
    red = reduce_events(_ops(), _spans(), SCOPES,
                        op_scopes={"fusion.5": "jit(epoch)/epoch_advance/mul"})
    assert red.window_ns == 100
    assert red.busy_ns == 5 + 50 + 10
    # the while's self time (50 - 18 - 15) plus both body ops, the
    # unscoped one inheriting epoch_step from the while around it
    assert red.scope_ns == {"epoch_policy": 5, "epoch_step": 50,
                            "epoch_advance": 10}
    assert sum(red.scope_ns.values()) == red.busy_ns
    assert red.n_ops == 5
    top = dict(red.top_ops)
    assert top["epoch_step:fusion.3 pred[170]"] == pytest.approx(18e-9)
    assert top["epoch_step:while.2 (s32[4]"] == pytest.approx(17e-9)


def test_idle_gaps_are_labelled_by_the_host_span():
    from lcbench.trace import reduce_events
    red = reduce_events(_ops(), _spans(), SCOPES)
    assert red.idle_gaps == [("bench.counter_read", pytest.approx(20e-9)),
                             ("bench.dispatch", pytest.approx(10e-9)),
                             ("bench.dispatch", pytest.approx(5e-9))]
    # unscoped ops fall under "other" without a map
    assert "other" in red.scope_ns


def test_hlo_map_reads_op_names():
    from lcbench.trace import hlo_op_scopes, instr_name
    text = ('  %fusion.995 = pred[170000]{0} fusion(%a), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(epoch)/epoch_step/while/'
            'body/gather" source_file="x.py"}\n'
            '  ROOT %tuple.1 = (s32[]) tuple(%b)\n')
    assert hlo_op_scopes(text) == {
        "fusion.995": "jit(epoch)/epoch_step/while/body/gather"}
    assert instr_name("%fusion.995 = pred[170000]{0} fusion(%a)") == \
        "fusion.995"
    assert instr_name("while.3") == "while.3"


def _recorded():
    import gzip
    import json
    path = os.path.join(BENCH, "tests", "data", "tpu_trace_128.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_tpu_trace():
    """A trace recorded on a TPU v5e (two epochs of a 128-leaf fleet in
    the harness's window), reduced to its events."""
    import numpy as np
    from lcbench.trace import reduce_events
    d = _recorded()
    ops = [tuple(o) for o in d["ops"]]
    spans = [tuple(s) for s in d["spans"]]
    all_scopes = ("epoch_policy", "epoch_cancel_all", "epoch_step",
                  "epoch_stats", "epoch_after_step", "epoch_advance")
    red = reduce_events(ops, spans, all_scopes, op_scopes=d["op_scopes"])
    (w0, w1), = [(a, b) for a, b, n in spans if n == "bench.window"]
    assert red.window_ns == w1 - w0
    # busy: the union of the op intervals, against a 1 ns timeline
    line = np.zeros(int(w1 - w0) + 2, bool)
    for a, b, _, _ in ops:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            line[int(round(a - w0)):int(round(b - w0))] = True
    assert red.busy_ns == pytest.approx(line.sum(), abs=len(ops))
    # self times: every busy ns is charged once, nested ops included
    assert sum(red.scope_ns.values()) == pytest.approx(red.busy_ns,
                                                       rel=1e-9)
    # the epoch scopes agree with the device's own module events
    module = sum(b - a for a, b, n in d["modules"]
                 if n.startswith("jit_epoch") and w0 <= a and b <= w1)
    scoped = sum(v for k, v in red.scope_ns.items() if k != "other")
    assert scoped == pytest.approx(module, rel=0.02)
    assert red.scope_ns["epoch_step"] > 0.9 * scoped
    # idle gaps: the largest holes, each labelled by a harness span
    assert sum(g for _, g in red.idle_gaps) <= \
        (red.window_ns - red.busy_ns) * 1e-9 + 1e-12
    assert red.idle_gaps[0][0] in ("bench.sync", "bench.snapshot_copy")
    assert all(lbl.startswith("bench.") for lbl, _ in red.idle_gaps)


def _cpu_ops(pd):
    """The CPU's XLA ops, from the host threads that run them: on the
    CPU no device plane holds an ``XLA Ops`` line."""
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name, "")
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines if line.name.startswith("tf_XLA")
            for e in line.events]


def test_traced_run_reports_every_per_layer_metric(bench_run, tiny_root,
                                                   monkeypatch):
    """A ``--trace 1`` run end to end at a tiny size: every reader gets
    its context (trace, counters, config) and reports.  The numbers are
    the CPU's and are not checked."""
    import json
    from lcbench import trace as T
    monkeypatch.setattr(T, "_op_events", _cpu_ops)
    res = bench_run.run(["--workload", "tiny.arrival", "--seed", "5",
                         "--seconds", "0.05", "--trace", "1"],
                        require_tpu=False, root=tiny_root)
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        per_layer = [m["name"] for m in json.load(f)["per_layer"]]
    assert res["correct"] is True
    assert sorted(res["metrics"]) == sorted(per_layer)
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert res["breakdown"]["device_ops"]
