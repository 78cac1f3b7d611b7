"""Device time under the engine step's nested scopes
(``lcbench.inner``), the outer attribution of the recorded v5e trace
pinned, ``bench/engine_split.py``'s counters, the readers of the engine
scopes and counters, and the ``fleet_build_s`` reader."""
import dataclasses
import gzip
import json
import os

import pytest

from bench_helpers import (BENCH, tiny_config, bench_run,  # noqa: F401
                           tiny_root)

OUTER = ("epoch_policy", "epoch_cancel_all", "epoch_step", "epoch_stats",
         "epoch_after_step", "epoch_advance")
STEP = "jit(epoch)/epoch_step/jit(step)"


def _nested_ops():
    # (start, end, name, path): the cascade's while (10-60) holds a
    # clearing op with a path and one that inherits; a place op before
    # it; a fleet op after it
    return [
        (0, 8, "%fusion.1 = s32[8]{0} fusion(a)",
         f"{STEP}/engine_place/jit(place)/sort"),
        (10, 60, "%while.2 = (s32[4]) while(b)",
         f"{STEP}/engine_cascade/while"),
        (12, 30, "%fusion.3 = s32[9]{0} fusion(c)",
         f"{STEP}/engine_cascade/while/body/jit(_clear)/market_clear/"
         "gather"),
        (35, 50, "%fusion.4 = pred[9]{0} fusion(d)", ""),
        (70, 80, "%fusion.5 = f32[4]{0} fusion(e)",
         "jit(epoch)/epoch_advance/mul"),
    ]


def _spans():
    return [(0, 100, "bench.window")]


def test_nested_scopes_count_at_every_level():
    from lcbench.inner import INNER_SCOPES, inner_scope_ns
    from lcbench.trace import reduce_events
    ops = _nested_ops()
    inner = inner_scope_ns(ops, _spans(), INNER_SCOPES)
    # the while's self time (50 - 18 - 15) and both body ops; the
    # clear's op counts in the cascade too
    assert inner == {"engine_place": 8, "engine_cascade": 50,
                     "market_clear": 18}
    # the same through the instruction -> path map, as on the TPU
    bare = [(a, b, n, "") for a, b, n, _ in ops]
    paths = {f"fusion.{i}": p for i, (_, _, _, p) in
             enumerate(ops, start=1)}
    paths["while.2"] = paths.pop("fusion.2")
    assert inner_scope_ns(bare, _spans(), INNER_SCOPES, paths) == inner
    # the outer attribution does not see the engine scopes
    red = reduce_events(ops, _spans(), OUTER)
    assert red.scope_ns == {"epoch_step": 8 + 50, "epoch_advance": 10}


def test_scopes_match_whole_path_components_only():
    from lcbench.inner import inner_scope_ns
    ops = [(0, 10, "%fusion.1 = s32[8]{0} fusion(a)",
            "jit(epoch)/epoch_step_x/engine_cascade_2/my_market_clear_y/"
            "add"),
           (20, 30, "%fusion.2 = s32[8]{0} fusion(a)",
            "jit(epoch)/xengine_place/add")]
    got = inner_scope_ns(ops, _spans(), ("epoch_step", "engine_place",
                                         "engine_cascade", "market_clear"))
    assert got == {}


def _recorded():
    path = os.path.join(BENCH, "tests", "data", "tpu_trace_128.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_trace_outer_attribution_is_pinned():
    """The recorded v5e trace reduced as the benchmark reduces it: the
    numbers the benchmark's outer attribution gave when it was
    recorded."""
    from lcbench.inner import inner_scope_ns
    from lcbench.trace import reduce_events
    d = _recorded()
    ops = [tuple(o) for o in d["ops"]]
    spans = [tuple(s) for s in d["spans"]]
    red = reduce_events(ops, spans, OUTER, op_scopes=d["op_scopes"])
    assert red.window_ns == PINNED["window_ns"]
    assert red.busy_ns == PINNED["busy_ns"]
    assert red.n_ops == PINNED["n_ops"]
    assert red.scope_ns == PINNED["scope_ns"]
    assert [lbl for lbl, _ in red.idle_gaps] == PINNED["gap_labels"]
    assert [round(g * 1e9) for _, g in red.idle_gaps] == PINNED["gap_ns"]
    # the program that was traced had no engine scopes
    assert inner_scope_ns(ops, spans, op_scopes=d["op_scopes"]) == {}


def test_counters_are_replay_deltas(bench_run):
    import jax
    import engine_split
    from lcbench import workload
    from lcbench.deploy import Deployment
    cfg = tiny_config()
    params = workload.make_params(cfg, 1200.0, 5)
    dep = Deployment(cfg, 1200.0, 5, params)
    state = dep.initial
    for t in (0.0, 60.0):
        state = dep.epoch(state, t)
    snap = jax.block_until_ready(state)
    win = bench_run.Window(dep, snap, [120.0, 180.0])
    win.replay()
    win.replay()
    got = engine_split.counters(win, snap)
    assert got["waves"] == win.counters["waves"] > 0
    assert got["waves"] <= got["rounds"] <= dep.engine.k * got["waves"]
    assert 0 <= got["frozen"] <= got["waves"]
    assert engine_split.counters(win, snap, ("no_such",)) == \
        {"no_such": None}


def test_split_runs_at_tiny_size(bench_run, tiny_root):
    """The script end to end on the CPU, but for the trace (a CPU trace
    has no device plane)."""
    import engine_split
    out = engine_split.main(["--workload", "tiny.steady", "--seed",
                             "2147483659", "--seconds", "0.01"],
                            require_tpu=False, root=tiny_root, trace=False)
    c = out["counters"]
    assert out["epochs"] > 0 and c["waves"] > 0
    assert out["waves_per_epoch"] == c["waves"] / out["epochs"]
    assert out["claim_rounds_per_wave"] == c["rounds"] / c["waves"]
    assert 0 <= out["frozen_wave_share"] <= 1
    assert set(out["epoch_ms"]) == {"untraced", "traced"}
    assert out["host_s"]["make_fleet"] > 0


@pytest.mark.parametrize("metric,want", [
    ("place_ms", 8), ("cascade_ms", 50), ("clear_ms", 18)])
def test_engine_scope_readers(bench_run, metric, want):
    """The readers of the engine scopes reduce the traced window's raw
    events, per epoch, as ``lcbench.inner`` does."""
    from lcbench.trace import reduce_events
    red = reduce_events(_nested_ops(), _spans(), OUTER)
    read = bench_run.load_reader(metric)
    assert read({"epochs": 2, "trace": red}) == pytest.approx(
        want * 1e-6 / 2)
    # the outer reduction alone, without its events, gives nothing
    bare = dataclasses.replace(red, ops=[], spans=[], op_scopes={})
    assert read({"epochs": 2, "trace": bare}) is None


@pytest.mark.parametrize("metric,counters,want", [
    ("claim_rounds_per_wave", {"waves": 8, "rounds": 124, "frozen": 1},
     15.5),
    ("frozen_wave_share", {"waves": 8, "rounds": 124, "frozen": 1},
     0.125),
    ("claim_rounds_per_wave", {"waves": 0, "rounds": 0}, None),
    ("frozen_wave_share", {"waves": 8}, None)])
def test_engine_counter_readers(bench_run, metric, counters, want):
    got = bench_run.load_reader(metric)({"epochs": 2,
                                         "counters": counters})
    assert got == want


def test_fleet_build_s_reads_the_make_fleet_span(bench_run):
    from lcbench.trace import Reduced
    from repro.obs import host_seconds
    from repro.sim import simulator as S
    S.make_fleet(S.FleetScenarioConfig(
        n_leaves=128, n_training=4, n_inference=4, n_batch=4,
        duration_s=600.0, tick_s=60.0, b_max=128))
    red = Reduced(window_ns=1e9, busy_ns=5e8, scope_ns={}, n_ops=1)
    got = bench_run.load_reader("fleet_build_s")({"epochs": 3,
                                                  "trace": red})
    assert got == host_seconds()["make_fleet"] > 0


# reduce_events on the recorded trace at the commit that recorded it
PINNED = {
    "window_ns": 15573309.0, "busy_ns": 4650323.0, "n_ops": 5643,
    "scope_ns": {"other": 24538.0, "epoch_step": 4437114.0,
                 "epoch_policy": 154005.0, "epoch_cancel_all": 65.0,
                 "epoch_stats": 1718.0, "epoch_after_step": 32235.0,
                 "epoch_advance": 648.0},
    "gap_labels": ["bench.sync", "bench.snapshot_copy", "bench.sync",
                   "bench.snapshot_copy", "bench.snapshot_copy",
                   "bench.sync", "bench.dispatch", "bench.snapshot_copy",
                   "bench.counter_read", "bench.snapshot_copy"],
    "gap_ns": [3491312, 3334391, 2459884, 1620501, 2434, 1836, 1770, 720,
               716, 705],
}
