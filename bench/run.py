#!/usr/bin/env python3
"""LaissezCloud benchmark: one cell, one run, one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

run from the root of a checkout on a machine with a TPU.  A run

1. refuses to run without a TPU (or with fewer chips than the cell
   asks for) and names the device;
2. keeps JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
3. generates the tenant mix from ``--seed`` and builds the deployment
   through the program's normal path, both as the configuration's kit
   (``bench/kits/<name>.py``, ``lcbench.spec``) says;
4. runs the epochs before the cell's segment once and snapshots the
   state on the device;
5. warms up with one epoch from a copy of the snapshot; everything so
   far is ``setup_s``;
6. replays the segment from fresh device copies of the snapshot, back
   to back, while fewer than ``--seconds`` have passed, always
   finishing the replay in progress.  Every number comes from whole
   replays;
7. compares the end state of the window's last replay with the kit's
   plain reference, number by number (the kit's ``readings``, judged by
   ``lcbench.compare``), prints each compared number beside its limit
   on standard error, and prints the result as the last line of
   standard output.

The harness touches a deployment only through ``initial``, ``copy``,
``epoch``, ``orders`` (bids placed so far: ``attempted``),
``counters`` (device int scalars; ``dropped`` counts the bids that
failed), ``compiled_text`` and ``host_state``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` whole replays of at most ``TRACE_SECONDS`` run under the
profiler and the metrics are the cell's per-layer metrics, each read by
``bench/metrics/<name>.py`` from a context of

* ``epochs``: the traced window's epochs;
* ``trace``: the ``lcbench.trace.Reduced`` of its profile, the six
  epoch scopes' self times with the raw ops, harness spans and
  instruction -> op-name path map they came from (``lcbench.inner``
  reduces those for any other scope);
* ``counters``: each of the deployment's counters, its delta over the
  window;
* ``config``: the cell's configuration.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# a traced window holds whole replays for this long at most: reading
# the trace takes several times the window, and a run has 360 s
TRACE_SECONDS = 5.0
SCOPES = ("epoch_policy", "epoch_cancel_all", "epoch_step", "epoch_stats",
          "epoch_after_step", "epoch_advance")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"needs {chips} TPU chip(s); JAX sees "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def use_compile_cache(root: str) -> str:
    """The persistent cache at a fixed path inside the checkout."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def weighted_p95(latencies, weights) -> float:
    """Nearest-rank 95th percentile of ``latencies`` where each one
    stands for ``weights`` samples (the bids its epoch carried)."""
    pairs = sorted(zip(latencies, weights))
    total = sum(w for _, w in pairs)
    if total <= 0:
        return None                 # no bid was placed in the window
    need, acc = 0.95 * total, 0
    for lat, w in pairs:
        acc += w
        if acc >= need:
            return lat
    return pairs[-1][0]


class Stages:
    """Set-up stage boundaries, seconds since process start."""

    def __init__(self):
        self.marks = [("runtime", time.perf_counter() - T_START)]

    def __call__(self, name: str) -> None:
        self.marks.append((name, time.perf_counter() - T_START))

    def text(self) -> str:
        return " ".join(f"{n}={t:.3f}" for n, t in self.marks)


def counter_deltas(dep, start, end) -> dict:
    """Each of the deployment's counters: its value in state ``end``
    less that in state ``start``, both read in one ``jax.device_get``."""
    import jax
    a, b = jax.device_get((dep.counters(start), dep.counters(end)))
    return {k: int(b[k]) - int(a[k]) for k in b}


class Window:
    """Whole replays of the segment from fresh copies of a snapshot."""

    def __init__(self, dep, snapshot, times):
        self.dep, self.snap, self.times = dep, snapshot, times
        self.orders0 = int(dep.orders(snapshot))
        self.lat, self.bids = [], []
        self.replays, self.replay_s = 0, 0.0
        # each counter's delta, summed over the replays
        self.counters = dict.fromkeys(dep.counters(snapshot), 0)
        self.last = None

    def replay(self):
        import jax
        from jax.profiler import TraceAnnotation
        dep = self.dep
        self.last = None                    # free the previous end state
        with TraceAnnotation("bench.snapshot_copy"):
            state = dep.copy(self.snap)
            jax.block_until_ready(state)
        prev = self.orders0
        t_first = None
        for t in self.times:
            t0 = time.perf_counter()
            if t_first is None:
                t_first = t0
            with TraceAnnotation("bench.dispatch"):
                state = dep.epoch(state, t)
            with TraceAnnotation("bench.sync"):
                jax.block_until_ready(state)
            t1 = time.perf_counter()
            with TraceAnnotation("bench.counter_read"):
                orders = int(dep.orders(state))
            self.lat.append(t1 - t0)
            self.bids.append(orders - prev)
            prev = orders
        self.replay_s += t1 - t_first
        with TraceAnnotation("bench.counter_read"):
            for k, v in counter_deltas(dep, self.snap, state).items():
                self.counters[k] += v
        self.replays += 1
        self.last = state

    def run(self, seconds: float) -> float:
        from jax.profiler import TraceAnnotation
        w0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            while True:
                self.replay()
                if time.perf_counter() - w0 >= seconds:
                    break
        return time.perf_counter() - w0

    @property
    def epochs(self) -> int:
        return self.replays * len(self.times)


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "lcbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def epoch_op_scopes(dep, snapshot, t: float) -> dict:
    """HLO instruction -> op-name path of the compiled epoch."""
    from lcbench.trace import hlo_op_scopes
    return hlo_op_scopes(dep.compiled_text(snapshot, t))


def build(cell, seed: int, stage=lambda name: None, fault=None):
    """The cell's deployment built through its kit from ``seed``, the
    epochs before the segment run once and snapshotted, one segment
    epoch warmed up: ``(params, dep, snapshot, segment times)``.
    ``stage(name)`` marks the end of each step; ``fault(dep)``, for the
    harness's own tests, may break the timed path once it is built."""
    import jax
    kit, cfg, seg = cell.kit, cell.config, cell.traffic
    tick = float(cfg["tick_s"])
    horizon = seg.horizon_ticks * tick
    params = kit.make_params(cfg, horizon, seed)
    stage("tenants")
    dep = kit.Deployment(cfg, horizon, seed, params)
    stage("deployment")
    if fault is not None:
        fault(dep)
    state = dep.initial
    for t in seg.prefix_times(tick):
        state = dep.epoch(state, t)
    snap = jax.block_until_ready(state)
    stage("prefix")
    times = seg.times(tick)
    warm = dep.epoch(dep.copy(snap), times[0])
    jax.block_until_ready(warm)
    int(dep.orders(warm))
    stage("warm-up")
    return params, dep, snap, times


def run(argv=None, *, require_tpu: bool = True, fault=None,
        root: str = ROOT) -> dict:
    """One run; returns the result object (``main`` prints it).
    ``fault(dep)``, for the harness's own tests, may break the timed
    path after the deployment is built."""
    args = parse(argv)
    for p in (os.path.join(root, "src"), BENCH_DIR):
        if p not in sys.path:
            sys.path.insert(0, p)
    from lcbench import compare, spec
    cell = spec.load_cell(args.workload, root)
    devs = devices_for(cell.chips, require_tpu)
    import jax
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    log(f"compile cache: {use_compile_cache(root)}")
    stage = Stages()
    kit, cfg, seg = cell.kit, cell.config, cell.traffic
    limits = compare.load_limits(os.path.join(root, "bench"), cell.name,
                                 kit.NAMES)
    params, dep, snap, times = build(cell, args.seed, stage, fault)
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.6f} prefix_epochs={seg.first} "
        f"segment_epochs={seg.epochs} stages: {stage.text()}")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k: compiles.append(name)
        if "backend_compile" in name else None)
    win = Window(dep, snap, times)
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="lcbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        window_s = win.run(min(args.seconds, TRACE_SECONDS)
                           if args.trace else args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    log(f"window: {window_s:.6f} s real, {win.replays} whole replay(s), "
        f"{win.epochs} epochs, {len(compiles)} compile(s) inside")
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    result = {"correct": False, "attempted": int(sum(win.bids)),
              "failed": int(win.counters.get("dropped", 0)),
              "metrics": {}, "device": device}
    if args.trace:
        from lcbench.trace import find_xplane, reduce_trace
        try:
            red = reduce_trace(find_xplane(trace_dir), SCOPES,
                               op_scopes=epoch_op_scopes(dep, snap,
                                                         times[0]))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_ns * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        ctx = {"epochs": win.epochs, "trace": red,
               "counters": dict(win.counters), "config": cfg}
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": float(v),
                                                "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in red.top_ops],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps]}
        log("scope device s: " + json.dumps(
            {k: v * 1e-9 for k, v in red.scope_ns.items()}))
    else:
        p95 = weighted_p95(win.lat, win.bids)
        e2e = {"epoch_ms": 1e3 * win.replay_s / win.epochs,
               "decision_p95_ms": None if p95 is None else 1e3 * p95,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            if e2e[m["name"]] is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    log("epoch s: " + " ".join(f"{x:.6f}" for x in win.lat[:len(times)])
        + f" | bids: {win.bids[:len(times)]}")

    prog = dep.host_state(win.last)
    del win, snap, dep
    ref = kit.Reference(cfg, params)
    t0 = time.perf_counter()
    ref.run(seg.prefix_times(float(cfg["tick_s"])) + times)
    log(f"reference: {time.perf_counter() - t0:.3f} s")
    values = kit.readings(prog, ref.state())
    correct, rows = compare.judge(values, limits)
    result["correct"] = bool(correct)
    # JSON has no infinity: an infinite reading (an inf or NaN on one
    # side only) is printed as the largest double
    result["checks"] = {k: {"value": min(v, sys.float_info.max),
                            "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        log(f"check {k}: {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAIL'}")
    return result


def main() -> None:
    res = run()
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
