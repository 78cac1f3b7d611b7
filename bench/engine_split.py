#!/usr/bin/env python3
"""Where the engine step's time goes, for one cell, on the chip.

    python3 bench/engine_split.py --workload <cell> --seed <n> \\
        [--seconds 5]

Builds the cell's deployment through its kit and snapshots it as
``bench/run.py`` does (``run.build``), then runs two windows of whole
replays from the snapshot, each of at least ``--seconds``: one
untraced, one under the profiler.  It prints one JSON line with

* ``epoch_ms`` of both windows (their ratio is the profiler's cost);
* ``scope_ms``: device ms per epoch under each ``epoch_*`` scope
  (``lcbench.trace``, the outer attribution the benchmark reads) and
  under each engine scope, ``engine_place``, ``engine_cascade`` and
  ``market_clear`` (``lcbench.inner``: nested scopes count in each);
* ``waves_per_epoch``, ``claim_rounds_per_wave`` and
  ``frozen_wave_share`` from the engine's ``waves``, ``rounds`` and
  ``frozen`` counters (the deployment's ``counters``) over the traced
  window;
* ``host_s``: the program's set-up spans (``repro.obs``);
* the costliest device ops.

A program without a scope, counter or span reports ``null`` there.
The benchmark's own runs do not run it.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def counters(win, snap,
             keys=("waves", "rounds", "frozen", "liveness_gathers")
             ) -> dict:
    """Each counter's delta over the window's replays (every replay
    starts from ``snap``), ``None`` where the deployment has no such
    counter."""
    import run as bench_run
    d = bench_run.counter_deltas(win.dep, snap, win.last)
    return {k: win.replays * d[k] if k in d else None for k in keys}


def _ratio(a, b):
    return None if a is None or not b else a / b


def main(argv=None, require_tpu: bool = True, root: str = ROOT,
         trace: bool = True) -> dict:
    """One measurement; ``trace=False`` (the CPU tests: a CPU trace has
    no device plane) skips the traced window's reduction."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for p in (os.path.join(root, "src"), BENCH_DIR):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench_run
    from lcbench import spec
    cell = spec.load_cell(args.workload, root)
    devs = bench_run.devices_for(cell.chips, require_tpu)
    if require_tpu:
        bench_run.use_compile_cache(root)
    import jax
    _, dep, snap, times = bench_run.build(cell, args.seed)

    plain = bench_run.Window(dep, snap, times)
    plain.run(args.seconds)
    epoch_ms = {"untraced": 1e3 * plain.replay_s / plain.epochs}
    del plain
    win = bench_run.Window(dep, snap, times)
    trace_dir = tempfile.mkdtemp(prefix="lcbench_split_")
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        win.run(args.seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    epoch_ms["traced"] = 1e3 * win.replay_s / win.epochs
    cnt = counters(win, snap)
    out = {"workload": cell.name, "seed": args.seed,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind},
           "epochs": win.epochs, "epoch_ms": epoch_ms,
           "waves_per_epoch": cnt["waves"] / win.epochs,
           "claim_rounds_per_wave": _ratio(cnt["rounds"], cnt["waves"]),
           "frozen_wave_share": _ratio(cnt["frozen"], cnt["waves"]),
           "counters": cnt}
    try:
        from repro.obs import host_seconds
        out["host_s"] = host_seconds()
    except ImportError:
        out["host_s"] = None
    if trace:
        from lcbench.inner import inner_scope_ns
        from lcbench.trace import find_xplane, reduce_trace
        try:
            red = reduce_trace(find_xplane(trace_dir), bench_run.SCOPES,
                               op_scopes=bench_run.epoch_op_scopes(
                                   dep, snap, times[0]))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        scope_ns = dict(red.scope_ns)
        scope_ns.update(inner_scope_ns(red.ops, red.spans,
                                       op_scopes=red.op_scopes))
        out["busy_share"] = red.busy_ns / red.window_ns
        out["scope_ms"] = {k: v * 1e-6 / win.epochs
                           for k, v in sorted(scope_ns.items())}
        out["top_ops"] = red.top_ops
    else:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
