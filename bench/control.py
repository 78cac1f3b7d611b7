#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` (``bench/limits``).

    python3 bench/control.py --workload <cell> --seeds 1,2,3,...

For each seed, in one process on the chip: the program replays the
cell's segment once through the timed entry (the kit's
``Deployment.epoch``, after the prefix, from the snapshot), and the
kit's plain reference runs the same epochs.  It prints, per seed, the
compared numbers of

* ``program``: the program against the float32 reference (the sound
  readings, whose largest over the seeds is a limit's lower reading);
* ``control``: the reference computed in bfloat16, the precision below
  the configuration's float32, put in the program's place (whose
  smallest over the seeds is a limit's upper reading);

then one JSON line with both, per number.  The benchmark's own runs do
not run it.
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None, require_tpu: bool = True, root: str = ROOT) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for p in (os.path.join(root, "src"), BENCH_DIR):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench_run
    from lcbench import spec
    cell = spec.load_cell(args.workload, root)
    kit = cell.kit
    bench_run.devices_for(cell.chips, require_tpu)
    if require_tpu:
        bench_run.use_compile_cache(root)
    import jax
    cfg, seg = cell.config, cell.traffic
    tick = float(cfg["tick_s"])
    horizon = seg.horizon_ticks * tick
    seeds = [int(s) for s in args.seeds.split(",")]
    times = seg.prefix_times(tick) + seg.times(tick)
    dep = None
    prog_max, ctl_min = {}, {}
    for seed in seeds:
        t0 = time.perf_counter()
        params = kit.make_params(cfg, horizon, seed)
        if dep is None:
            dep = kit.Deployment(cfg, horizon, seed, params)
        else:
            dep.set_params(params)
        state = dep.initial
        for t in seg.prefix_times(tick):
            state = dep.epoch(state, t)
        state = dep.copy(state)
        for t in seg.times(tick):
            state = dep.epoch(state, t)
        prog = dep.host_state(jax.block_until_ready(state))
        del state
        t1 = time.perf_counter()
        ref = kit.Reference(cfg, params).run(times).state()
        t2 = time.perf_counter()
        ctl = kit.Reference(cfg, params, precision="bfloat16").run(
            times).state()
        t3 = time.perf_counter()
        p = kit.readings(prog, ref)
        c = kit.readings(ctl, ref)
        for k in kit.NAMES:
            prog_max[k] = max(prog_max.get(k, 0.0), p[k])
            ctl_min[k] = min(ctl_min.get(k, float("inf")), c[k])
        print(json.dumps({"seed": seed, "program": p, "control": c,
                          "program_s": t1 - t0, "reference_s": t2 - t1,
                          "control_s": t3 - t2}), flush=True)
    out = {"workload": cell.name, "seeds": seeds,
           "program_max": prog_max, "control_min": ctl_min}
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
