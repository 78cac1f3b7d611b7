"""Cells, configurations, traffic mixes and kits, found by name.

``BENCHMARK.json`` at the checkout's root lists the cells; each cell
names a configuration (``bench/configs/<name>.json``, located through
the manifest's ``file``) and a traffic mix
(``bench/traffic/<name>.json``).  A configuration may name its kit
(``"kit": "<name>"``, ``bench/kits/<name>.py``; ``fleet`` where it names
none): the module that binds its deployment's tenant generator, the
system under test, the plain reference and the compared numbers.  A
later cell is a new entry plus new files; nothing here changes for it.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclass(frozen=True)
class Segment:
    """A traffic mix: the scenario horizon and the replayed segment.

    Epoch ``i`` of the scenario runs at ``t = i * tick_s``.  Epochs
    ``0 .. first - 1`` run once in set-up (the prefix); the window
    replays epochs ``first .. first + epochs - 1`` from a snapshot."""
    name: str
    horizon_ticks: int
    first: int
    epochs: int

    def times(self, tick_s: float) -> List[float]:
        return [float((self.first + i) * tick_s)
                for i in range(self.epochs)]

    def prefix_times(self, tick_s: float) -> List[float]:
        return [float(i * tick_s) for i in range(self.first)]


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> Segment:
    d = load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))
    seg = Segment(name=name, horizon_ticks=int(d["horizon_ticks"]),
                  first=int(d["segment_first_tick"]),
                  epochs=int(d["segment_epochs"]))
    if seg.epochs < 1 or seg.first < 0 or \
            seg.first + seg.epochs > seg.horizon_ticks + 1:
        raise ValueError(f"traffic {name}: segment outside the horizon")
    return seg


DEFAULT_KIT = "fleet"
# what a kit binds:
#   tenant_table(config, horizon_s, seed) -> the population's arrays
#   make_params(config, horizon_s, seed) -> the arrays the program gets
#   Deployment(config, horizon_s, seed, params): the system under test,
#       with initial, copy, epoch, orders, counters, compiled_text,
#       host_state (and set_params, for bench/control.py)
#   Reference(config, params, precision="float32"): .run(times) -> self,
#       .state() -> host_state's layout
#   readings(prog, ref) -> {name: number}, for every name of NAMES
KIT_ATTRS = ("tenant_table", "make_params", "Deployment", "Reference",
             "readings", "NAMES")


def load_kit(name: str, bench_dir: str = BENCH_DIR) -> ModuleType:
    """``<bench_dir>/kits/<name>.py``, loaded by path."""
    kits = os.path.join(bench_dir, "kits")
    path = os.path.join(kits, f"{name}.py")
    if os.path.basename(name) != name or not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(kits)
                       if f.endswith(".py")) if os.path.isdir(kits) else []
        raise SystemExit(f"unknown kit {name!r}; known: {known}")
    mod_name = "lcbench_kit_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered, so that what the kit defines (dataclasses, pickled
    # objects) can find its module
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    missing = [a for a in KIT_ATTRS if not hasattr(mod, a)]
    if missing:
        raise SystemExit(f"kit {name!r} ({path}) lacks {missing}")
    return mod


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: Segment
    kit: ModuleType
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              man: Optional[dict] = None) -> Cell:
    man = manifest(root) if man is None else man
    cells: Dict[str, dict] = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
    bench_dir = os.path.join(root, "bench")
    return Cell(name=name, config=cfg,
                traffic=load_traffic(w["traffic"], bench_dir),
                kit=load_kit(cfg.get("kit", DEFAULT_KIT), bench_dir),
                chips=int(w["chips"]),
                end_to_end=[m for m in man["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in man["per_layer"]
                           if _applies(m, name)])
