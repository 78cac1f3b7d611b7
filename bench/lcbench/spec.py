"""Cells, configurations and traffic mixes, found by name.

``BENCHMARK.json`` at the checkout's root lists the cells; each cell
names a configuration (``bench/configs/<name>.json``, located through
the manifest's ``file``) and a traffic mix
(``bench/traffic/<name>.json``).  A later cell is a new entry plus new
data files; nothing here changes for it.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
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


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: Segment
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
    return Cell(name=name, config=cfg,
                traffic=load_traffic(w["traffic"],
                                     os.path.join(root, "bench")),
                chips=int(w["chips"]),
                end_to_end=[m for m in man["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in man["per_layer"]
                           if _applies(m, name)])
