"""Helpers and fixtures of the benchmark harness's CPU tests: the
harness and the program on ``sys.path``, one tiny configuration, a
checkout-shaped directory with it under both traffic mixes (and a way
to add cells to a copy of it), and ``bench/run.py`` as a module.
(Test modules import the fixtures by name; a ``conftest.py`` here would
shadow the repository's own ``tests/conftest.py``, which its tests
import as ``conftest``.)"""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]

TINY = dict(n_leaves=128, n_training=6, n_inference=6, n_batch=4,
            b_max=128)
TINY_CELLS = {"tiny.arrival": "arrival", "tiny.steady": "steady"}
# the tiny cells are judged by the limits of the full-size cells
LIMITS_OF = {"tiny.arrival": "paper-10k.arrival",
             "tiny.steady": "paper-10k.steady"}


def tiny_config() -> dict:
    with open(os.path.join(BENCH, "configs", "paper-10k.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY, name="tiny")
    return cfg


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory: BENCHMARK.json with the tiny cells,
    the tiny config, the real traffic mixes, kits and limits."""
    root = tmp_path_factory.mktemp("tiny_root")
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "limits").mkdir()
    for d in ("traffic", "kits"):
        shutil.copytree(os.path.join(BENCH, d), bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(tiny_config()))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    man["configs"] = [dict(man["configs"][0], name="tiny",
                           file="bench/configs/tiny.json")]
    man["workloads"] = [dict(man["workloads"][0], name=c, config="tiny",
                             traffic=t) for c, t in TINY_CELLS.items()]
    for m in man["per_layer"]:
        m["workloads"] = list(TINY_CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    for c, full in LIMITS_OF.items():
        shutil.copy(os.path.join(BENCH, "limits", f"{full}.json"),
                    bench / "limits" / f"{c}.json")
    return str(root)


def add_cell(root, cell: str, config: dict, traffic: str,
             limits: dict) -> None:
    """Add to the checkout at ``root`` the configuration ``config``
    (``bench/configs/<its name>.json``), a cell of it under ``traffic``
    and the cell's ``limits`` file."""
    bench = os.path.join(root, "bench")
    name = config["name"]
    with open(os.path.join(bench, "configs", f"{name}.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "limits", f"{cell}.json"), "w") as f:
        json.dump(limits, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["configs"].append(dict(man["configs"][0], name=name,
                               file=f"bench/configs/{name}.json"))
    man["workloads"].append(dict(man["workloads"][0], name=cell,
                                 config=name, traffic=traffic))
    for m in man["per_layer"]:
        m["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(man, f)


def tiny_limits(cell: str) -> dict:
    """The limits file a tiny cell is judged by."""
    with open(os.path.join(BENCH, "limits", f"{LIMITS_OF[cell]}.json")) as f:
        return json.load(f)


@pytest.fixture
def bench_run(monkeypatch):
    """``bench/run.py`` as a module, with the persistent compilation
    cache left off (tests must not write one)."""
    import run
    monkeypatch.setattr(run, "use_compile_cache", lambda root: "off")
    return run
