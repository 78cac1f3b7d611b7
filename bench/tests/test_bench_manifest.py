"""BENCHMARK.json against the benchmark's contract, and every file a
cell names."""
import json
import os
import re

import pytest

from bench_helpers import BENCH, CELLS, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_names_and_units(man):
    assert set(man) == TOP_KEYS
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert man["command"] == ["python3", "bench/run.py"]
    assert man["paths"] == ["bench"]
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    metrics = man["end_to_end"] + man["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
    names = [x["name"] for x in metrics]
    assert len(names) == len(set(names))
    assert "setup_s" in e2e


def test_run_seconds_fits_a_full_check(man):
    rs = man["run_seconds"]
    assert 1 <= rs <= 51
    cells = 24
    assert (2 + 14 * cells) * (rs + 60) + cells * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_load(man, cell):
    from lcbench import compare, spec
    c = spec.load_cell(cell, ROOT, man)
    assert c.traffic.first + c.traffic.epochs <= c.traffic.horizon_ticks
    assert c.config["precision"] == "float32"
    lim = compare.load_limits(BENCH, cell, c.kit.NAMES)
    assert list(lim) == list(c.kit.NAMES)
    for m in c.per_layer:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           f"{m['name']}.py"))


def test_every_config_is_used_and_files_are_distinct(man):
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))


def test_peaks_table_names_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    assert "source" in peaks
    from lcbench.peaks import peak
    with pytest.raises(KeyError):
        peak("some unknown device")


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if c.rsplit(".", 1)[-1] in
                                  ("arrival", "steady")])
def test_segment_lies_in_its_phase(man, cell):
    """``arrival`` replays every tenant's arrival from t = 0; ``steady``
    starts after the last arrival and ends before the first deadline,
    on the configuration's own population."""
    from lcbench import spec
    c = spec.load_cell(cell, ROOT, man)
    tick = float(c.config["tick_s"])
    horizon = c.traffic.horizon_ticks * tick
    p = c.kit.tenant_table(c.config, horizon,
                           int(c.config["population_seed"]))
    times = c.traffic.times(tick)
    if c.traffic.name == "arrival":
        assert times[0] == 0.0 and p["arrival_s"].max() <= times[-1]
    else:
        assert p["arrival_s"].max() < times[0]
        assert (p["arrival_s"] + p["deadline_s"]).min() > times[-1]


def _per_layer_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


@pytest.mark.parametrize("metric", _per_layer_names())
def test_reader_with_nothing_to_read_returns_nothing(metric):
    """A per-layer reader that finds nothing returns None, never 0: the
    harness then leaves the metric out of the line."""
    import run
    from lcbench.trace import Reduced
    read = run.load_reader(metric)
    empty = Reduced(window_ns=0.0, busy_ns=0.0, scope_ns={}, n_ops=0)
    for ctx in ({"epochs": 0}, {"epochs": 3, "trace": None},
                {"epochs": 3, "trace": empty, "counters": {}}):
        assert read(ctx) is None, ctx
