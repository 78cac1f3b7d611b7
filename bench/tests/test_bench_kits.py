"""Kits: a configuration names the module that binds its tenant
generator, deployment, reference and compared numbers
(``bench/kits/<name>.py``), and a new one runs through ``bench/run.py``
with new files only."""
import os
import shutil

import pytest

from bench_helpers import (BENCH, add_cell, tiny_config,  # noqa: F401
                           tiny_limits, bench_run, tiny_root)

ARGS = ["--seed", "2147483659", "--seconds", "0.05", "--trace", "0"]

# a kit of its own, as a later configuration would bring it: it wraps
# ``fleet`` and compares one more number, the count of owned leaves
OWNED_KIT = '''
import os

import numpy as np

from lcbench.spec import load_kit

fleet = load_kit("fleet", os.path.dirname(os.path.dirname(__file__)))
tenant_table = fleet.tenant_table
NAMES = fleet.NAMES + ("owned_off",)


def make_params(config, horizon_s, seed):
    return fleet.make_params(config, horizon_s, seed)


def _owned(state):
    return dict(state, owned=int(np.sum(state["owner"] >= 0)))


class Deployment(fleet.Deployment):
    def host_state(self, state):
        return _owned(super().host_state(state))


class Reference(fleet.Reference):
    def state(self):
        return _owned(super().state())


def readings(prog, ref):
    return dict(fleet.readings(prog, ref),
                owned_off=float(abs(prog["owned"] - ref["owned"])))
'''


def _checkout(tiny_root, tmp_path):
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    return str(root)


def test_fleet_is_the_kit_of_a_config_that_names_none(bench_run,
                                                      tiny_root, tmp_path):
    """A config naming ``fleet`` runs exactly as one naming no kit."""
    root = _checkout(tiny_root, tmp_path)
    add_cell(root, "tiny-fleet.steady",
             dict(tiny_config(), name="tiny-fleet", kit="fleet"), "steady",
             tiny_limits("tiny.steady"))
    # a window of 0 s holds one whole replay, so both attempt as many
    args = ["--seed", "2147483659", "--seconds", "0", "--trace", "0"]
    got = [bench_run.run(["--workload", cell] + args, require_tpu=False,
                         root=root)
           for cell in ("tiny.steady", "tiny-fleet.steady")]
    assert got[0]["correct"] is True
    for key in ("checks", "attempted", "failed"):
        assert got[0][key] == got[1][key], key


def _unchanged(dep):
    """A step that returns its state unchanged."""
    dep.epoch = lambda state, t: dep.copy(state)


@pytest.mark.parametrize("fault,want", [(None, True), (_unchanged, False)],
                         ids=["sound", "unchanged_state"])
def test_a_kit_of_new_files_runs_end_to_end(bench_run, tiny_root, tmp_path,
                                            fault, want):
    root = _checkout(tiny_root, tmp_path)
    with open(os.path.join(root, "bench", "kits", "owned.py"), "w") as f:
        f.write(OWNED_KIT)
    limits = dict(tiny_limits("tiny.arrival"),
                  owned_off={"limit": 0.0, "lower": 0.0, "upper": 1.0})
    add_cell(root, "tiny-owned.arrival",
             dict(tiny_config(), name="tiny-owned", kit="owned"),
             "arrival", limits)
    res = bench_run.run(["--workload", "tiny-owned.arrival"] + ARGS,
                        require_tpu=False, fault=fault, root=root)
    assert res["correct"] is want, res["checks"]
    assert list(res["checks"])[-1] == "owned_off"
    if want:
        assert res["attempted"] > 0 and res["failed"] == 0
    else:
        assert res["checks"]["owned_off"]["value"] > 0


def test_unknown_kit_stops_before_any_device_work(bench_run, tiny_root,
                                                  tmp_path):
    root = _checkout(tiny_root, tmp_path)
    add_cell(root, "tiny-none.arrival",
             dict(tiny_config(), name="tiny-none", kit="no_such"),
             "arrival", tiny_limits("tiny.arrival"))
    # the look for a TPU would stop a CPU run too, so stopping with the
    # kit's message shows that the kit is resolved first
    with pytest.raises(SystemExit, match=r"unknown kit 'no_such'; "
                       r"known: \['fleet'\]"):
        bench_run.run(["--workload", "tiny-none.arrival"] + ARGS,
                      require_tpu=True, root=root)
    from lcbench.spec import load_kit
    with pytest.raises(SystemExit, match="unknown kit '../run'"):
        load_kit("../run", BENCH)
    kits = tmp_path / "bench" / "kits"
    kits.mkdir(parents=True)
    (kits / "bare.py").write_text("NAMES = ()\n")
    with pytest.raises(SystemExit, match="lacks .'tenant_table'"):
        load_kit("bare", str(tmp_path / "bench"))
