"""``correct`` at a tiny size on the CPU: a sound run passes; each fault
the cells can have, planted under the timed path, fails; the control
(the reference in bfloat16, in the program's place) fails the limits
of every cell.  The harness's look for a chip is skipped; everything
else of a run is driven as on the chip."""
import numpy as np
import pytest

from bench_helpers import (BENCH, CELLS, tiny_config,  # noqa: F401
                           bench_run, tiny_root)

ARGS = ["--seed", "2147483659", "--seconds", "0.05", "--trace", "0"]


def _keep(dep):
    return None


def _unchanged(dep):
    """A step that returns its state unchanged."""
    dep.epoch = lambda state, t: dep.copy(state)


def _half_batch(dep):
    """Half of each epoch's bid batch (every second bid) left out."""
    import jax.numpy as jnp
    fleet = dep.runner.fleet
    policy = fleet.policy

    def half(*a):
        limits, relinq, sel, bids, state, info = policy(*a)
        odd = jnp.arange(bids["tenant"].shape[0]) % 2 == 1
        bids = dict(bids, tenant=jnp.where(odd, -1, bids["tenant"]))
        return limits, relinq, sel, bids, state, info
    fleet.policy = half


def _altered(dep):
    """One answer altered where it is produced: a leaf's owner."""
    epoch = dep.epoch

    def alter(state, t):
        est, fst, stats = epoch(state, t)
        owner = est["owner"]
        est = dict(est, owner=owner.at[0].set(
            np.where(owner[0] == 1, 2, 1)))
        return est, fst, stats
    dep.epoch = alter


@pytest.mark.parametrize("cell", ["tiny.arrival", "tiny.steady"])
@pytest.mark.parametrize("fault,want", [
    (_keep, True), (_unchanged, False), (_half_batch, False),
    (_altered, False)], ids=["sound", "unchanged_state", "half_batch",
                             "altered_answer"])
def test_run_correct_under_faults(bench_run, tiny_root, cell, fault,
                                  want):
    res = bench_run.run(["--workload", cell] + ARGS, require_tpu=False,
                        fault=fault, root=tiny_root)
    assert res["correct"] is want, res["checks"]
    assert list(res)[-1] == "checks"
    if want:
        assert set(res["metrics"]) == {"epoch_ms", "decision_p95_ms",
                                       "setup_s"}
        assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_every_cells_limits(cell):
    from lcbench import compare, workload
    from lcbench.reference import Reference
    cfg = tiny_config()
    limits = compare.load_limits(BENCH, cell)
    times = [i * 60.0 for i in range(14)]
    for seed in (1, 2, 3):
        params = workload.make_params(cfg, 1200.0, seed)
        ref = Reference(cfg, params).run(times).state()
        ctl = Reference(cfg, params, precision="bfloat16").run(
            times).state()
        ok, rows = compare.judge(compare.readings(ctl, ref), limits)
        assert not ok, (seed, rows)
        same = compare.readings(ref, ref)
        assert compare.judge(same, limits)[0]
