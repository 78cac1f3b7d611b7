"""The window's replays, and the harness's inputs, at a tiny size."""
import jax
import numpy as np

from bench_helpers import tiny_config, bench_run  # noqa: F401


def test_two_replays_from_one_snapshot_agree(bench_run):
    from lcbench import workload
    from lcbench.deploy import Deployment
    cfg = tiny_config()
    params = workload.make_params(cfg, 1200.0, 7)
    dep = Deployment(cfg, 1200.0, 7, params)
    state = dep.initial
    for t in (0.0, 60.0, 120.0):
        state = dep.epoch(state, t)
    snap = jax.block_until_ready(state)
    win = bench_run.Window(dep, snap, [180.0, 240.0, 300.0])
    ends = []
    for _ in range(2):
        win.replay()
        ends.append(dep.host_state(win.last))
    for key in ("owner", "rate", "bills", "limit", "acq"):
        np.testing.assert_array_equal(ends[0][key], ends[1][key], key)
    assert ends[0]["stats"] == ends[1]["stats"]
    assert win.bids[:3] == win.bids[3:] and sum(win.bids) > 0
    # the snapshot itself is untouched by the donated replays
    assert int(snap[2]["orders"]) == win.orders0


def test_window_counters_are_deltas_of_the_state(bench_run):
    """The readers' counters are each of the deployment's counters, end
    of the replay less the snapshot, summed over the replays."""
    from lcbench import workload
    from lcbench.deploy import COUNTER_KEYS, Deployment
    cfg = tiny_config()
    params = workload.make_params(cfg, 1200.0, 11)
    dep = Deployment(cfg, 1200.0, 11, params)
    state = dep.initial
    for t in (0.0, 60.0):
        state = dep.epoch(state, t)
    snap = jax.block_until_ready(state)
    win = bench_run.Window(dep, snap, [120.0, 180.0])
    win.replay()
    end, start = win.last[0], snap[0]
    assert set(win.counters) == set(COUNTER_KEYS)
    assert win.counters == {k: int(end[k]) - int(start[k])
                            for k in COUNTER_KEYS}
    assert win.counters["waves"] > 0
    win.replay()
    assert win.counters == {k: 2 * (int(end[k]) - int(start[k]))
                            for k in COUNTER_KEYS}


def test_generator_matches_the_programs_tenant_mix():
    """``lcbench.workload`` is a copy of the program's scenario
    generator; at the same seed both give the same arrays."""
    from repro.sim.fleet import params_from_tenants
    from repro.sim.simulator import ScenarioConfig, make_tenants
    from repro.core.topology import build_cluster
    from lcbench import workload
    cfg = tiny_config()
    for seed in (0, 2**31 + 11):
        mine = workload.tenant_table(cfg, 1200.0, seed)
        topo = build_cluster({"H100": cfg["n_leaves"]}, gpus_per_host=8,
                             hosts_per_rack=4, racks_per_zone=4)
        sc = ScenarioConfig(regime=cfg["regime"], n_h100=cfg["n_leaves"],
                            n_a100=0, duration_s=1200.0, tick_s=60.0,
                            seed=seed, n_training=cfg["n_training"],
                            n_inference=cfg["n_inference"],
                            n_batch=cfg["n_batch"])
        theirs = params_from_tenants(make_tenants(sc, topo), 1200.0)
        assert set(mine) == set(theirs)
        for k in mine:
            np.testing.assert_array_equal(mine[k], np.asarray(theirs[k]),
                                          k)


def test_weighted_p95_is_nearest_rank_over_bids(bench_run):
    lat = [5.0, 1.0, 3.0]
    assert bench_run.weighted_p95(lat, [1, 90, 9]) == 3.0
    assert bench_run.weighted_p95(lat, [10, 80, 10]) == 5.0
    assert bench_run.weighted_p95(lat, [0, 100, 0]) == 1.0


def test_seeds_order_one_population():
    """Every seed brings the configuration's population, reordered."""
    from lcbench import workload
    cfg = tiny_config()
    a = workload.make_params(cfg, 1200.0, 3)
    b = workload.make_params(cfg, 1200.0, 2**31 + 7)
    assert not np.array_equal(a["arrival_s"], b["arrival_s"])
    order_a = np.lexsort((a["arrival_s"], a["work"], a["kind"]))
    order_b = np.lexsort((b["arrival_s"], b["work"], b["kind"]))
    for k in a:
        np.testing.assert_array_equal(a[k][order_a], b[k][order_b], k)
    np.testing.assert_array_equal(
        workload.make_params(cfg, 1200.0, 3)["rates"], a["rates"])
