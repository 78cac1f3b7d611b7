"""Discrete-event simulation driver + the paper's metrics.

Primary metric (paper §5.1): *performance retention under contention* —
per-tenant performance in a multi-tenant run divided by the same tenant's
performance running alone on the same cluster. We report the distribution
and the mean, plus total cost and performance-per-cost.
"""
from __future__ import annotations

import math
import statistics
import time
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.market import VolatilityControls
from repro.core.topology import Topology, build_cluster
from repro.core.econadapter import AdapterConfig
from repro.sim import traces
from repro.sim.cloud import CloudBase, FCFSCloud, FCFSPCloud, \
    LaissezBatchCloud, LaissezCloud, SpotCloud
from repro.sim.workloads import ON_DEMAND, Tenant, WorkloadParams


@dataclass
class ScenarioConfig:
    regime: str = "slight"          # right_sized | slight | heavy
    n_h100: int = 16
    n_a100: int = 16
    duration_s: float = 7200.0
    tick_s: float = 30.0
    seed: int = 0
    n_training: int = 3
    n_inference: int = 3
    n_batch: int = 2
    overhead_mult: float = 1.0      # Fig 13
    reconfig_estimate_mult: float = 1.0  # Fig 15
    controls: VolatilityControls = field(
        default_factory=lambda: VolatilityControls(max_bid_multiple=4.0,
                                                   floor_fall_rate=0.5,
                                                   min_holding_s=600.0))
    # min_holding_s ~ the largest reconfig overhead: a node must get
    # the chance to amortize its restart before a limit crossing can
    # evict it, else grant->evict treadmills burn both sides' stalls
    # (calibration audit, docs/DESIGN.md §13)
    topology_aware: bool = True     # Fig 10 toggle


# oversubscription factors per regime (Faro demand regimes)
REGIME_DEMAND = {"right_sized": 1.0, "slight": 1.25, "heavy": 2.0}


def make_tenants(cfg: ScenarioConfig, topo: Topology) -> List[Tenant]:
    """Tenant mix sized so aggregate peak demand hits the regime's
    oversubscription of cluster capacity."""
    rng = np.random.default_rng(cfg.seed)
    capacity = cfg.n_h100 * 1.0 + cfg.n_a100 * 0.45
    demand_target = capacity * REGIME_DEMAND[cfg.regime]
    n_t = cfg.n_training + cfg.n_inference + cfg.n_batch
    share = demand_target / max(n_t, 1)
    tenants: List[Tenant] = []
    for i in range(cfg.n_training):
        nodes = max(1, int(round(share * rng.uniform(0.7, 1.3))))
        dl = cfg.duration_s * rng.uniform(0.7, 1.0)
        work = nodes * (dl / 3600.0) * 0.7    # satisfiable alone
        tenants.append(Tenant(
            f"train{i}",
            WorkloadParams(kind="training", work=work, deadline_s=dl,
                           checkpoint_interval_s=rng.uniform(180, 420),
                           reconfig_s=rng.uniform(60, 240),
                           max_nodes=nodes * 2,
                           topology_sensitive=True,
                           value_per_gap=rng.uniform(15, 40)),
            topo, arrival_s=rng.uniform(0, cfg.duration_s * 0.2),
            overhead_mult=cfg.overhead_mult))
    for i in range(cfg.n_inference):
        nodes = max(1, int(round(share * rng.uniform(0.7, 1.3))))
        base_rps = nodes * 10.0 * 0.6
        tenants.append(Tenant(
            f"infer{i}",
            WorkloadParams(kind="inference", deadline_s=cfg.duration_s,
                           reconfig_s=60.0,        # Dynamo ~1 min
                           max_nodes=nodes * 2,
                           rate_fn=traces.llm_request_rate(
                               cfg.seed * 101 + i, cfg.duration_s,
                               base_rps=base_rps),
                           sla_value_per_h=rng.uniform(30, 80)),
            topo, arrival_s=rng.uniform(0, cfg.duration_s * 0.1),
            overhead_mult=cfg.overhead_mult))
    for i in range(cfg.n_batch):
        nodes = max(1, int(round(share * rng.uniform(0.7, 1.3))))
        dl = cfg.duration_s * rng.uniform(0.8, 1.0)
        work = nodes * (dl / 3600.0) * 0.6
        tenants.append(Tenant(
            f"batch{i}",
            WorkloadParams(kind="batch", work=work, deadline_s=dl,
                           checkpoint_interval_s=600.0,
                           reconfig_s=rng.uniform(240, 720),  # Parabricks
                           max_nodes=nodes * 2,
                           topology_sensitive=False,
                           value_per_gap=rng.uniform(8, 20)),
            topo, arrival_s=rng.uniform(0, cfg.duration_s * 0.3),
            overhead_mult=cfg.overhead_mult))
    return tenants


def build_cloud(kind: str, topo: Topology, cfg: ScenarioConfig) -> CloudBase:
    if kind == "fcfs":
        return FCFSCloud(topo)
    if kind == "fcfsp":
        return FCFSPCloud(topo)
    if kind == "spot":
        return SpotCloud(topo)
    if kind == "laissez":
        return LaissezCloud(topo, cfg.controls)
    if kind == "laissez_batch":
        return LaissezBatchCloud(topo, cfg.controls)
    raise ValueError(kind)


@dataclass
class RunResult:
    perf: Dict[str, float]
    cost: Dict[str, float]
    retention: Dict[str, float] = field(default_factory=dict)
    stats: Dict[str, float] = field(default_factory=dict)

    @property
    def mean_retention(self) -> float:
        vals = list(self.retention.values())
        return statistics.fmean(vals) if vals else float("nan")


def run_once(kind: str, cfg: ScenarioConfig,
             only_tenant: Optional[str] = None) -> RunResult:
    topo = build_cluster({"H100": cfg.n_h100, "A100": cfg.n_a100},
                         gpus_per_host=4, hosts_per_rack=2,
                         racks_per_zone=2)
    cloud = build_cloud(kind, topo, cfg)
    tenants = make_tenants(cfg, topo)
    if only_tenant is not None:
        tenants = [t for t in tenants if t.name == only_tenant]
    acfg = AdapterConfig(
        topology_aware=cfg.topology_aware,
        reconfig_estimate_mult=cfg.reconfig_estimate_mult)
    for t in tenants:
        if isinstance(cloud, LaissezCloud):
            cloud.add_tenant(t, acfg)
        else:
            cloud.add_tenant(t)
    t = 0.0
    while t <= cfg.duration_s:
        cloud.step(t)
        for tn in cloud.tenants.values():
            tn.advance(t)
        t += cfg.tick_s
    perf = {tn.name: tn.performance(cfg.duration_s)
            for tn in cloud.tenants.values()}
    cost = {tn.name: cloud.cost_of(tn.name)
            for tn in cloud.tenants.values()}
    stats = {}
    if isinstance(cloud, LaissezCloud):
        stats = dict(cloud.market.stats)
    elif isinstance(cloud, SpotCloud):
        stats = dict(cloud.stats)
    return RunResult(perf=perf, cost=cost, stats=stats)


def run_with_retention(kind: str, cfg: ScenarioConfig) -> RunResult:
    """Multi-tenant run + per-tenant alone runs => retention (Fig 6)."""
    multi = run_once(kind, cfg)
    for name in list(multi.perf):
        alone = run_once(kind, cfg, only_tenant=name)
        denom = max(alone.perf[name], 1e-9)
        multi.retention[name] = min(1.5, multi.perf[name] / denom)
    return multi


# ---------------------------------------------------------------------------
# FleetScenario: the paper's contention scenarios at 10k-node scale on the
# vectorized tenant fleet + batch engine (sim/fleet.py; docs/DESIGN.md §8).
# ---------------------------------------------------------------------------
@dataclass
class FleetScenarioConfig:
    """Scale-path scenario: one homogeneous type-tree, regime-scaled
    tenant mix, every epoch a single array batch into the batch engine."""
    regime: str = "heavy"
    n_leaves: int = 2048
    n_training: int = 24
    n_inference: int = 24
    n_batch: int = 16
    duration_s: float = 1800.0
    tick_s: float = 60.0
    seed: int = 0
    k: int = 16                     # top-K cascade width at fleet scale
    b_max: int = 1024               # bid-batch capacity per epoch
    per_tenant_bids: int = 8
    use_pallas: bool = False
    interpret: Optional[bool] = None    # None = package default
    alone: str = "analytic"         # retention denominator:
    #   "analytic" — uncontended counterfactual, one vectorized run
    #   "engine"   — per-tenant alone runs through the engine (toy scale)
    #   "engine_sampled" — engine-alone for a per-kind sample, analytic
    #                 x per-kind engine/analytic ratio for the rest
    #   "none"     — skip (perf only)
    alone_sample: int = 4           # per-kind sample size (engine_sampled)
    fused: bool = True              # drive epochs through the fused
    # donated megastep (sim/epoch.py); False = the legacy six-dispatch
    # loop (kept for the bit-identity differential suite)
    faults: Optional[list] = None   # fault schedule: a list of
    # sim.faults.FaultEvent records; a fresh FaultInjector is built per
    # drive (so alone runs and reruns replay the identical schedule)
    controls: VolatilityControls = field(
        default_factory=lambda: VolatilityControls(max_bid_multiple=4.0,
                                                   floor_fall_rate=0.5,
                                                   min_holding_s=600.0))

    @property
    def n_tenants(self) -> int:
        return self.n_training + self.n_inference + self.n_batch


@dataclass
class FleetRunResult:
    perf: np.ndarray                 # (n_tenants,) multi-tenant run
    alone_perf: np.ndarray           # (n_tenants,) denominator (or ones)
    retention: np.ndarray            # clip(perf / alone, 1.5)
    epoch_s: List[float]             # wall-clock per multi-run epoch
    stats: Dict[str, float]

    @property
    def mean_retention(self) -> float:
        return float(np.mean(self.retention)) if len(self.retention) \
            else float("nan")


def make_fleet(fcfg: FleetScenarioConfig):
    """Build (topo, tenants, market, fleet, params) for a fleet scenario.

    Tenant mixes reuse ``make_tenants``'s regime scaling on a single
    H100 tree; ``topology_sensitive`` is forced off — the fleet's v1
    fidelity contract is locality-free (sim/fleet.py docstring)."""
    from repro import obs
    from repro.market_jax.bridge import BatchMarket
    from repro.sim.fleet import Fleet, FleetConfig, params_from_tenants
    with obs.span("make_fleet"):
        topo = build_cluster({"H100": fcfg.n_leaves}, gpus_per_host=8,
                             hosts_per_rack=4, racks_per_zone=4)
        scfg = ScenarioConfig(
            regime=fcfg.regime, n_h100=fcfg.n_leaves, n_a100=0,
            duration_s=fcfg.duration_s, tick_s=fcfg.tick_s,
            seed=fcfg.seed, n_training=fcfg.n_training,
            n_inference=fcfg.n_inference, n_batch=fcfg.n_batch,
            controls=fcfg.controls)
        with obs.span("make_tenants"):
            tenants = make_tenants(scfg, topo)
        for t in tenants:
            t.p.topology_sensitive = False
        cap = 1 << max(11, (2 * fcfg.b_max - 1).bit_length())
        with obs.span("market_build"):
            market = BatchMarket(topo, fcfg.controls, capacity=cap,
                                 n_tenants=len(tenants) + 1, k=fcfg.k,
                                 use_pallas=fcfg.use_pallas,
                                 interpret=fcfg.interpret)
        fleet = Fleet(FleetConfig(n=len(tenants), b_max=fcfg.b_max,
                                  per_tenant_bids=fcfg.per_tenant_bids),
                      market.engines["H100"].tree)
        params = params_from_tenants(tenants, fcfg.duration_s)
    return topo, tenants, market, fleet, params


def _seed_floors(market, topo) -> None:
    for rtype, root in topo.roots.items():
        market.set_floor(root, ON_DEMAND.get(rtype, 2.0) * 0.7)


def _drive_fleet(fleet, params, market, fcfg: FleetScenarioConfig,
                 rtype: str = "H100", time_epochs: bool = True):
    """The UNFUSED multi-tenant fleet loop: per epoch, one jitted
    policy, one jitted engine step, one jitted transfer/advance
    application — six dispatches with host gaps between them.  Kept as
    the bit-identity reference for the fused megastep
    (``_drive_fleet_fused`` / sim/epoch.py); ``run_fleet_scenario``
    uses the fused driver by default.

    ``time_epochs=False`` skips the per-epoch device sync (epochs
    still serialize on step_arrays' host-side stats, but the fleet
    advance pipeline stays async) and returns an empty timing list.
    """
    import jax
    import jax.numpy as jnp
    injector = _make_injector(fcfg)
    state = fleet.init_state(params)
    epoch_s: List[float] = []
    clipped = jnp.zeros((), jnp.int32)   # device accumulator — no
    t = 0.0                              # per-epoch int() host sync
    while t <= fcfg.duration_s:
        t0 = time.perf_counter()
        if injector is not None:
            injector.apply_market(market, rtype, t)
        owner_b, rate, floors = market.leaf_view(rtype)
        limits, relinq, sel, bids, state, info = fleet.policy(
            params, state, t, owner_b, rate, floors)
        market.cancel_all(rtype)
        # ``sel`` (the per-leaf graceful-release mask) IS the explicit
        # set — passed as a device mask, not a rebuilt host set()
        market.step_arrays(rtype, t, bids=bids, relinquish=relinq,
                           limits=limits, explicit=sel)
        owner_a = market.leaf_view(rtype)[0]
        state, held = fleet.after_step(params, state, t, owner_b,
                                       owner_a, sel)
        state = fleet.advance(params, state, t, held)
        clipped = clipped + info["bids_clipped"]
        if time_epochs:
            jax.block_until_ready(state["progress"])
            epoch_s.append(time.perf_counter() - t0)
        t += fcfg.tick_s
    jax.block_until_ready(state["progress"])
    return state, epoch_s, int(clipped)


def _drive_fleet_fused(fleet, params, market,
                       fcfg: FleetScenarioConfig, rtype: str = "H100",
                       time_epochs: bool = True):
    """The fused-megastep fleet loop: ONE donated jitted dispatch per
    epoch (sim/epoch.py) — bit-identical owners/rates/bills/retention
    to ``_drive_fleet`` (pinned by tests/test_epoch.py)."""
    from repro.sim.epoch import EpochRunner
    runner = EpochRunner(market, fleet, rtype)
    state = fleet.init_state(params)
    state, epoch_s, stats = runner.drive(
        params, state, fcfg.duration_s, fcfg.tick_s,
        time_epochs=time_epochs, injector=_make_injector(fcfg))
    return state, epoch_s, stats["bids_clipped"]


def _make_injector(fcfg: FleetScenarioConfig):
    """A FRESH injector per drive — consumption pointers are run-local,
    so alone runs / reruns replay the identical schedule."""
    if not fcfg.faults:
        return None
    from repro.sim.faults import FaultInjector
    return FaultInjector(fcfg.faults)


# The denominator is CLOUD-INDEPENDENT (the uncontended counterfactual
# — docs/DESIGN.md §13), so the four clouds benchmarked at the same
# pool size share one computation.  Keyed on the config repr minus
# ``fused`` (the alone paths are analytic or the unfused loop; the
# flag never reaches them), which at 10k saves ~5 recomputations of
# the sampled engine-alone sweep per benchmark run.
_ALONE_CACHE: Dict[str, np.ndarray] = {}


def _alone_perf(fleet, params, market, topo,
                fcfg: FleetScenarioConfig) -> np.ndarray:
    """Retention denominator — see FleetScenarioConfig.alone."""
    n = fcfg.n_tenants
    if fcfg.alone == "none":
        return np.ones(n, np.float32)
    key = repr(replace(fcfg, fused=True))
    cached = _ALONE_CACHE.get(key)
    if cached is not None:
        return cached.copy()
    if fcfg.alone == "analytic":
        out = _alone_analytic(fleet, params, fcfg)
    elif fcfg.alone == "engine_sampled":
        out = _alone_engine_sampled(fleet, params, market, topo, fcfg)
    else:
        assert fcfg.alone == "engine", fcfg.alone
        out = np.ones(n, np.float32)
        for i in range(n):
            out[i] = _alone_engine_one(fleet, params, market, topo,
                                       fcfg, i)
    _ALONE_CACHE[key] = out.copy()
    return out


def _alone_analytic(fleet, params, fcfg: FleetScenarioConfig
                    ) -> np.ndarray:
    """Uncontended counterfactual, one vectorized run: grant desired
    instantly (``resize_to_desired``), advance."""
    import jax.numpy as jnp
    n = fcfg.n_tenants
    state = fleet.init_state(params)
    held = jnp.zeros((n,), jnp.int32)
    t = 0.0
    while t <= fcfg.duration_s:
        state, held = fleet.resize_to_desired(params, state, t, held)
        state = fleet.advance(params, state, t, held)
        t += fcfg.tick_s
    return np.asarray(fleet.performance(params, state, fcfg.duration_s))


def _alone_engine_one(fleet, params, market, topo,
                      fcfg: FleetScenarioConfig, i: int) -> float:
    """One tenant's alone performance through the real engine loop
    (unfused — jitted traces are reused across tenants via the
    shape-preserving ``params_alone`` masking)."""
    from repro.sim.fleet import params_alone
    market.reset()
    _seed_floors(market, topo)
    p_i = params_alone(params, i)
    state, _, _ = _drive_fleet(fleet, p_i, market, fcfg,
                               time_epochs=False)
    return float(fleet.performance(p_i, state, fcfg.duration_s)[i])


def _alone_engine_sampled(fleet, params, market, topo,
                          fcfg: FleetScenarioConfig) -> np.ndarray:
    """Sampled engine-alone denominator for fleet scale: run the REAL
    engine alone loop for an evenly-spaced per-kind sample of tenants,
    then correct the analytic counterfactual for every unsampled tenant
    by its kind's mean engine/analytic ratio.  Exact for sampled
    tenants; at ``alone_sample >= tenants per kind`` this degenerates to
    ``alone="engine"`` (pinned at toy scale by
    tests/test_fig06_calibration.py)."""
    n = fcfg.n_tenants
    analytic = _alone_analytic(fleet, params, fcfg)
    kinds = np.asarray(params["kind"])
    out = analytic.copy()
    for kind in np.unique(kinds):
        idx = np.nonzero(kinds == kind)[0]
        k = min(max(fcfg.alone_sample, 1), len(idx))
        sampled = idx[np.unique(np.linspace(0, len(idx) - 1, k)
                                .round().astype(int))]
        ratios = []
        for i in sampled:
            engine_i = _alone_engine_one(fleet, params, market, topo,
                                         fcfg, int(i))
            ratios.append(engine_i / max(float(analytic[i]), 1e-9))
            out[i] = engine_i
        ratio = float(np.mean(ratios)) if ratios else 1.0
        rest = np.setdiff1d(idx, sampled)
        out[rest] = analytic[rest] * ratio
    return out


def run_fleet_scenario(fcfg: FleetScenarioConfig) -> FleetRunResult:
    """Multi-tenant fleet run (+ alone denominator) => paper-scale
    retention under contention, with per-epoch wall times."""
    topo, tenants, market, fleet, params = make_fleet(fcfg)
    _seed_floors(market, topo)
    drive = _drive_fleet_fused if fcfg.fused else _drive_fleet
    state, epoch_s, clipped = drive(fleet, params, market, fcfg)
    perf = np.asarray(fleet.performance(params, state, fcfg.duration_s))
    # snapshot BEFORE the alone runs: alone="engine" resets the market
    # per tenant, so reading stats afterwards would report the last
    # single-tenant run instead of the multi-tenant one
    stats = dict(market.stats)
    stats["bids_clipped"] = clipped
    alone = _alone_perf(fleet, params, market, topo, fcfg)
    retention = np.minimum(1.5, perf / np.maximum(alone, 1e-9))
    return FleetRunResult(perf=perf, alone_perf=alone,
                          retention=retention, epoch_s=epoch_s,
                          stats=stats)
