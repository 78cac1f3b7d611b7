"""Tenant mix of a deployment, generated from ``--seed``.

A configuration fixes its tenant population: ``tenant_table`` draws it
from the configuration's ``population_seed`` with the same draws, in
the same order, as the repository's scenario generator
(``repro.sim.simulator.make_tenants`` with
``repro.sim.traces.llm_request_rate`` and the fleet's
``params_from_tenants``), copied here so that the benchmark owns its
inputs: the program under test only receives the arrays.
``make_params`` hands the program that population in an order drawn
from ``--seed``.  Every seed thus brings the same sizes, deadlines and
arrivals, and the same amount of work, while the tenant order (the
bids' arrival order, equal-price tie-breaks and so which leaf goes to
whom) changes with the seed.

Three tenant kinds share a regime-scaled demand target
(``oversubscription`` x the fleet's leaves):

* training: gang jobs with a deadline and checkpoints, arriving in the
  first 20% of the horizon;
* inference: a bursty diurnal request rate (log-normal residuals and
  occasional 2-4x spikes on a 10 s grid), arriving in the first 10%;
* batch: long-reconfiguration jobs arriving in the first 30%.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

KIND_TRAIN, KIND_INFER, KIND_BATCH = 0, 1, 2
RATE_TICK_S = 10.0


def _request_rate(seed: int, duration_s: float, base_rps: float,
                  tick_s: float = RATE_TICK_S) -> np.ndarray:
    """One inference tenant's request rate on its ``tick_s`` grid."""
    rng = np.random.default_rng(seed)
    n = int(duration_s / tick_s) + 2
    t = np.arange(n) * tick_s
    diurnal = 1.0 + 0.4 * np.sin(2 * math.pi * t / 86400.0
                                 + rng.uniform(0, 2 * math.pi))
    bursts = rng.lognormal(mean=0.0, sigma=0.35, size=n)
    spikes = np.ones(n)
    for i in range(n):
        if rng.random() < tick_s / 1200.0:
            spikes[i:i + int(120 / tick_s)] *= rng.uniform(2.0, 4.0)
    return base_rps * diurnal * bursts * spikes


def make_params(config: dict, horizon_s: float, seed: int
                ) -> Dict[str, np.ndarray]:
    """The configuration's tenant population in the order ``seed``
    draws."""
    table = tenant_table(config, horizon_s, int(config["population_seed"]))
    perm = np.random.default_rng(seed).permutation(len(table["kind"]))
    return {k: v[perm] for k, v in table.items()}


def tenant_table(config: dict, horizon_s: float, seed: int
                 ) -> Dict[str, np.ndarray]:
    """Per-tenant parameter arrays (training, then inference, then
    batch tenants) and the ``(n, n_rate_ticks)`` inference-rate grid,
    as float32 / int32 numpy arrays.

    A copy of ``make_tenants`` + ``llm_request_rate`` +
    ``params_from_tenants``, kept so that an edit to the program's
    generator cannot change what the benchmark feeds it: the inputs are
    part of the yardstick.  ``test_generator_matches_the_programs_
    tenant_mix`` shows the two agree today."""
    rng = np.random.default_rng(seed)
    n_leaves = int(config["n_leaves"])
    n_tr, n_in, n_ba = (int(config[k]) for k in
                        ("n_training", "n_inference", "n_batch"))
    n = n_tr + n_in + n_ba
    share = n_leaves * float(config["oversubscription"]) / max(n, 1)
    n_ticks = int(horizon_s / RATE_TICK_S) + 2
    cols = {k: [] for k in ("kind", "work", "deadline_s",
                            "checkpoint_interval_s", "reconfig_s",
                            "max_nodes", "cap_per_node",
                            "sla_value_per_h", "value_per_gap",
                            "arrival_s", "overhead_mult")}
    rates = np.zeros((n, n_ticks), np.float32)

    def add(kind, work, deadline, ckpt, reconf, nodes, sla, vpg,
            arrival):
        for k, v in zip(cols, (kind, work, deadline, ckpt, reconf,
                               nodes * 2, 10.0, sla, vpg, arrival, 1.0)):
            cols[k].append(v)

    for _ in range(n_tr):
        nodes = max(1, int(round(share * rng.uniform(0.7, 1.3))))
        dl = horizon_s * rng.uniform(0.7, 1.0)
        work = nodes * (dl / 3600.0) * 0.7
        ckpt = rng.uniform(180, 420)
        reconf = rng.uniform(60, 240)
        vpg = rng.uniform(15, 40)
        add(KIND_TRAIN, work, dl, ckpt, reconf, nodes, 40.0, vpg,
            rng.uniform(0, horizon_s * 0.2))
    for i in range(n_in):
        nodes = max(1, int(round(share * rng.uniform(0.7, 1.3))))
        rates[n_tr + i] = _request_rate(seed * 101 + i, horizon_s,
                                        base_rps=nodes * 10.0 * 0.6)
        sla = rng.uniform(30, 80)
        add(KIND_INFER, 0.0, horizon_s, 300.0, 60.0, nodes, sla, 20.0,
            rng.uniform(0, horizon_s * 0.1))
    for _ in range(n_ba):
        nodes = max(1, int(round(share * rng.uniform(0.7, 1.3))))
        dl = horizon_s * rng.uniform(0.8, 1.0)
        work = nodes * (dl / 3600.0) * 0.6
        reconf = rng.uniform(240, 720)
        vpg = rng.uniform(8, 20)
        add(KIND_BATCH, work, dl, 600.0, reconf, nodes, 40.0, vpg,
            rng.uniform(0, horizon_s * 0.3))
    out = {k: np.asarray(v, np.int32 if k in ("kind", "max_nodes")
                         else np.float32) for k, v in cols.items()}
    out["rates"] = rates
    return out
