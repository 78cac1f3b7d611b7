"""The system under test, built through the program's normal path.

config + traffic -> ``FleetScenarioConfig`` -> ``make_fleet`` ->
``_seed_floors`` -> ``EpochRunner``; the window drives
``EpochRunner.epoch``.  The clearing backend and the top-K width are
left at the program's defaults.  The tenant arrays are the harness's
own (``workload.make_params``); the program receives them as inputs.

A state is the triple ``(engine state, fleet state, market stats)``;
the harness touches it only through the methods here.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from lcbench.reference import FLEET_KEYS, strides_of

# the engine's counters (market_jax/engine.py) that ``counters`` hands
# the harness: cascade waves, bids the full table dropped, claim rounds,
# waves frozen for a full re-clear, rounds that re-gathered the slate
# liveness
COUNTER_KEYS = ("waves", "dropped", "rounds", "frozen", "liveness_gathers")


class Deployment:
    def __init__(self, config: dict, horizon_s: float, seed: int,
                 params_np: Dict[str, np.ndarray]) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core.market import VolatilityControls
        from repro.sim import simulator as S
        from repro.sim.epoch import STAT_KEYS, EpochRunner

        self.rtype = config["rtype"]
        self.stat_keys = STAT_KEYS
        fcfg = S.FleetScenarioConfig(
            regime=config["regime"], n_leaves=int(config["n_leaves"]),
            n_training=int(config["n_training"]),
            n_inference=int(config["n_inference"]),
            n_batch=int(config["n_batch"]), duration_s=float(horizon_s),
            tick_s=float(config["tick_s"]), seed=int(seed),
            b_max=int(config["b_max"]),
            per_tenant_bids=int(config["per_tenant_bids"]),
            controls=VolatilityControls(**config["controls"]))
        topo, _, market, fleet, _ = S.make_fleet(fcfg)
        S._seed_floors(market, topo)
        eng = market.engines[self.rtype]
        self._check_built(config, eng, fleet, params_np)
        self.runner = EpochRunner(market, fleet, self.rtype)
        self.engine = eng
        est = dict(market.states[self.rtype])
        est["floor"] = tuple(est["floor"])
        est["floor_t"] = tuple(est["floor_t"])
        self._est0 = est
        # donated buffers must not alias one another (the engine's
        # zero scalars share one buffer): start from distinct copies
        self.copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
        self.set_params(params_np)
        floors = [np.asarray(f) for f in jax.device_get(est["floor"])]
        root = np.float32(float(config["on_demand_per_h"])
                          * float(config["floor_fraction"]))
        if floors[-1][0] != root or any(f.any() for f in floors[:-1]):
            raise RuntimeError("operator floors differ from the config")

    def set_params(self, params_np: Dict[str, np.ndarray]) -> None:
        """Tenant arrays, and the initial state they give."""
        import jax.numpy as jnp
        fleet = self.runner.fleet
        if int(params_np["kind"].shape[0]) != fleet.cfg.n:
            raise RuntimeError("tenant count differs from the built fleet")
        self.params = {k: jnp.asarray(v) for k, v in params_np.items()}
        stats = {k: jnp.zeros((), jnp.int32) for k in self.stat_keys}
        self.initial = self.copy((self._est0,
                                  fleet.init_state(self.params), stats))

    @staticmethod
    def _check_built(config, eng, fleet, params_np) -> None:
        """The program must build the deployment the config states."""
        want = tuple(strides_of(config))
        if tuple(eng.tree.strides) != want:
            raise RuntimeError(f"tree strides {eng.tree.strides} != "
                               f"config {want}")
        n = int(params_np["kind"].shape[0])
        if fleet.cfg.n != n or eng.n_tenants != n + 1:
            raise RuntimeError("tenant count differs from the config")
        if fleet.cfg.b_max != int(config["b_max"]):
            raise RuntimeError("b_max differs from the config")

    def epoch(self, state, t: float):
        """One fused epoch (donates ``state``)."""
        import jax.numpy as jnp
        est, fst, stats = state
        return self.runner.epoch(self.params, est, fst, stats,
                                 jnp.float32(t))

    def orders(self, state):
        """The device scalar counting the bids placed so far."""
        return state[2]["orders"]

    def counters(self, state) -> dict:
        """The engine's device int scalars of ``COUNTER_KEYS``, each
        where the program's state has it."""
        est = state[0]
        return {k: est[k] for k in COUNTER_KEYS if k in est}

    def compiled_text(self, state, t: float) -> str:
        """HLO text of the epoch compiled for ``state`` at ``t``."""
        import jax.numpy as jnp
        est, fst, stats = state
        runner = self.runner
        lowered = type(runner).epoch.lower(runner, self.params, est, fst,
                                           stats, jnp.float32(t))
        return lowered.compile().as_text()

    def host_state(self, state) -> dict:
        """The comparable end state, on the host (``reference.state``'s
        layout)."""
        import jax
        est, fst, stats = jax.device_get(state)
        live = (est["price"] > -1e30 / 2) & (est["tenant"] >= 0)
        idx = np.nonzero(live)[0]
        order = idx[np.lexsort((est["seq"][idx], -est["price"][idx]))]
        return {"owner": est["owner"], "limit": est["limit"],
                "acq": est["acq_t"], "rate": est["rate"],
                "bills": est["bills"],
                "book_tenant": est["tenant"][order],
                "book_price": est["price"][order],
                "book_limit": est["blimit"][order],
                "stats": {k: int(v) for k, v in stats.items()},
                "dropped": int(est["dropped"]),
                "fleet": {k: np.asarray(fst[k]) for k in FLEET_KEYS}}
