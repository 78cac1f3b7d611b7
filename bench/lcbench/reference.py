"""Plain reference of the fleet renegotiation epoch, in numpy.

It imports nothing of the program.  One epoch at time ``t``, as the
configuration's guarantees state it:

1. **Fleet policy.**  Every tenant reads the pre-epoch owners, charged
   rates and floors.  It publishes Listing-1 retention limits on the
   leaves it keeps, releases surplus leaves (highest rate first, leaf
   index breaking ties) once 120 s have passed since its last release,
   and bids for up to ``per_tenant_bids`` more leaves at the type root
   ("anywhere"), at its Listing-1 grow price against the cheapest path
   floor.  Bids are emitted in tenant order, at most ``b_max``.
2. **Market.**  Every resting order is cancelled.  Owners accrue
   ``rate * dt`` into their bills.  Retention-limit crossings whose
   holding window (``min_holding_s``) has passed are evicted.  The
   limits are refreshed.  Each bid is clipped to ``max_bid_multiple``
   times its scope's reference price (the path floor, the top of its
   book, the highest charged rate under it) and keeps
   ``max(clipped, limit)`` as the limit it brings to a leaf it wins.
   Then the book clears to a fixpoint, one transfer decision at a time:

   * a leaf's charged rate is the larger of its path floor and the best
     resting bid not from its owner (price desc, arrival asc);
   * while any idle leaf has a bid that meets its floor, only idle
     leaves trade: the best bid goes to the lowest such leaf;
   * otherwise every evicted (rate above limit, holding window passed)
     or released leaf takes its best non-owner bid meeting its floor,
     the lowest leaf first where two want the same order; one with no
     such bid goes back to the operator;
   * a bid that wins a leaf is consumed; it wins nothing else;
   * a released leaf that has changed hands once is released no more.

3. **Fleet.**  Grants and revocations open reconfiguration windows and
   waste the work since the last checkpoint on forced revocations;
   then one tick of workload dynamics (progress, served and demanded
   load, the planner's smoothed rate, checkpoints, completion).

All arithmetic is in float32, as the configuration states, with ``q``
rounding every stored value: the identity here, bfloat16 for the
control (``Reference(..., precision="bfloat16")``).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

NEG = np.float32(-1e30)
EPS = np.float32(1e-6)
OPERATOR = -1
KIND_INFER = 1
SLA_CREDITS = 0.10 + 0.25
STAT_KEYS = ("orders", "transfers", "explicit_relinquish",
             "implicit_relinquish", "bids_clipped", "revoked_by_fault")
FLEET_KEYS = ("progress", "served", "demanded", "rate_ewma",
              "reconfig_until", "last_checkpoint", "last_t",
              "last_scale_down", "done_at", "cold_cnt", "cold_until")


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes
        bf = ml_dtypes.bfloat16
        return lambda x: np.asarray(x, np.float32).astype(bf).astype(
            np.float32)
    raise ValueError(precision)


def strides_of(config: dict) -> List[int]:
    """Leaf -> root subtree sizes: instance, host, rack, zone, root."""
    h = int(config["gpus_per_host"])
    r = h * int(config["hosts_per_rack"])
    z = r * int(config["racks_per_zone"])
    return [1, h, r, z, int(config["n_leaves"])]


def rank_in_group(group: np.ndarray, *tie_keys: np.ndarray) -> np.ndarray:
    """Rank of each element within its group, ordered by ``tie_keys``
    (major key last)."""
    order = np.lexsort((*tie_keys, group))
    sg = group[order]
    first = np.searchsorted(sg, sg, side="left")
    out = np.empty(len(group), np.int64)
    out[order] = np.arange(len(group)) - first
    return out


class Reference:
    """The scenario from t = 0, one ``epoch`` call per tick."""

    def __init__(self, config: dict, params: Dict[str, np.ndarray],
                 precision: str = "float32") -> None:
        self.cfg = config
        self.q = _rounder(precision)
        q = self.q
        self.p = {k: (q(v) if v.dtype.kind == "f" else v)
                  for k, v in params.items()}
        ctl = config["controls"]
        self.mult = float(ctl["max_bid_multiple"])
        self.min_hold = np.float32(ctl["min_holding_s"])
        self.strides = strides_of(config)
        self.n_leaves = int(config["n_leaves"])
        self.n = int(self.p["kind"].shape[0])
        self.n_ten = self.n + 1           # the market's bill vector
        self.b_max = int(config["b_max"])
        self.per_bids = int(config["per_tenant_bids"])
        self.leaf = np.arange(self.n_leaves)
        L = len(self.strides)
        floors = [np.zeros(-(-self.n_leaves // s), np.float32)
                  for s in self.strides]
        floors[L - 1][0] = np.float32(float(config["on_demand_per_h"])
                                      * float(config["floor_fraction"]))
        self.floors = [q(f) for f in floors]
        fl = np.zeros(self.n_leaves, np.float32)
        for d, s in enumerate(self.strides):
            fl = np.maximum(fl, self.floors[d][self.leaf // s])
        self.floor_leaf = fl
        self.uniform_floor = bool(np.all(fl == fl[0]))
        arr = self.p["arrival_s"]
        z = np.zeros(self.n, np.float32)
        self.fleet = {
            "progress": z.copy(), "served": z.copy(),
            "demanded": z.copy(), "rate_ewma": z.copy(),
            "reconfig_until": np.full(self.n, -1.0, np.float32),
            "last_checkpoint": arr.copy(), "last_t": arr.copy(),
            "last_scale_down": arr.copy(),
            "done_at": np.full(self.n, np.inf, np.float32),
            "cold_cnt": z.copy(),
            "cold_until": np.full(self.n, -1.0, np.float32)}
        self.owner = np.full(self.n_leaves, OPERATOR, np.int32)
        self.limit = np.full(self.n_leaves, np.inf, np.float32)
        self.acq = np.zeros(self.n_leaves, np.float32)
        self.rate = fl.copy()
        self.bills = np.zeros(self.n_ten, np.float32)
        self.t = np.float32(0.0)
        self.stats = {k: 0 for k in STAT_KEYS}
        self._empty_book()

    # ------------------------------------------------------------ book
    def _empty_book(self) -> None:
        self.bp = np.zeros(0, np.float32)      # price
        self.bl = np.zeros(0, np.float32)      # limit kept on a win
        self.bt = np.zeros(0, np.int32)        # tenant
        self.alive = np.zeros(0, bool)

    def _price_at(self, idx):
        """Book price per leaf of order ``idx`` (NEG where -1)."""
        if not len(self.bp):
            return np.full(idx.shape, NEG, np.float32)
        return np.where(idx >= 0, self.bp[np.maximum(idx, 0)], NEG)

    def book(self):
        """Resting orders, best first: (tenant, price, limit)."""
        a = self.alive
        return self.bt[a], self.bp[a], self.bl[a]

    # ---------------------------------------------------------- fleet
    def _lam(self, t):
        rates = self.p["rates"]
        T = rates.shape[1]
        idx = np.clip((np.float32(t) / np.float32(10.0)).astype(np.int32)
                      if np.ndim(t) else
                      np.int32(np.float32(t) / np.float32(10.0)), 0, T - 1)
        idx = np.broadcast_to(idx, (self.n,))
        return rates[np.arange(self.n), idx]

    def _desired(self, s, now):
        p, q = self.p, self.q
        done = np.isfinite(s["done_at"])
        lam = self._lam(now)
        plan = q(np.maximum(s["rate_ewma"], q(0.7 * lam)))
        want_inf = np.minimum(
            p["max_nodes"],
            np.ceil(q(plan / p["cap_per_node"])).astype(np.int32))
        remaining = q(np.maximum(q(p["work"] - s["progress"]), 0.0))
        t_left = q(np.maximum(q(q(p["arrival_s"] + p["deadline_s"])
                                - now), 1.0))
        need = q(remaining / q(t_left / 3600.0))
        want_wk = np.minimum(p["max_nodes"], np.maximum(
            0, np.ceil(need).astype(np.int32)))
        want = np.where(p["kind"] == KIND_INFER, want_inf, want_wk)
        return np.where((now < p["arrival_s"]) | done, 0, want)

    def _hooks(self, s, held):
        p, q = self.p, self.q
        heldf = held.astype(np.float32)
        lam = self._lam(s["last_t"])
        plan = q(np.maximum(s["rate_ewma"], q(0.7 * lam)))
        is_inf = p["kind"] == KIND_INFER
        mu_inf = np.where(plan > 0, q(q(np.minimum(p["cap_per_node"], plan))
                                      / np.maximum(plan, 1e-30)), 0.0)
        mu_wk = q(np.minimum(1.0, q(1.0 / np.maximum(
            q(p["work"] - s["progress"]), 1e-9))))
        mu = np.where(is_inf, mu_inf, mu_wk).astype(np.float32)
        cap_rps = q(heldf * p["cap_per_node"])
        gap_inf = np.where(plan > 0, np.maximum(
            0.0, q(1.0 - q(cap_rps / np.maximum(plan, 1e-30)))), 0.0)
        t_left = q(np.maximum(q(q(p["arrival_s"] + p["deadline_s"])
                                - s["last_t"]), 1.0))
        need = q(q(np.maximum(q(p["work"] - s["progress"]), 0.0))
                 / q(t_left / 3600.0))
        gap_wk = np.maximum(0.0, q(q(need - heldf)
                                   / np.maximum(need, 1e-9)))
        gap = np.where(is_inf, gap_inf, gap_wk).astype(np.float32)
        urgency = q(1.0 + q(2.0 * gap))
        value = q(np.where(is_inf, q(p["sla_value_per_h"] * SLA_CREDITS),
                           p["value_per_gap"]).astype(np.float32)
                  * urgency)
        since = np.where(is_inf, 0.0,
                         q(s["last_t"] - s["last_checkpoint"])
                         ).astype(np.float32)
        reconf_h = q(q(p["reconfig_s"] + since) / 3600.0)
        gang = np.where(is_inf, 0.0, heldf).astype(np.float32)
        return mu, value, reconf_h, gang

    def _grow_price(self, mu, value, reconf_h, ref, gang):
        q = self.q
        vm = q(value * mu)
        burn = q(q(gang + 1.0) * q(vm + ref))
        return q(vm - q(reconf_h * burn))

    def _retention_limit(self, mu, value, reconf_h, rate, gang):
        q = self.q
        r = np.maximum(rate, np.float32(1e-6))
        vm = q(value * mu)
        burn = q(q(gang + 1.0) * q(vm + r))
        return q(vm + q(reconf_h * burn))

    def _policy(self, now):
        p, s, n = self.p, dict(self.fleet), self.n
        owner, rate_leaf = self.owner, self.rate
        owner_c = np.clip(owner, 0, n - 1)
        owned = (owner >= 0) & (owner < n)
        held = np.bincount(owner_c[owned], minlength=n).astype(np.int32)
        want = self._desired(s, now)
        mu, value, reconf_h, gang = self._hooks(s, held)
        extra = held - want
        eligible = ((now - s["last_scale_down"]) >= 120.0) & (extra > 0)
        rank = rank_in_group(np.where(owned, owner, n), self.leaf,
                             -rate_leaf)
        sel = owned & eligible[owner_c] & (rank < extra[owner_c])
        rel_cnt = np.bincount(owner_c[sel], minlength=n)
        s["last_scale_down"] = np.where(rel_cnt > 0, now,
                                        s["last_scale_down"]
                                        ).astype(np.float32)
        lim_leaf = self._retention_limit(
            mu[owner_c], value[owner_c], reconf_h[owner_c], rate_leaf,
            gang[owner_c])
        limits = np.where(owned & ~sel, lim_leaf, np.nan
                          ).astype(np.float32)
        ref = np.float32(self.floor_leaf.min())
        price = self._grow_price(mu, value, reconf_h, ref, gang)
        can_bid = (want > held) & (now >= p["arrival_s"]) \
            & ~np.isfinite(s["done_at"]) & (price > 0) \
            & (now > s["reconfig_until"])
        nb = np.where(can_bid, np.minimum(want - held, self.per_bids), 0)
        tid = np.repeat(np.arange(n), nb)
        clipped = max(len(tid) - self.b_max, 0)
        tid = tid[:self.b_max]
        return (limits, sel, tid, price[tid], s, clipped)

    def _after_step(self, now, owner_b, owner_a, sel):
        p, s, n, q = self.p, dict(self.fleet), self.n, self.q
        ob_c = np.clip(owner_b, 0, n - 1)
        oa_c = np.clip(owner_a, 0, n - 1)
        owned_b = (owner_b >= 0) & (owner_b < n)
        owned_a = (owner_a >= 0) & (owner_a < n)
        held_b = np.bincount(ob_c[owned_b], minlength=n)
        held_a = np.bincount(oa_c[owned_a], minlength=n).astype(np.int32)
        moved = owner_b != owner_a
        lost = moved & owned_b
        gain = moved & owned_a
        forced = lost & ~sel
        k_rank = rank_in_group(np.where(lost, ob_c, n), self.leaf)
        waste_s = q(np.minimum(q(now - s["last_checkpoint"]),
                               p["checkpoint_interval_s"]))
        contrib = np.where(forced, held_b[ob_c] - k_rank - 1, 0)
        lost_nodes = np.bincount(ob_c, weights=contrib,
                                 minlength=n).astype(np.float32)
        lost_work = q(q(np.maximum(waste_s, 0.0) / 3600.0) * lost_nodes)
        wk = p["kind"] != KIND_INFER
        s["progress"] = np.where(
            wk, np.maximum(0.0, q(s["progress"] - lost_work)),
            s["progress"]).astype(np.float32)
        gain_cnt = np.bincount(oa_c[gain], minlength=n)
        lost_cnt = np.bincount(ob_c[lost], minlength=n)
        touched = (gain_cnt > 0) | (lost_cnt > 0)
        done = np.isfinite(s["done_at"])
        is_inf = p["kind"] == KIND_INFER
        restart = q(now + q(p["reconfig_s"] * p["overhead_mult"]))
        s["reconfig_until"] = np.where(
            touched & ~done & ~is_inf & (now > s["reconfig_until"]),
            restart, s["reconfig_until"]).astype(np.float32)
        cold0 = np.where(now >= s["cold_until"], 0.0, s["cold_cnt"])
        cold1 = q(cold0 + gain_cnt.astype(np.float32))
        s["cold_cnt"] = np.where(
            is_inf & touched, np.minimum(cold1, held_a.astype(np.float32)),
            s["cold_cnt"]).astype(np.float32)
        s["cold_until"] = np.where(is_inf & (gain_cnt > 0), restart,
                                   s["cold_until"]).astype(np.float32)
        self.fleet = s
        return held_a

    def _advance(self, now, held):
        p, s, q = self.p, dict(self.fleet), self.q
        heldf = held.astype(np.float32)
        dt = q(now - s["last_t"])
        tick = dt > 0
        done = np.isfinite(s["done_at"])
        live = tick & (now >= p["arrival_s"]) & ~done
        ru = s["reconfig_until"]
        active_dt = np.where(now <= ru, 0.0,
                             np.where(ru > q(now - dt), q(now - ru), dt)
                             ).astype(np.float32)
        lam = self._lam(now)
        inf_m = live & (p["kind"] == KIND_INFER)
        alpha = q(np.minimum(1.0, q(dt / 300.0)))
        s["rate_ewma"] = np.where(
            inf_m, q(s["rate_ewma"] + q(alpha * q(lam - s["rate_ewma"]))),
            s["rate_ewma"]).astype(np.float32)
        s["demanded"] = np.where(inf_m, q(s["demanded"] + q(lam * dt)),
                                 s["demanded"]).astype(np.float32)
        cold_frac = np.clip(q(q(now - s["cold_until"])
                              / np.maximum(dt, 1e-9)), 0.0, 1.0)
        share = np.where(heldf > 0,
                         q(s["cold_cnt"] / np.maximum(heldf, 1.0)), 0.0)
        cap_rps = q(q(heldf * p["cap_per_node"])
                    * q(1.0 - q(share * q(1.0 - cold_frac))))
        s["served"] = np.where(
            inf_m, q(s["served"] + q(np.minimum(lam, cap_rps) * dt)),
            s["served"]).astype(np.float32)
        s["cold_cnt"] = np.where(inf_m & (now >= s["cold_until"]), 0.0,
                                 s["cold_cnt"]).astype(np.float32)
        wk = live & (p["kind"] != KIND_INFER)
        s["progress"] = np.where(
            wk, q(s["progress"] + q(q(heldf * active_dt) / 3600.0)),
            s["progress"]).astype(np.float32)
        s["last_checkpoint"] = np.where(
            wk & (q(now - s["last_checkpoint"])
                  >= p["checkpoint_interval_s"]),
            now, s["last_checkpoint"]).astype(np.float32)
        s["done_at"] = np.where(wk & (s["progress"] >= p["work"]), now,
                                s["done_at"]).astype(np.float32)
        s["last_t"] = np.where(tick, now, s["last_t"]).astype(np.float32)
        self.fleet = s

    # --------------------------------------------------------- market
    def _clear(self, t, release):
        """Clear to a fixpoint (module docstring, step 2)."""
        q, fl = self.q, self.floor_leaf
        while True:
            alive = self.alive
            owner = self.owner
            i1 = int(np.argmax(alive)) if alive.any() else -1
            best = np.full(self.n_leaves, -1, np.int64)
            if i1 >= 0:
                other = alive & (self.bt != self.bt[i1])
                i2 = int(np.argmax(other)) if other.any() else -1
                best[:] = i1
                best[owner == self.bt[i1]] = i2
            bp = self._price_at(best)
            self.rate = np.maximum(fl, np.maximum(bp, 0.0)
                                   ).astype(np.float32)
            has = (best >= 0) & (bp >= q(fl - EPS))
            evict = (owner >= 0) & (self.rate > q(self.limit + EPS))
            if self.min_hold > 0:
                evict &= q(t - self.acq) >= self.min_hold
            sell = (owner < 0) & has
            if sell.any() and self.uniform_floor:
                # every idle leaf sees the same best bid: the bids that
                # meet the floor go to the idle leaves in order, which
                # is the one-decision-per-step sequence taken at once
                idle = np.nonzero(owner < 0)[0]
                ok = np.nonzero(alive & (self.bp >= q(fl[0] - EPS)))[0]
                m = min(len(idle), len(ok))
                self.owner[idle[:m]] = self.bt[ok[:m]]
                self.limit[idle[:m]] = self.bl[ok[:m]]
                self.acq[idle[:m]] = t
                self.alive[ok[:m]] = False
                release = release.copy()
                release[idle[:m]] = False
                continue
            if sell.any():
                unresolved, releasing = sell, np.zeros_like(sell)
                evict = np.zeros_like(sell)
            else:
                releasing = release & (owner >= 0)
                unresolved = evict | releasing
            if not unresolved.any():
                return
            lim_fl = fl > q(self.limit + EPS)
            prop = np.where(unresolved & has, best, -1)
            # an evicted leaf whose best bid no longer exceeds its
            # limit stays put (pressure consumed elsewhere)
            still = lim_fl | (self._price_at(prop) > q(self.limit + EPS))
            lapsed = evict & ~releasing & (prop >= 0) & ~still
            act_leaves = np.nonzero(unresolved & ~lapsed & (prop >= 0))[0]
            bids, first = np.unique(prop[act_leaves], return_index=True)
            win = act_leaves[first]                   # lowest leaf
            recl = unresolved & ~lapsed & (prop < 0) & (
                releasing | (evict & lim_fl))
            self.owner[win] = self.bt[bids]
            self.limit[win] = self.bl[bids]
            self.acq[win] = t
            self.alive[bids] = False
            self.owner[recl] = OPERATOR
            self.limit[recl] = np.inf
            self.acq[recl] = t
            moved = recl.copy()
            moved[win] = True
            if not moved.any():
                return
            release = release & ~moved

    def _clip(self, price, limit):
        """Admission clip at the root scope: the book is empty after
        the cancel, so the reference price is the larger of the root's
        path floor and the highest charged rate under it."""
        if self.mult <= 0:
            return price
        ref = max(float(self.floors[-1][0]), float(self.rate.max()), 0.0)
        ref = np.float32(ref)
        if ref <= 0:
            return price
        return np.minimum(price, self.q(ref * np.float32(self.mult)))

    def epoch(self, t: float) -> None:
        q = self.q
        now = np.float32(t)
        owner_b = self.owner.copy()
        limits, sel, tid, price, fleet_s, clipped = self._policy(now)
        self.fleet = fleet_s
        self._empty_book()                                  # cancel all
        dt_h = q(np.maximum(q(now - self.t), 0.0) / 3600.0)
        own = self.owner >= 0
        np.add.at(self.bills, self.owner[own],
                  q(self.rate[own] * dt_h))
        self.bills = q(self.bills)
        self.t = now
        no_release = np.zeros(self.n_leaves, bool)
        if self.min_hold > 0:
            self._clear(now, no_release)
        upd = ~np.isnan(limits) & (self.owner >= 0)
        self.limit = np.where(upd, limits, self.limit).astype(np.float32)
        clipped_price = self._clip(price, price)
        order = np.lexsort((np.arange(len(tid)), -clipped_price))
        self.bp = clipped_price[order].astype(np.float32)
        self.bl = np.maximum(clipped_price, price)[order].astype(
            np.float32)
        self.bt = tid[order].astype(np.int32)
        self.alive = np.ones(len(tid), bool)
        release = sel.copy()
        self._clear(now, release)
        moved = owner_b != self.owner
        taken = moved & (self.owner >= 0)
        st = self.stats
        st["orders"] += len(tid)
        st["transfers"] += int(taken.sum())
        st["explicit_relinquish"] += int((moved & sel).sum())
        st["implicit_relinquish"] += int((taken & ~sel
                                          & (owner_b >= 0)).sum())
        st["bids_clipped"] += int(clipped)
        held = self._after_step(now, owner_b, self.owner, sel)
        self._advance(now, held)

    def run(self, times) -> "Reference":
        for t in times:
            self.epoch(t)
        return self

    def state(self) -> dict:
        """The comparable end state."""
        bt, bp, bl = self.book()
        return {"owner": self.owner.copy(), "limit": self.limit.copy(),
                "acq": self.acq.copy(), "rate": self.rate.copy(),
                "bills": self.bills.copy(), "book_tenant": bt.copy(),
                "book_price": bp.copy(), "book_limit": bl.copy(),
                "stats": dict(self.stats),
                "fleet": {k: self.fleet[k].copy() for k in FLEET_KEYS}}
