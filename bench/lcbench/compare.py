"""The comparison that decides ``correct``.

The program's end state after the window's last replay is compared
with the plain reference's state after the same epochs (``reference``),
number by number.  Each number has a limit of its own, kept per cell in
``bench/limits/<cell>.json`` with the readings it was set from
(``PERF.md`` section 2 lists them).

* ``owners_off``: leaves whose owner or acquisition time differs.
* ``book_off``: resting orders whose tenant differs, position by
  position (price desc, arrival asc), plus any difference in length.
* ``counts_off``: summed difference of the market's counters (orders,
  transfers, explicit and implicit relinquishes, clipped bids, fault
  revocations), plus orders the program's table dropped.
* ``rate_err``, ``limit_err``, ``price_err``, ``bill_err``,
  ``fleet_err``: the widest relative difference of the charged rates,
  the owners' retention limits, the resting orders' prices and limits,
  the tenants' bills and the fleet's state arrays.  Each value is
  measured against the larger of the reference's own magnitude and a
  thousandth of the largest finite magnitude in that array, so an
  all-but-zero entry does not swamp it; an infinity or NaN that the
  other side lacks reads as infinite.
"""
from __future__ import annotations

import json
import math
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

NAMES = ("owners_off", "book_off", "counts_off", "rate_err",
         "limit_err", "price_err", "bill_err", "fleet_err")


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return math.inf
    if a.size == 0:
        return 0.0
    fa, fb = np.isfinite(a), np.isfinite(b)
    if np.any(fa != fb) or np.any(a[~fb] != b[~fb]):
        return math.inf
    if not fb.any():
        return 0.0
    scale = float(np.max(np.abs(b[fb])))
    den = np.maximum(np.abs(b[fb]), max(1e-3 * scale, 1e-30))
    return float(np.max(np.abs(a[fa] - b[fb]) / den))


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """Every compared number, program against reference."""
    owners = int(np.sum((prog["owner"] != ref["owner"])
                        | (prog["acq"] != ref["acq"])))
    n_p, n_r = len(prog["book_tenant"]), len(ref["book_tenant"])
    m = min(n_p, n_r)
    book = abs(n_p - n_r) + int(np.sum(
        prog["book_tenant"][:m] != ref["book_tenant"][:m]))
    counts = sum(abs(int(prog["stats"][k]) - int(ref["stats"][k]))
                 for k in ref["stats"]) + int(prog.get("dropped", 0))
    owned = (ref["owner"] >= 0) & (prog["owner"] == ref["owner"])
    price = max(rel_err(prog["book_price"][:m], ref["book_price"][:m]),
                rel_err(prog["book_limit"][:m], ref["book_limit"][:m]))
    fleet = max(rel_err(prog["fleet"][k], ref["fleet"][k])
                for k in ref["fleet"])
    return {"owners_off": float(owners), "book_off": float(book),
            "counts_off": float(counts),
            "rate_err": rel_err(prog["rate"], ref["rate"]),
            "limit_err": rel_err(prog["limit"][owned],
                                 ref["limit"][owned]),
            "price_err": price,
            "bill_err": rel_err(prog["bills"], ref["bills"]),
            "fleet_err": fleet}


def load_limits(bench_dir: str, cell: str,
                names: Sequence[str] = NAMES) -> Dict[str, float]:
    """The cell's limits, in ``names`` order (the cell's kit's
    ``NAMES``); the file lists exactly those numbers."""
    path = os.path.join(bench_dir, "limits", f"{cell}.json")
    with open(path) as f:
        d = json.load(f)
    if set(d) != set(names):
        raise ValueError(f"{path} lists {sorted(d)}, the kit compares "
                         f"{sorted(names)}")
    return {k: float(d[k]["limit"]) for k in names}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """``correct`` and the (name, value, limit) rows, in the limits'
    order.  A number passes when it is at or below its limit."""
    rows = [(k, values[k], lim) for k, lim in limits.items()]
    ok = all(v <= lim for _, v, lim in rows)
    return ok, rows
