"""The comparison that decides ``correct``.

Each checked fit's Z is held against the float64 reference by one number,
``max_abs_err``: the widest gap of any entry, |Z - Z_ref|, over N x K.  It
catches an answer altered in one place as well as a contraction computed a
step less exactly everywhere.  Its limit is in the cell's
``bench/limits/<workload>.json``.  A Z of the wrong shape, or with a NaN or
an inf in it, reads inf.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("max_abs_err",)


def gaps(z: np.ndarray, z_ref: np.ndarray) -> dict:
    z = np.asarray(z)
    if z.shape != z_ref.shape or not np.all(np.isfinite(z)):
        return {name: float("inf") for name in NUMBERS}
    d = np.abs(z.astype(np.float64) - z_ref)
    return {"max_abs_err": float(d.max(initial=0.0))}


def worst(readings: list[dict]) -> dict:
    """Per number, the worst reading over the checked fits."""
    return {name: max((r[name] for r in readings), default=float("inf"))
            for name in NUMBERS}


def verdict(readings: list[dict], limits: dict) -> tuple[bool, int, dict]:
    """(correct, failed fits, {number: {"value", "limit"}})."""
    failed = sum(any(not r[name] <= limits[name] for name in NUMBERS)
                 for r in readings)
    w = worst(readings)
    shown = {name: {"value": w[name], "limit": limits[name]}
             for name in NUMBERS}
    return bool(readings) and failed == 0, failed, shown
