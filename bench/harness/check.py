"""The comparison that decides ``correct``.

Each checked fit's Z is held against the float64 reference by one number,
``max_abs_err``: the widest gap of any entry, |Z - Z_ref|, over N x K.  It
catches an answer altered in one place as well as a contraction computed a
step less exactly everywhere.  Its limit is in the cell's
``bench/limits/<workload>.json``.  A Z of the wrong shape, or with a NaN or
an inf in it, reads inf.

The reference is compared one block of rows at a time (``block_gaps``),
so that only one block of it is in memory; the widest gap over the blocks
is the widest gap over the whole, bit for bit.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

NUMBERS = ("max_abs_err",)
INF = {name: float("inf") for name in NUMBERS}


def gaps(z: np.ndarray, z_ref: np.ndarray) -> dict:
    z = np.asarray(z)
    if z.shape != z_ref.shape or not np.all(np.isfinite(z)):
        return dict(INF)
    d = np.abs(z.astype(np.float64) - z_ref)
    return {"max_abs_err": float(d.max(initial=0.0))}


def block_gaps(z_rows: Callable, ref_rows: Callable, blocks: list) -> dict:
    """``gaps`` over all rows, taken block by block: ``z_rows(lo, hi)`` and
    ``ref_rows(lo, hi)`` give rows [lo, hi) of the answer and of the
    reference, ``blocks`` the [lo, hi) that cover them."""
    worst = dict.fromkeys(NUMBERS, 0.0)
    for lo, hi in blocks:
        g = gaps(z_rows(lo, hi), ref_rows(lo, hi))
        worst = {name: max(worst[name], g[name]) for name in NUMBERS}
    return worst


def answer_gaps(z: np.ndarray, ref_rows: Callable, blocks: list) -> dict:
    """``block_gaps`` of one answer Z, whose row count is checked first."""
    z = np.asarray(z)
    if z.ndim != 2 or z.shape[0] != blocks[-1][1]:
        return dict(INF)
    return block_gaps(lambda lo, hi: z[lo:hi], ref_rows, blocks)


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
