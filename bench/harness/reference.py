"""The plain reference: GEE in float64 numpy, a bincount over the directed
edge list.  It imports nothing of the program and takes nothing the program
made; only the edge arrays and the labels, which the benchmark made itself.

Copied from the bring-up smoke test's ``numpy_gee`` with the graph-only
part (degrees, ``D^-1/2 A D^-1/2`` edge weights) hoisted out, so that the
many label vectors of one run share it.  The arithmetic is unchanged.

The controls are the same reference one precision below what the path
under test states, with the epilogue in float32:

  "high"      every contraction term carried as a pair of bfloat16 numbers,
              which is what a one-hot contraction at ``Precision.HIGH``
              (three bf16 passes) keeps of a float32 term: the step below
              the Pallas kernels' ``Precision.HIGHEST``;
  "bfloat16"  every term rounded to bfloat16: the step below the float32
              segment-sum folds, which run no matmul.

The sums themselves are taken in float64, so a control is, if anything,
closer to the reference than a real lower-precision program would be.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    """float32 ``x`` as the control keeps it."""
    x = x.astype(np.float32)
    if precision == "bfloat16":
        return _bf16(x)
    hi = _bf16(x)                                   # "high"
    return hi + _bf16(x - hi)


class Reference:
    """GEE of one fixed graph under any labels vector.

    ``src``/``dst`` hold every directed edge (both directions of each
    undirected one), all of weight 1.
    """

    def __init__(self, src: np.ndarray, dst: np.ndarray, num_nodes: int,
                 options: dict):
        self.n = int(num_nodes)
        self.opts = options
        self.src = src.astype(np.int64)
        self.dst = dst.astype(np.int64)
        deg = np.bincount(self.src, minlength=self.n).astype(np.float64)
        if options["diag_aug"]:
            deg += 1.0                                    # the self loop
        if options["laplacian"]:
            self.dinv = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-300)),
                                 0.0)
            self.w_hat = self.dinv[self.src] * self.dinv[self.dst]
        else:
            self.dinv = np.ones(self.n)
            self.w_hat = np.ones(self.src.shape[0])

    def embed(self, labels: np.ndarray, num_classes: int,
              precision: str = "float64") -> np.ndarray:
        """[N, K] embedding (float64, or the control's rounding)."""
        n, k = self.n, int(num_classes)
        labels = np.asarray(labels, np.int64)
        known = labels >= 0
        counts = np.bincount(labels[known], minlength=k).astype(np.float64)
        winv = np.where(counts > 0, 1.0 / np.maximum(counts, 1.0), 0.0)
        yd = labels[self.dst]
        keep = yd >= 0
        contrib = self.w_hat[keep] * winv[yd[keep]]
        control = precision != "float64"
        if control:
            if precision not in ("high", "bfloat16"):
                raise ValueError(f"unknown precision {precision!r}")
            contrib = _round(contrib, precision)
        z = np.bincount(self.src[keep] * k + yd[keep], weights=contrib,
                        minlength=n * k).reshape(n, k)
        if control:
            z = z.astype(np.float32)
        if self.opts["diag_aug"]:
            rows = np.flatnonzero(known)
            z[rows, labels[rows]] += (self.dinv[rows] ** 2
                                      * winv[labels[rows]]).astype(z.dtype)
        if self.opts["correlation"]:
            norm = np.sqrt((z * z).sum(axis=1, keepdims=True))
            z = np.divide(z, norm, out=np.zeros_like(z), where=norm > 0)
        return z
