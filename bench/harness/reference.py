"""The plain reference: GEE in float64 numpy, a bincount over the directed
edge list.  It imports nothing of the program and takes nothing the program
made; only the edge arrays and the labels, which the benchmark made itself.

Copied from the bring-up smoke test's ``numpy_gee`` with the graph-only
part (degrees, ``D^-1/2 A D^-1/2`` edge weights) hoisted out, so that the
many label vectors of one run share it.  The arithmetic is unchanged.

It works one block of rows at a time (``embed_rows``), so that a check
holds one block of the float64 reference, and never all N x K of it: a
row's entry is the bincount of that row's edges alone, taken in their
original order, so a block's rows are bit for bit the rows of the whole
array.  ``blocks`` groups the edges by block once, so that each block's
edges are a contiguous range.

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

# float64 bytes of the reference that one block holds
BLOCK_BYTES = 256 << 20


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
        ids = np.int32 if self.n < 2**31 else np.int64
        self.src = src.astype(ids, copy=False)
        self.dst = dst.astype(ids, copy=False)
        self._ranges = {}               # (lo, hi) -> its edges' [e0, e1)
        deg = np.bincount(self.src, minlength=self.n).astype(np.float64)
        if options["diag_aug"]:
            deg += 1.0                                    # the self loop
        if options["laplacian"]:
            self.dinv = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-300)),
                                 0.0)
        else:
            self.dinv = np.ones(self.n)

    def blocks(self, num_classes: int, rows: int | None = None) -> list:
        """[lo, hi) of consecutive blocks of ``rows`` rows covering all N
        (by default as many as ``BLOCK_BYTES`` of float64 hold, and never
        more than N), with the edges grouped by block, each block's edges
        in their original order."""
        if rows is None:
            rows = BLOCK_BYTES // (8 * int(num_classes))
        rows = max(1, min(int(rows), self.n))
        bounds = [(lo, min(lo + rows, self.n))
                  for lo in range(0, self.n, rows)]
        self._ranges = {}
        if len(bounds) > 1:
            block = self.src // rows
            block = block.astype(np.min_scalar_type(len(bounds)))
            order = np.argsort(block, kind="stable")
            counts = np.bincount(block, minlength=len(bounds))
            del block
            self.src = self.src[order]
            self.dst = self.dst[order]
            del order
            ends = np.cumsum(counts)
            self._ranges = {b: (int(e - c), int(e))
                            for b, e, c in zip(bounds, ends, counts)}
        return bounds

    def _edges(self, lo: int, hi: int):
        """The edges whose source lies in rows [lo, hi), each row's in
        their original order."""
        if (lo, hi) in self._ranges:
            return slice(*self._ranges[lo, hi])
        if lo <= 0 and hi >= self.n:
            return slice(None)
        return np.flatnonzero((self.src >= lo) & (self.src < hi))

    def embed(self, labels: np.ndarray, num_classes: int,
              precision: str = "float64") -> np.ndarray:
        """[N, K] embedding (float64, or the control's rounding)."""
        return self.embed_rows(labels, num_classes, 0, self.n, precision)

    def embed_rows(self, labels: np.ndarray, num_classes: int, lo: int,
                   hi: int, precision: str = "float64") -> np.ndarray:
        """Rows [lo, hi) of the [N, K] embedding, bit for bit."""
        k = int(num_classes)
        labels = np.asarray(labels)
        known = labels >= 0
        counts = np.bincount(labels[known], minlength=k).astype(np.float64)
        winv = np.where(counts > 0, 1.0 / np.maximum(counts, 1.0), 0.0)
        sel = self._edges(lo, hi)
        src, dst = self.src[sel], self.dst[sel]
        yd = labels[dst]
        keep = yd >= 0
        src, dst, yd = src[keep], dst[keep], yd[keep]
        w_hat = self.dinv[src] * self.dinv[dst]
        contrib = w_hat * winv[yd]
        control = precision != "float64"
        if control:
            if precision not in ("high", "bfloat16"):
                raise ValueError(f"unknown precision {precision!r}")
            contrib = _round(contrib, precision)
        # float64 also where the block has no edge (bincount's int zeros)
        z = np.bincount((src - lo).astype(np.int64) * k + yd,
                        weights=contrib, minlength=(hi - lo) * k
                        ).astype(np.float64, copy=False).reshape(hi - lo, k)
        if control:
            z = z.astype(np.float32)
        if self.opts["diag_aug"]:
            rows = lo + np.flatnonzero(known[lo:hi])
            z[rows - lo, labels[rows]] += (self.dinv[rows] ** 2
                                           * winv[labels[rows]]
                                           ).astype(z.dtype)
        if self.opts["correlation"]:
            norm = np.sqrt((z * z).sum(axis=1, keepdims=True))
            z = np.divide(z, norm, out=np.zeros_like(z), where=norm > 0)
        return z
