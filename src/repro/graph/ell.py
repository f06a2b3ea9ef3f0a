"""ELL packing layer: edge list -> TPU-friendly fixed-width tiles.

This is the bridge between the paper's CSR pipeline and the Pallas kernel
(``repro.kernels.gee_spmm``).  CSR's variable-length rows are hostile to the
MXU, so we re-block the sparse structure into fixed-width row tiles:

  * ``edges_to_ell``          one plane, width = global max degree.  Simple,
                              but a power-law graph with one hub row of degree
                              10k pads every other row to 10k slots.
  * ``edges_to_bucketed_ell`` rows are partitioned into *degree buckets* with
                              geometrically growing widths (8, 16, 32, ...).
                              Each row lands in the narrowest bucket that fits
                              its degree, so per-row padding waste is < 2x and
                              total stored slots are <= 2E + row-tile padding
                              regardless of the degree distribution.

Both packers are O(E): grouping edges by row uses ``np.argsort(kind="stable")``
on int32 keys, which numpy implements as an LSD radix sort (linear), followed
by vectorized slot assignment.  No Python-level per-edge loop anywhere.

The kernel does not consume neighbor ids directly; it consumes *planes*:

  ylab    [R, D] int32   class of the neighbor in each slot, -1 = padding
  contrib [R, D] float32 w_ij / n_k contribution of the slot, 0 = padding

``ell_planes`` builds them with exactly the label/weight preprocessing of
``repro.core.gee.gee_sparse_jax`` (the -1-label convention, the 1/n_k class
weights), so kernel and segment-sum backends agree to float tolerance.
Where the label table is small, the fused kernel makes the same planes in
VMEM from ``cols`` and ``vals`` instead
(``repro.kernels.gee_fused.gee_lookup_fused_padded``).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.containers import ELL, EdgeList

SUBLANE = 8       # f32 sublane height: minimum useful row-tile multiple
LANE = 128        # TPU lane width: widths beyond this grow in LANE multiples


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ELLBucket:
    """One degree bucket: all member rows share the same tile width.

    cols:    [R_pad, width] int32 neighbor ids (0 in padding slots).
    vals:    [R_pad, width] float32 edge weights (0 in padding slots).
    row_ids: [R_pad] int32 original node id of each packed row; padding rows
             point at the dump row ``num_nodes`` (see BucketedELL.num_nodes).
    num_rows: static number of *real* rows (<= R_pad).
    width:    static tile width of this bucket.
    num_edges: static number of real (nonzero-weight) entries packed.
    """

    cols: jax.Array
    vals: jax.Array
    row_ids: jax.Array
    num_rows: int = dataclasses.field(metadata=dict(static=True))
    width: int = dataclasses.field(metadata=dict(static=True))
    num_edges: int = dataclasses.field(metadata=dict(static=True))

    @property
    def slots(self) -> int:
        """Stored slots, padding rows included: R_pad * width."""
        return int(self.cols.shape[0]) * self.width


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BucketedELL:
    """Degree-bucketed ELL tiling of one graph.

    Rows with degree 0 appear in no bucket (they contribute nothing and the
    output is initialized to zero).  Scatter targets use ``num_nodes`` as a
    dump row, so consumers allocate N+1 output rows and slice ``[:N]``.
    """

    buckets: Tuple[ELLBucket, ...]
    num_nodes: int = dataclasses.field(metadata=dict(static=True))

    @property
    def total_slots(self) -> int:
        return sum(b.slots for b in self.buckets)

    @property
    def total_edges(self) -> int:
        return sum(b.num_edges for b in self.buckets)


# ---------------------------------------------------------------------------
# O(E) row grouping (shared by both packers)
# ---------------------------------------------------------------------------

def _group_edges_by_row(edges: EdgeList, max_degree: int | None):
    """Counting-sort edges by source row.

    Returns (src, dst, w, counts, slot): arrays sorted by src, per-row edge
    counts [N] (post-truncation), and each edge's slot index within its row.
    Weight-0 (padding) edges are dropped first.  O(E): radix argsort on int32
    keys + vectorized rank-within-row.
    """
    n = edges.num_nodes
    src, dst, w = edges.valid_arrays()
    keep = w != 0
    src, dst, w = src[keep], dst[keep], w[keep]

    order = np.argsort(src, kind="stable")   # radix sort on int32: O(E)
    src, dst, w = src[order], dst[order], w[order]
    counts = np.bincount(src, minlength=n).astype(np.int64)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    slot = np.arange(src.size, dtype=np.int64) - indptr[src]
    if max_degree is not None:
        keep2 = slot < max_degree
        src, dst, w, slot = src[keep2], dst[keep2], w[keep2], slot[keep2]
        counts = np.minimum(counts, max_degree)
    return src, dst, w, counts, slot


# ---------------------------------------------------------------------------
# single-plane packer (width = global max degree)
# ---------------------------------------------------------------------------

def edges_to_ell(edges: EdgeList, row_pad: int = SUBLANE,
                 max_degree: int | None = None) -> ELL:
    """Edge list -> single-plane ELL.  Rows above ``max_degree`` are truncated
    only if it is given (tests never truncate)."""
    n = edges.num_nodes
    src, dst, w, counts, slot = _group_edges_by_row(edges, max_degree)
    dmax = max(int(counts.max()) if counts.size else 1, 1)
    n_pad = ((n + row_pad - 1) // row_pad) * row_pad
    cols = np.zeros((n_pad, dmax), np.int32)
    vals = np.zeros((n_pad, dmax), np.float32)
    cols[src, slot] = dst
    vals[src, slot] = w
    return ELL(cols=jnp.asarray(cols), vals=jnp.asarray(vals), num_nodes=n)


# ---------------------------------------------------------------------------
# degree-bucketed packer
# ---------------------------------------------------------------------------

def bucket_widths(max_degree: int, base: int = SUBLANE) -> Tuple[int, ...]:
    """Geometric width ladder 8, 16, 32, ... covering ``max_degree``.

    Consecutive widths differ by 2x, so a row of degree d is padded to less
    than 2d slots -- the padding-waste bound that makes power-law graphs safe.
    """
    widths = [base]
    while widths[-1] < max_degree:
        widths.append(widths[-1] * 2)
    return tuple(widths)


def edges_to_bucketed_ell(edges: EdgeList, row_pad: int = SUBLANE,
                          widths: Sequence[int] | None = None,
                          max_degree: int | None = None) -> BucketedELL:
    """Edge list -> degree-bucketed ELL.

    Each row goes to the narrowest bucket whose width >= its degree; empty
    rows go nowhere.  Total work is O(E + N + E * num_buckets) with
    num_buckets ~ log2(max degree).
    """
    n = edges.num_nodes
    src, dst, w, counts, slot = _group_edges_by_row(edges, max_degree)
    dmax = max(int(counts.max()) if counts.size else 1, 1)
    if widths is None:
        widths = bucket_widths(dmax)
    widths = tuple(sorted(set(int(x) for x in widths)))
    if widths[-1] < dmax:
        raise ValueError(f"widths {widths} do not cover max degree {dmax}")

    # bucket index per row: narrowest width >= degree; -1 for empty rows
    bucket_of_row = np.searchsorted(widths, counts, side="left")
    bucket_of_row[counts == 0] = -1

    buckets = []
    for b, width in enumerate(widths):
        rows = np.nonzero(bucket_of_row == b)[0]
        if rows.size == 0:
            continue
        r_pad = ((rows.size + row_pad - 1) // row_pad) * row_pad
        cols = np.zeros((r_pad, width), np.int32)
        vals = np.zeros((r_pad, width), np.float32)
        # position of each member row inside this bucket
        row_pos = np.empty(n, np.int64)
        row_pos[rows] = np.arange(rows.size)
        emask = bucket_of_row[src] == b
        cols[row_pos[src[emask]], slot[emask]] = dst[emask]
        vals[row_pos[src[emask]], slot[emask]] = w[emask]
        row_ids = np.full((r_pad,), n, np.int32)   # padding -> dump row
        row_ids[: rows.size] = rows
        buckets.append(ELLBucket(
            cols=jnp.asarray(cols), vals=jnp.asarray(vals),
            row_ids=jnp.asarray(row_ids), num_rows=int(rows.size),
            width=int(width), num_edges=int(emask.sum())))
    return BucketedELL(buckets=tuple(buckets), num_nodes=n)


# ---------------------------------------------------------------------------
# plane construction (the gee_sparse_jax label/weight preprocessing)
# ---------------------------------------------------------------------------

def ell_planes(cols: jax.Array, vals: jax.Array, labels: jax.Array,
               winv: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(cols, vals) + labels -> (ylab, contrib) kernel planes.

    Mirrors ``gee_sparse_jax`` exactly: a slot contributes w * 1/n_k iff it is
    a real edge (w != 0) whose neighbor has a known label; otherwise ylab=-1,
    contrib=0 (an exact no-op in the kernel).
    """
    n = labels.shape[0]
    safe_cols = jnp.clip(cols, 0, n - 1)
    yd = labels[safe_cols]
    valid = (vals != 0) & (yd >= 0)
    ylab = jnp.where(valid, yd, -1).astype(jnp.int32)
    contrib = jnp.where(valid, vals * winv[jnp.maximum(yd, 0)], 0.0)
    return ylab, contrib.astype(jnp.float32)


# ---------------------------------------------------------------------------
# padding accounting (benchmarks report this)
# ---------------------------------------------------------------------------

def ell_stats(edges: EdgeList, row_pad: int = SUBLANE) -> dict:
    """Slots-per-edge overhead of single-plane vs bucketed packing.

    Runs both real packers so the numbers always describe the packing the
    Pallas backend actually consumes (no parallel accounting to drift).
    """
    _, _, _, counts, _ = _group_edges_by_row(edges, None)
    e = int(counts.sum())
    ell = edges_to_ell(edges, row_pad=row_pad)
    bell = edges_to_bucketed_ell(edges, row_pad=row_pad)
    flat_slots = int(ell.cols.shape[0]) * int(ell.cols.shape[1])
    return {
        "num_nodes": edges.num_nodes,
        "num_edges": e,
        "max_degree": max(int(counts.max()) if counts.size else 1, 1),
        "flat_slots": flat_slots,
        "flat_overhead": flat_slots / max(e, 1),
        "bucketed_slots": bell.total_slots,
        "bucketed_overhead": bell.total_slots / max(e, 1),
        "num_buckets": len(bell.buckets),
    }


__all__ = ["ELL", "ELLBucket", "BucketedELL", "edges_to_ell",
           "edges_to_bucketed_ell", "ell_planes", "ell_stats",
           "bucket_widths"]
