"""Out-of-core GEE: the two-pass, chunk-streamed form of ``gee_sparse_jax``.

One-Hot GEE (2109.13098) observes that the accumulator state -- the class
counts ``n_k``, the degree vector ``d`` and the embedding ``Z`` -- is
O(N + N*K), tiny next to the edge list; Edge-Parallel GEE (2402.04403)
shows edge-chunked accumulation is exact because every GEE formula is a
sum over edges.  So the edge list never needs to be resident: stream it
from disk in fixed windows and fold each window into the accumulators.

  pass 1   (Laplacian only) degrees of the *augmented* graph:
           ``d_i = sum_j w_ij (+ 1 under diag-aug)``, one segment-sum per
           chunk.  Class counts ``n_k`` come from the labels, O(N).
  pass 2   per-class sums: each chunk contributes
           ``Z[i, y_j] += w_ij * d_i^{-1/2} d_j^{-1/2} / n_{y_j}`` via the
           same flat segment-sum as ``gee_sparse_jax``.
  finalize diag-aug self loops (``Z[i, y_i] += d_i^{-1} / n_{y_i}``) and
           the correlation row-normalization are O(N*K), applied once.

Peak memory is O(chunk_edges + N*K) however large E grows; every chunk
has identical array shapes (the tail is weight-0 padded), so the jitted
folds trace exactly once per (chunk size, N, K) configuration.

The fold itself lives in :mod:`repro.core.fold` -- this module is the
single-device configuration of the shared accumulator (the multi-device
streaming configuration is ``repro.core.fold.gee_streamed_sharded``).

Undirected sources (one stored entry per edge {i, j}) are folded in both
directions per chunk -- self loops counted once -- so the result matches
materializing :func:`repro.graph.containers.symmetrize` first.

>>> import numpy as np
>>> from repro.core.chunked import gee_chunked
>>> from repro.core.gee import GEEOptions, gee_sparse_jax
>>> from repro.graph.containers import edge_list_from_numpy, symmetrize
>>> from repro.graph.io import ChunkedEdgeList
>>> edges = symmetrize(edge_list_from_numpy(
...     np.array([0, 1, 2, 0]), np.array([1, 2, 3, 3]), None, 4))
>>> labels = np.array([0, 1, 0, 1], np.int32)
>>> opts = GEEOptions(laplacian=True, diag_aug=True, correlation=True)
>>> z_stream = gee_chunked(ChunkedEdgeList.from_edge_list(edges, 3),
...                        labels, 2, opts)
>>> z_full = gee_sparse_jax(edges, labels, 2, opts)
>>> bool(np.abs(np.asarray(z_stream) - np.asarray(z_full)).max() <= 1e-5)
True
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.epilogue import finalize
from repro.core.fold import (both_directions, fold_degrees, fold_z,
                             stream_fold)
from repro.core.gee import GEEOptions
from repro.graph.io import (ChunkedEdgeList, DEFAULT_CHUNK_EDGES,
                            load_labels, open_edge_list)
from repro.obs import trace as obs_trace

# Deprecated aliases: the fold primitives moved to repro.core.fold.
_both_directions = both_directions
_fold_degrees = fold_degrees
_fold_z = fold_z


def gee_chunked(chunked: ChunkedEdgeList, labels, num_classes: int,
                opts: GEEOptions = GEEOptions(),
                impl: str = "jnp",
                prefetch_windows: int | None = None) -> jax.Array:
    """Chunk-streamed GEE over any :class:`ChunkedEdgeList` source.

    The single-device instance of the shared
    :func:`repro.core.fold.stream_fold` accumulator, followed by the one
    O(N*K) epilogue (``repro.core.epilogue.finalize``: diag-aug self
    loops + correlation), applied once after streaming.

    Numerically the ``gee_sparse_jax`` contract (<= 1e-5 max-abs under
    every option setting); host memory stays O(chunk_edges + N*K).
    ``impl`` selects the epilogue row-norm implementation
    (``repro.core.epilogue.row_l2_normalize``; ``"auto"`` picks the
    Pallas kernel on TPU).  ``prefetch_windows`` stages windows ahead on
    background threads (``None``: ``REPRO_GEE_PREFETCH_WINDOWS`` or 2;
    ``0``: synchronous reads).
    """
    k = int(num_classes)
    z, winv, dinv = stream_fold(chunked, labels, k, opts,
                                prefetch_windows=prefetch_windows)
    with obs_trace.span("fold.epilogue", n=chunked.num_nodes, k=k):
        return finalize(z, jnp.asarray(labels, jnp.int32), winv, dinv,
                        num_classes=k, opts=opts, impl=impl)


def gee_chunked_from_file(path: str, labels=None, num_classes: int | None = None,
                          opts: GEEOptions = GEEOptions(),
                          chunk_edges: int = DEFAULT_CHUNK_EDGES,
                          prefetch_windows: int | None = None,
                          **open_kw) -> jax.Array:
    """Embed straight from an edge file (see ``repro.graph.io`` formats).

    ``labels=None`` reads the ``<path>.labels.npy`` sidecar;
    ``num_classes=None`` infers ``max(labels) + 1``.
    """
    chunked = open_edge_list(path, chunk_edges=chunk_edges, **open_kw)
    if labels is None:
        labels = load_labels(path)
        if labels is None:
            raise ValueError(f"no labels given and no sidecar "
                             f"{path}.labels.npy")
    if num_classes is None:
        num_classes = int(max(int(jnp.asarray(labels).max()) + 1, 1))
    return gee_chunked(chunked, labels, num_classes, opts,
                       prefetch_windows=prefetch_windows)
