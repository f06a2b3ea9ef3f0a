"""Jit'd wrappers assembling the Pallas kernels into the full GEE pipeline.

``gee_pallas`` mirrors the semantics of ``repro.core.gee.gee_sparse_jax``
exactly (same options, same -1-label convention) but routes the contraction
through the ``gee_spmm`` kernel and the tail through the shared epilogue
(``repro.core.epilogue.apply_epilogue`` with ``impl="pallas"``, i.e. the
``row_norm`` kernel).  Both drivers pack the *base* graph: diagonal
augmentation folds in as degrees + 1 and the epilogue's diag-aug term, as
in the fused and streaming backends.
On CPU the kernels run in interpret mode (Python evaluation of the kernel
body); on TPU they compile to Mosaic (see :mod:`repro.kernels.platform`).

Two packing strategies feed the kernel:

  * flat (``bucketed=False``): one [N_pad, D_max] plane.  Simple, but a
    power-law hub row pads everything to its degree.
  * bucketed (``bucketed=True``, the default): rows grouped into geometric
    degree buckets (see ``repro.graph.ell``).  Each bucket gets its own
    kernel launch with block sizes from the (N, max-degree, K) autotuner,
    and partial outputs are scattered back by row id.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.epilogue import apply_epilogue, inv_sqrt_degrees
from repro.core.gee import GEEOptions, class_weight_inv
from repro.graph.containers import ELL, EdgeList
from repro.graph.ell import (BucketedELL, edges_to_bucketed_ell, edges_to_ell,
                             ell_planes)
from repro.kernels.gee_spmm import choose_block_sizes, gee_spmm
from repro.kernels.platform import interpret_mode


def gee_pallas_from_ell(ell: ELL, labels: jax.Array, num_classes: int,
                        opts: GEEOptions = GEEOptions(), *,
                        block_rows: int | None = None,
                        block_deg: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """GEE from a pre-built flat ELL tiling of the *base* graph
    (device-side math only)."""
    if interpret is None:
        interpret = interpret_mode()
    labels = jnp.asarray(labels, jnp.int32)
    n = ell.num_nodes
    vals, cols = ell.vals, ell.cols
    winv = class_weight_inv(labels, num_classes)

    dinv = jnp.ones((vals.shape[0],), jnp.float32)
    if opts.laplacian:
        deg = jnp.sum(vals, axis=1)                       # padded rows -> 0
        if opts.diag_aug:
            deg = deg + 1.0                               # the unpacked loop
        dinv = inv_sqrt_degrees(deg)
        vals = vals * dinv[:, None] * dinv[jnp.clip(cols, 0, n - 1)]

    ylab, contrib = ell_planes(cols, vals, labels, winv)
    z = gee_spmm(ylab, contrib, num_classes, block_rows=block_rows,
                 block_deg=block_deg, deg_sub=None, interpret=interpret)[:n]
    return apply_epilogue(z, labels, winv, dinv[:n], opts=opts, impl="pallas")


def gee_pallas_from_bucketed(bell: BucketedELL, labels: jax.Array,
                             num_classes: int,
                             opts: GEEOptions = GEEOptions(), *,
                             block_rows: int | None = None,
                             block_deg: int | None = None,
                             interpret: bool | None = None) -> jax.Array:
    """GEE from a degree-bucketed ELL tiling of the *base* graph: one kernel
    launch per bucket, partial outputs scattered into the [N+1]-row
    accumulator (row N is the dump row for bucket padding).  Explicit block
    sizes override the autotuner for every bucket; by default each bucket
    is tuned on its own (rows, width, K)."""
    if interpret is None:
        interpret = interpret_mode()
    labels = jnp.asarray(labels, jnp.int32)
    n = bell.num_nodes
    winv = class_weight_inv(labels, num_classes)

    dinv = jnp.ones((n,), jnp.float32)
    if opts.laplacian:
        # degree = total out-weight per node, assembled across buckets
        deg = jnp.zeros((n + 1,), jnp.float32)
        for b in bell.buckets:
            deg = deg.at[b.row_ids].add(jnp.sum(b.vals, axis=1))
        deg = deg[:n]
        if opts.diag_aug:
            deg = deg + 1.0                               # the unpacked loop
        dinv = inv_sqrt_degrees(deg)

    z = jnp.zeros((n + 1, num_classes), jnp.float32)
    for b in bell.buckets:
        vals = b.vals
        if opts.laplacian:
            safe_rows = jnp.minimum(b.row_ids, n - 1)
            vals = vals * dinv[safe_rows][:, None] \
                        * dinv[jnp.clip(b.cols, 0, n - 1)]
        ylab, contrib = ell_planes(b.cols, vals, labels, winv)
        br, bd, _ = choose_block_sizes(int(b.cols.shape[0]), b.width,
                                       num_classes)
        out = gee_spmm(ylab, contrib, num_classes,
                       block_rows=block_rows if block_rows is not None else br,
                       block_deg=block_deg if block_deg is not None else bd,
                       deg_sub=None, interpret=interpret)
        z = z.at[b.row_ids].add(out)
    return apply_epilogue(z[:n], labels, winv, dinv, opts=opts, impl="pallas")


def gee_pallas(edges: EdgeList, labels, num_classes: int,
               opts: GEEOptions = GEEOptions(), *, bucketed: bool = True,
               block_rows: int | None = None, block_deg: int | None = None,
               interpret: bool | None = None) -> jax.Array:
    """Full pipeline: edge list -> (bucketed) ELL (host) -> Pallas GEE.

    Laplacian caveat: ELL rows hold *out*-edges, so the row-sum degree equals
    the symmetrized graph degree (our edge lists are stored directed with
    both (i,j) and (j,i) present -- see ``containers.symmetrize``).
    """
    labels = jnp.asarray(labels, jnp.int32)
    if bucketed:
        bell = edges_to_bucketed_ell(edges)
        return gee_pallas_from_bucketed(bell, labels, num_classes, opts,
                                        block_rows=block_rows,
                                        block_deg=block_deg,
                                        interpret=interpret)
    ell = edges_to_ell(edges)
    return gee_pallas_from_ell(ell, labels, num_classes, opts,
                               block_rows=block_rows, block_deg=block_deg,
                               interpret=interpret)
