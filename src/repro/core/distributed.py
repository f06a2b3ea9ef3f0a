"""Multi-device sparse GEE via shard_map (DESIGN.md section 5).

The paper's insight -- never store or visit zeros -- promoted to the
collective level:

* The edge list is 1-D sharded across the data-parallel mesh axes (each
  device owns E/P edges; padding edges have weight 0 and are exact no-ops).
* Each device computes a *partial* embedding by local segment-sum: O(E/P)
  work, an [N_pad, K] partial.
* One ``psum_scatter`` (reduce-scatter) over the edge axes produces the
  row-sharded final Z: each device ends with [N_pad/P, K].  Only O(N*K)
  bytes ever cross the interconnect -- no adjacency structure is shipped.
* Laplacian degrees need one extra all-reduce of an [N_pad] vector.

Communication accounting (used by the roofline benchmark):

  lap off:  reduce-scatter of N_pad*K floats          -> (P-1)/P * N*K*4 B/dev
  lap on:   + all-reduce of N_pad floats              -> 2(P-1)/P * N*4 B/dev

Compare with the dense alternative (all-gather A or Z dense): the sparse
path's collective volume is independent of E, exactly the paper's "zeros
never cost" property.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.compat import shard_map, shard_map_nocheck

from repro.core.epilogue import inv_sqrt_degrees
from repro.core.fold import (axis_size as _axis_size, combine_partials,
                             pad_nodes, scatter_partial)
from repro.core.gee import GEEOptions, class_weight_inv
from repro.graph.containers import EdgeList
from repro.graph.partition import shard_edges, shard_edges_to_ell
from repro.kernels.platform import interpret_mode


def _local_degrees(weight, src, num_nodes_pad: int, diag_aug: bool,
                   axes: tuple[str, ...]):
    """Global degrees inside the body: partial degree then all-reduce,
    plus the diag-aug +1 (self loops are never appended as edges -- the
    shared epilogue folds the diagonal term instead)."""
    deg = jax.lax.psum(
        jax.ops.segment_sum(weight, src, num_segments=num_nodes_pad), axes)
    if diag_aug:
        deg = deg + 1.0
    return inv_sqrt_degrees(deg)


@partial(jax.jit, static_argnames=("num_classes", "opts", "mesh", "axes"))
def _gee_distributed_jit(src, dst, weight, labels, num_classes: int,
                         opts: GEEOptions, mesh: Mesh,
                         axes: tuple[str, ...]):
    n_pad = labels.shape[0]              # labels pre-padded to mult of p
    winv = class_weight_inv(labels, num_classes)

    def body(src_l, dst_l, w_l, labels_l, winv_l):
        if opts.laplacian:
            dinv = _local_degrees(w_l, src_l, n_pad, opts.diag_aug, axes)
        else:
            dinv = jnp.ones((n_pad,), jnp.float32)
        # The shared fold scatter: one in-memory window per device.
        z_part = scatter_partial(src_l, dst_l, w_l, labels_l, winv_l, dinv,
                                 n_pad, num_classes
                                 ).reshape(n_pad, num_classes)
        # reduce-scatter rows + row-local epilogue: the shared combine.
        return combine_partials(z_part, labels_l, winv_l, dinv,
                                mesh=mesh, axes=axes, opts=opts)

    spec_e = P(axes)                  # edge arrays sharded on dim 0
    spec_r = P()                      # labels / winv replicated
    out_spec = P(axes, None)          # Z rows sharded on dim 0
    fn = shard_map(body, mesh=mesh,
                   in_specs=(spec_e, spec_e, spec_e, spec_r, spec_r),
                   out_specs=out_spec)
    return fn(src, dst, weight, labels, winv)


@partial(jax.jit, static_argnames=("num_classes", "opts", "mesh", "axes",
                                   "interpret"))
def _gee_distributed_pallas_jit(cols, vals, labels, num_classes: int,
                                opts: GEEOptions, mesh: Mesh,
                                axes: tuple[str, ...], interpret: bool):
    """Per-shard Pallas kernel: each device contracts its local ELL plane
    (cols/vals rows = all N_pad nodes, columns = the device's edge subset)
    and the shared combine reduce-scatters the partials -- identical
    collective pattern to the segment-sum body."""
    from repro.graph.ell import ell_planes
    from repro.kernels.gee_spmm import gee_spmm

    n_pad = labels.shape[0]
    winv = class_weight_inv(labels, num_classes)

    def body(cols_l, vals_l, labels_l, winv_l):
        if opts.laplacian:
            deg = jax.lax.psum(jnp.sum(vals_l, axis=1), axes)
            if opts.diag_aug:
                deg = deg + 1.0
            dinv = inv_sqrt_degrees(deg)
            vals_scaled = vals_l * dinv[:, None] * dinv[cols_l]
        else:
            dinv = jnp.ones((n_pad,), jnp.float32)
            vals_scaled = vals_l
        ylab, contrib = ell_planes(cols_l, vals_scaled, labels_l, winv_l)
        z_part = gee_spmm(ylab, contrib, num_classes, block_rows=None,
                          block_deg=None, deg_sub=None, interpret=interpret)
        return combine_partials(z_part, labels_l, winv_l, dinv,
                                mesh=mesh, axes=axes, opts=opts)

    # nocheck: jax has no replication rule for pallas_call inside shard_map
    fn = shard_map_nocheck(body, mesh=mesh,
                           in_specs=(P(axes, None), P(axes, None), P(), P()),
                           out_specs=P(axes, None))
    return fn(cols, vals, labels, winv)


def gee_distributed(edges, labels, num_classes: int,
                    opts: GEEOptions = GEEOptions(), *, mesh: Mesh,
                    axes: tuple[str, ...] = ("data",),
                    pre_sharded: bool = False,
                    local_backend: str = "segment_sum") -> jax.Array:
    """Distributed sparse GEE.  Returns Z with rows sharded over ``axes``.

    The one-window multi-device instance of the ``repro.core.fold``
    accumulator: per-device ``scatter_partial`` over the local edge
    shard, then the shared ``combine_partials`` reduce-scatter +
    row-local epilogue.  Diagonal augmentation is applied entirely in
    the epilogue (degrees get the +1; no self-loop edges are appended),
    exactly like the chunked and streamed_sharded instances.

    ``edges`` is an ``EdgeList`` or a ``repro.core.plan.PreparedGraph``.
    ``pre_sharded=True`` skips the host-side shuffle/pad (the caller already
    produced device-ready arrays, e.g. the dry-run path).
    ``local_backend`` selects the per-shard compute: ``"segment_sum"`` (the
    O(E/P) scatter default) or ``"pallas"`` (each shard packs its edges into
    an ELL plane and runs the ``gee_spmm`` kernel; same collectives).
    Row padding: Z has ``pad_nodes(N, P)`` rows; callers slice ``[:N]``.
    """
    p = _axis_size(mesh, axes)
    if not isinstance(edges, EdgeList):
        edges = edges.base             # PreparedGraph (duck-typed: no cycle)
    n_pad = pad_nodes(edges.num_nodes, p)
    labels = jnp.asarray(labels, jnp.int32)
    if labels.shape[0] < n_pad:
        labels = jnp.concatenate(
            [labels, jnp.full((n_pad - labels.shape[0],), -1, jnp.int32)])
    if local_backend == "pallas":
        if pre_sharded:
            raise ValueError(
                "pre_sharded edge arrays cannot feed local_backend='pallas' "
                "(the ELL planes are packed from the unsharded edge list)")
        cols, vals = shard_edges_to_ell(edges, p, num_rows=n_pad)
        interpret = interpret_mode()
        return _gee_distributed_pallas_jit(cols, vals, labels, num_classes,
                                           opts, mesh, tuple(axes), interpret)
    if local_backend != "segment_sum":
        raise ValueError(f"unknown local_backend {local_backend!r}")
    if not pre_sharded:
        edges = shard_edges(edges, p)
    return _gee_distributed_jit(edges.src, edges.dst, edges.weight, labels,
                                num_classes, opts, mesh, tuple(axes))


def lower_gee_distributed(mesh: Mesh, axes: tuple[str, ...], num_nodes: int,
                          num_edges: int, num_classes: int,
                          opts: GEEOptions = GEEOptions()):
    """Abstract lowering of the distributed GEE step for the dry-run: no
    device arrays are allocated, shapes only."""
    p = _axis_size(mesh, axes)
    e_pad = ((num_edges + p - 1) // p) * p
    n_pad = pad_nodes(num_nodes, p)
    s_e = jax.ShapeDtypeStruct((e_pad,), jnp.int32,
                               sharding=NamedSharding(mesh, P(axes)))
    s_w = jax.ShapeDtypeStruct((e_pad,), jnp.float32,
                               sharding=NamedSharding(mesh, P(axes)))
    s_y = jax.ShapeDtypeStruct((n_pad,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    fn = partial(_gee_distributed_jit, num_classes=num_classes, opts=opts,
                 mesh=mesh, axes=tuple(axes))
    return jax.jit(fn).lower(s_e, s_e, s_w, s_y)
