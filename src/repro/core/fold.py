"""The shared GEE accumulator fold: one abstraction, three backends.

Every scalable GEE path is the same computation -- stream edge windows,
fold each into O(N + N*K) accumulator state (degrees ``d``, class counts
``n_k`` via ``winv``, the embedding ``Z``), then apply the single
O(N*K) epilogue from :mod:`repro.core.epilogue`.  One-Hot GEE
(2109.13098) reaches billions of edges with exactly this structure;
Edge-Parallel GEE (2402.04403) adds the edge-partitioned per-shard
layout.  This module is the one home for that fold; the execution
backends are configurations of it:

  ``repro.core.chunked``      one device,  windows from disk
                              (``stream_fold`` + ``finalize``)
  ``repro.core.distributed``  P devices,   one in-memory window
                              (``scatter_partial`` + ``combine_partials``)
  ``gee_streamed_sharded``    P devices,   windows from disk -- each
                              window splits into P disjoint sub-windows
                              (O(1) mmap offsets), each device folds its
                              slice into a donated per-device partial,
                              one reduce-scatter + epilogue at the end.

The fold is exact under any edge order and any padding (weight-0 edges
are no-ops for every GEE formula), which is what lets the same
accumulator serve all three data placements.

>>> import numpy as np
>>> from repro.core.fold import gee_streamed_sharded
>>> from repro.core.gee import GEEOptions, gee_sparse_jax
>>> from repro.graph.containers import edge_list_from_numpy, symmetrize
>>> edges = symmetrize(edge_list_from_numpy(
...     np.array([0, 1, 2, 0]), np.array([1, 2, 3, 3]), None, 4))
>>> labels = np.array([0, 1, 0, 1], np.int32)
>>> opts = GEEOptions(laplacian=True, diag_aug=True, correlation=True)
>>> z = gee_streamed_sharded(edges, labels, 2, opts)   # 1-device mesh ok
>>> z_ref = gee_sparse_jax(edges, labels, 2, opts)
>>> bool(np.abs(np.asarray(z) - np.asarray(z_ref)).max() <= 1e-5)
True
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.epilogue import apply_epilogue, finalize, inv_sqrt_degrees
from repro.core.gee import GEEOptions, class_weight_inv
from repro.distributed.compat import shard_map, shard_map_nocheck
from repro.graph.prefetch import PlaneWindow, prefetch_windows
from repro.kernels.platform import interpret_mode
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

LOCAL_BACKENDS = ("segment_sum", "pallas")


def axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    """Total device count across the given mesh axes."""
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def pad_nodes(n: int, p: int) -> int:
    """Smallest multiple of p >= n (row padding for the reduce-scatter)."""
    return ((n + p - 1) // p) * p


# ---------------------------------------------------------------------------
# the fold primitives (every backend is built from these)
# ---------------------------------------------------------------------------

def both_directions(src, dst, weight):
    """Expand one-entry-per-undirected-edge arrays to both directions in
    one concatenation (self loops stored once keep a single copy: the
    reversed duplicate gets weight 0, an exact no-op)."""
    w_rev = jnp.where(src == dst, 0.0, weight)
    return (jnp.concatenate([src, dst]), jnp.concatenate([dst, src]),
            jnp.concatenate([weight, w_rev]))


def scatter_partial(src, dst, weight, labels, winv, dinv, num_rows: int,
                    num_classes: int):
    """The one edge->Z scatter: ``Z[i, y_j] += w_ij dinv_i dinv_j / n_k``.

    Exactly ``gee_sparse_jax``'s contraction, as a flat [num_rows * K]
    segment-sum.  ``dinv`` is all-ones when Laplacian normalization is
    off (``w * 1.0`` is exact in float32, so that path stays
    bit-faithful).  Unlabeled targets (-1) and weight-0 padding edges
    contribute exactly zero.
    """
    yd = labels[dst]
    valid = yd >= 0
    yd_safe = jnp.where(valid, yd, 0)
    w_hat = weight * dinv[src] * dinv[dst]
    contrib = jnp.where(valid, w_hat * winv[yd_safe], 0.0)
    flat = src * num_classes + yd_safe
    return jax.ops.segment_sum(contrib, flat,
                               num_segments=num_rows * num_classes)


@partial(jax.jit, static_argnames=("undirected",))
def fold_degrees(deg, src, dst, weight, *, undirected: bool):
    """deg += window's weighted out-degrees (both directions if undirected;
    padding edges have weight 0 and are exact no-ops)."""
    if undirected:
        src, dst, weight = both_directions(src, dst, weight)
    return deg + jax.ops.segment_sum(weight, src,
                                     num_segments=deg.shape[0])


@partial(jax.jit, static_argnames=("num_classes", "undirected"))
def fold_z(z_flat, src, dst, weight, labels, winv, dinv, *,
           num_classes: int, undirected: bool):
    """z += window's per-class sums via :func:`scatter_partial`."""
    if undirected:
        src, dst, weight = both_directions(src, dst, weight)
    num_rows = z_flat.shape[0] // num_classes
    return z_flat + scatter_partial(src, dst, weight, labels, winv, dinv,
                                    num_rows, num_classes)


def combine_partials(z_part, labels, winv, dinv, *, mesh: Mesh,
                     axes: tuple[str, ...], opts: GEEOptions):
    """shard_map-body tail shared by every multi-device fold.

    Reduce-scatters the local [N_pad, K] partial into this device's row
    block (the only O(N*K) collective), then applies the epilogue
    row-locally: the diag-aug term and the correlation row norm touch
    one row at a time, so a row-sharded Z finishes without another
    collective.  ``labels``/``winv``/``dinv`` are the replicated full
    vectors.
    """
    z_rows = jax.lax.psum_scatter(z_part, axes, scatter_dimension=0,
                                  tiled=True)
    rows_per = z_rows.shape[0]
    lin = 0                            # linear device index, row-major in axes
    for a in axes:
        lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
    off = lin * rows_per
    labels_l = jax.lax.dynamic_slice_in_dim(labels, off, rows_per)
    dinv_l = jax.lax.dynamic_slice_in_dim(dinv, off, rows_per)
    # the one shared epilogue composition (repro.core.epilogue), row-local
    return apply_epilogue(z_rows, labels_l, winv, dinv_l, opts=opts,
                          impl="jnp")


# ---------------------------------------------------------------------------
# single-device streaming instance (what repro.core.chunked wraps)
# ---------------------------------------------------------------------------

def stream_fold(source, labels, num_classes: int, opts: GEEOptions, *,
                prefetch_windows: int | None = None):
    """Two-pass fold of a ``WindowSource`` on the current default device.

    Returns ``(z_flat, winv, dinv)`` ready for
    :func:`repro.core.epilogue.finalize`.  Peak memory is
    O(window + N*K) however large E grows; every window has identical
    array shapes, so the jitted folds trace once per configuration.

    ``prefetch_windows`` stages that many windows ahead on background
    threads (read + pad + ``device_put``) so host-side window costs
    overlap the device fold; ``None`` resolves through
    ``REPRO_GEE_PREFETCH_WINDOWS`` (default 2) and ``0`` is the
    synchronous path.
    """
    n, k = source.num_nodes, int(num_classes)
    labels = jnp.asarray(labels, jnp.int32)
    if labels.shape[0] != n:
        raise ValueError(f"labels cover {labels.shape[0]} nodes, "
                         f"graph has {n}")
    winv = class_weight_inv(labels, k)
    und = source.undirected
    source = _prefetch(source, prefetch_windows)
    tr = obs_trace.get_tracer()
    traced = tr.enabled
    degree_windows = 0

    if opts.laplacian:
        deg = jnp.zeros((n,), jnp.float32)
        for i, w in enumerate(source.windows()):             # pass 1
            with tr.span("fold.window", phase="degrees", idx=i,
                         edges=int(w.num_edges)):
                deg = fold_degrees(deg, w.src, w.dst, w.weight,
                                   undirected=und)
                if traced:       # async dispatch: sync for honest spans
                    deg.block_until_ready()
            degree_windows += 1
        if opts.diag_aug:
            deg = deg + 1.0
        dinv = inv_sqrt_degrees(deg)
    else:
        dinv = jnp.ones((n,), jnp.float32)

    t_scatter = time.perf_counter()
    scatter_windows = edges_folded = 0
    z = jnp.zeros((n * k,), jnp.float32)
    for i, w in enumerate(source.windows()):                 # pass 2
        with tr.span("fold.window", phase="scatter", idx=i,
                     edges=int(w.num_edges)):
            z = fold_z(z, w.src, w.dst, w.weight, labels, winv, dinv,
                       num_classes=k, undirected=und)
            if traced:
                z.block_until_ready()
        scatter_windows += 1
        edges_folded += int(w.num_edges)

    _record_fold(degree_windows, scatter_windows, edges_folded,
                 time.perf_counter() - t_scatter)
    return z, winv, dinv


def _prefetch(source, depth: int | None, stage=None, sharding=None):
    """Wrap a window source for background staging (module-level import
    aliased to avoid shadowing by the ``prefetch_windows=`` kwarg)."""
    return prefetch_windows(source, depth, stage=stage, sharding=sharding)


def _record_fold(degree_windows: int, scatter_windows: int, edges: int,
                 scatter_s: float) -> None:
    """Registry bookkeeping shared by the streaming folds.  Runs once per
    fold (never per window), so the always-on cost is a few lock
    acquisitions.

    Each logical window counts once in ``fold.windows`` (the scatter
    pass walks every window exactly once in every configuration); the
    laplacian degree pre-pass is tracked separately as
    ``fold.windows.degrees`` so two-pass folds no longer double-count
    windows or edges.  ``fold.edges`` and the ``fold.edges_per_sec``
    gauge come from the scatter pass only: edges folded over scatter-pass
    wall time (honest under tracing, where stage syncs are forced;
    untraced it includes async dispatch overlap).
    """
    reg = obs_metrics.get_registry()
    reg.counter("fold.windows").inc(scatter_windows)
    reg.counter("fold.windows.scatter").inc(scatter_windows)
    reg.counter("fold.windows.degrees").inc(degree_windows)
    reg.counter("fold.edges").inc(edges)
    if scatter_s > 0 and edges:
        reg.gauge("fold.edges_per_sec").set(edges / scatter_s)


# ---------------------------------------------------------------------------
# multi-device streaming instance: the streamed_sharded backend
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mesh", "axes", "undirected"),
         donate_argnums=(0,))
def _fold_degrees_sharded(deg_parts, src, dst, weight, *, mesh: Mesh,
                          axes: tuple[str, ...], undirected: bool):
    """deg_parts[d] += device d's sub-window degrees (donated in place)."""
    def body(deg_l, src_l, dst_l, w_l):
        if undirected:
            src_l, dst_l, w_l = both_directions(src_l, dst_l, w_l)
        return deg_l + jax.ops.segment_sum(
            w_l, src_l, num_segments=deg_l.shape[1])[None, :]

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axes, None), P(axes), P(axes), P(axes)),
                     out_specs=P(axes, None))(deg_parts, src, dst, weight)


@partial(jax.jit, static_argnames=("mesh", "axes", "num_classes",
                                   "undirected"),
         donate_argnums=(0,))
def _fold_z_sharded(z_parts, src, dst, weight, labels, winv, dinv, *,
                    mesh: Mesh, axes: tuple[str, ...], num_classes: int,
                    undirected: bool):
    """z_parts[d] += device d's sub-window scatter (donated in place)."""
    num_rows = labels.shape[0]

    def body(z_l, src_l, dst_l, w_l, labels_l, winv_l, dinv_l):
        if undirected:
            src_l, dst_l, w_l = both_directions(src_l, dst_l, w_l)
        return z_l + scatter_partial(src_l, dst_l, w_l, labels_l, winv_l,
                                     dinv_l, num_rows, num_classes)[None, :]

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axes, None), P(axes), P(axes), P(axes),
                               P(), P(), P()),
                     out_specs=P(axes, None))(
        z_parts, src, dst, weight, labels, winv, dinv)


@partial(jax.jit, static_argnames=("mesh", "axes", "num_classes",
                                   "interpret"),
         donate_argnums=(0,))
def _fold_plane_sharded(z_parts, cols, vals, labels, winv, dinv, *,
                        mesh: Mesh, axes: tuple[str, ...], num_classes: int,
                        interpret: bool):
    """z_parts[d] += device d's ELL-plane contraction via the Pallas
    ``gee_spmm`` kernel (planes packed per window by
    ``repro.graph.partition.shard_edges_to_ell``)."""
    from repro.graph.ell import ell_planes
    from repro.kernels.gee_spmm import gee_spmm

    def body(z_l, cols_l, vals_l, labels_l, winv_l, dinv_l):
        vals_scaled = vals_l * dinv_l[:, None] * dinv_l[cols_l]
        ylab, contrib = ell_planes(cols_l, vals_scaled, labels_l, winv_l)
        z = gee_spmm(ylab, contrib, num_classes, block_rows=None,
                     block_deg=None, deg_sub=None, interpret=interpret)
        return z_l + z.reshape(1, -1)

    # nocheck: jax has no replication rule for pallas_call inside shard_map
    return shard_map_nocheck(body, mesh=mesh,
                             in_specs=(P(axes, None), P(axes, None),
                                       P(axes, None), P(), P(), P()),
                             out_specs=P(axes, None))(
        z_parts, cols, vals, labels, winv, dinv)


@partial(jax.jit, static_argnames=("mesh", "axes", "num_classes", "opts"))
def _combine_sharded(z_parts, labels, winv, dinv, *, mesh: Mesh,
                     axes: tuple[str, ...], num_classes: int,
                     opts: GEEOptions):
    """Fold the P per-device partials into the row-sharded final Z."""
    num_rows = labels.shape[0]

    def body(z_l, labels_l, winv_l, dinv_l):
        z_part = z_l.reshape(num_rows, num_classes)
        return combine_partials(z_part, labels_l, winv_l, dinv_l,
                                mesh=mesh, axes=axes, opts=opts)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axes, None), P(), P(), P()),
                     out_specs=P(axes, None))(z_parts, labels, winv, dinv)


def default_mesh() -> Mesh:
    """1-D ``("data",)`` mesh over every device of this process, in the
    device order ``jax.make_mesh`` picks for the interconnect."""
    return jax.make_mesh((jax.local_device_count(),), ("data",),
                         axis_types=(AxisType.Auto,),
                         devices=jax.local_devices())


def _window_plane(window, num_shards: int, num_rows: int,
                  undirected: bool):
    """Host-side per-window ELL pack for the pallas local backend.

    Expands undirected storage to both directions on the host, then
    packs one [P * num_rows, width] plane with a pow2-laddered width so
    only O(log max_degree) distinct shapes ever trace.
    """
    from repro.graph.containers import edge_list_from_numpy
    from repro.graph.partition import shard_edges_to_ell, stable_plane_width

    e = window.num_edges
    src = np.asarray(window.src)[:e]
    dst = np.asarray(window.dst)[:e]
    w = np.asarray(window.weight)[:e]
    if undirected:
        nonloop = src != dst
        src, dst, w = (np.concatenate([src, dst[nonloop]]),
                       np.concatenate([dst, src[nonloop]]),
                       np.concatenate([w, w[nonloop]]))
    edges = edge_list_from_numpy(src, dst, w, num_rows)
    deg = np.bincount(src[w != 0], minlength=1)
    width = stable_plane_width(int(deg.max(initial=0)), num_shards)
    return shard_edges_to_ell(edges, num_shards, num_rows=num_rows,
                              width=width)


def gee_streamed_sharded(source, labels, num_classes: int,
                         opts: GEEOptions = GEEOptions(), *,
                         mesh: Mesh | None = None,
                         axes: tuple[str, ...] = ("data",),
                         local_backend: str = "segment_sum",
                         impl: str = "jnp",
                         prefetch_windows: int | None = None) -> jax.Array:
    """Disk-bounded multi-device GEE: stream windows, fold per shard.

    ``source`` is anything :func:`repro.graph.io.as_window_source`
    accepts -- an in-memory ``EdgeList``, a ``ChunkedEdgeList`` (mmap
    ``.geeb`` included), or a ``PreparedGraph``.  Each window is padded
    so it splits into P equal disjoint sub-windows; device d folds slice
    ``[d*c/P, (d+1)*c/P)`` of every window into its donated partial
    accumulator, so steady-state host->device traffic and device memory
    are O(window/P + N*K) per device -- E never needs to fit anywhere.

    One reduce-scatter at the end produces the row-sharded Z; the
    epilogue runs row-locally inside the same ``shard_map``
    (:func:`combine_partials`).  Numerically the ``gee_sparse_jax``
    contract (<= 1e-5 max-abs under every option setting).

    ``mesh=None`` builds a 1-D ``("data",)`` mesh over all local
    devices (:func:`default_mesh`).  ``local_backend`` is ``"segment_sum"`` (default) or
    ``"pallas"`` (per-window ELL planes contracted by ``gee_spmm``).
    ``prefetch_windows`` stages reads, ELL packing and the sharded
    ``device_put`` on background threads so window *i+1*'s host costs
    overlap window *i*'s donated fold (``None``: env-resolved default 2;
    ``0``: synchronous).  Returns Z rows sharded over ``axes``, sliced
    to [N, K].
    """
    from repro.graph.io import as_window_source

    del impl  # row norm runs inside shard_map: always the jnp form
    if hasattr(source, "chunked") and not hasattr(source, "windows"):
        source = source.chunked()      # PreparedGraph (duck-typed: no cycle)
    source = as_window_source(source)
    if local_backend not in LOCAL_BACKENDS:
        raise ValueError(f"unknown local_backend {local_backend!r}; "
                         f"pick one of {LOCAL_BACKENDS}")
    if mesh is None:
        mesh = default_mesh()
        axes = ("data",)
    axes = tuple(axes)
    p = axis_size(mesh, axes)
    # per-device partials are born sharded, one row per device: an
    # unsharded zeros would first land whole on device 0
    parts = NamedSharding(mesh, P(axes, None))

    n, k = source.num_nodes, int(num_classes)
    labels = jnp.asarray(labels, jnp.int32)
    if labels.shape[0] != n:
        raise ValueError(f"labels cover {labels.shape[0]} nodes, "
                         f"graph has {n}")
    n_pad = pad_nodes(n, p)
    if n_pad > n:
        labels = jnp.concatenate(
            [labels, jnp.full((n_pad - n,), -1, jnp.int32)])
    winv = class_weight_inv(labels, k)
    und = source.undirected
    g = pad_nodes(source.window_edges, p)   # window split into P sub-windows
    # Stage windows eagerly on background threads, already committed with
    # the sharding the jitted folds consume (1-D edge arrays split over
    # ``axes``), so window i+1's host->device copy overlaps window i's
    # donated fold.
    pf = _prefetch(source, prefetch_windows,
                   sharding=NamedSharding(mesh, P(axes)))
    tr = obs_trace.get_tracer()
    traced = tr.enabled
    degree_windows = 0

    if opts.laplacian:
        deg_parts = jnp.zeros((p, n_pad), jnp.float32, device=parts)
        for i, w in enumerate(pf.windows(pad_to=g)):         # pass 1
            with tr.span("fold.window", phase="degrees", idx=i, shards=p,
                         edges=int(w.num_edges)):
                deg_parts = _fold_degrees_sharded(
                    deg_parts, w.src, w.dst, w.weight,
                    mesh=mesh, axes=axes, undirected=und)
                if traced:
                    deg_parts.block_until_ready()
            degree_windows += 1
        deg = deg_parts.sum(axis=0)
        if opts.diag_aug:
            deg = deg + 1.0
        dinv = inv_sqrt_degrees(deg)
    else:
        dinv = jnp.ones((n_pad,), jnp.float32)

    t_scatter = time.perf_counter()
    scatter_windows = edges_folded = 0
    z_parts = jnp.zeros((p, n_pad * k), jnp.float32, device=parts)
    if local_backend == "pallas":
        interpret = interpret_mode()
        plane_sharding = parts

        def plane_stage(w):
            """Worker-thread stage: ELL plane pack + sharded device_put."""
            cols, vals = _window_plane(w, p, n_pad, und)
            # per-leaf device_put: a tuple arg lowers to an XLA
            # computation, which the CPU client would serialize behind
            # the consumer's in-flight fold steps
            cols = jax.device_put(cols, plane_sharding)
            vals = jax.device_put(vals, plane_sharding)
            jax.block_until_ready((cols, vals))
            return PlaneWindow(int(w.num_edges), cols, vals)

        pf_planes = _prefetch(source, prefetch_windows, stage=plane_stage)
        for i, w in enumerate(pf_planes.windows(pad_to=g)):  # pass 2
            with tr.span("fold.window", phase="scatter", idx=i, shards=p,
                         edges=int(w.num_edges)):
                if isinstance(w, PlaneWindow):               # pre-packed
                    cols, vals = w.cols, w.vals
                else:                                        # synchronous
                    cols, vals = _window_plane(w, p, n_pad, und)
                z_parts = _fold_plane_sharded(
                    z_parts, cols, vals, labels, winv, dinv,
                    mesh=mesh, axes=axes, num_classes=k,
                    interpret=interpret)
                if traced:
                    z_parts.block_until_ready()
            scatter_windows += 1
            edges_folded += int(w.num_edges)
    else:
        for i, w in enumerate(pf.windows(pad_to=g)):         # pass 2
            with tr.span("fold.window", phase="scatter", idx=i, shards=p,
                         edges=int(w.num_edges)):
                z_parts = _fold_z_sharded(
                    z_parts, w.src, w.dst, w.weight, labels, winv, dinv,
                    mesh=mesh, axes=axes, num_classes=k, undirected=und)
                if traced:
                    z_parts.block_until_ready()
            scatter_windows += 1
            edges_folded += int(w.num_edges)

    with tr.span("fold.combine", shards=p, n=n, k=k):
        z = _combine_sharded(z_parts, labels, winv, dinv, mesh=mesh,
                             axes=axes, num_classes=k, opts=opts)
        if traced:
            z.block_until_ready()
    _record_fold(degree_windows, scatter_windows, edges_folded,
                 time.perf_counter() - t_scatter)
    return z[:n]


__all__ = ["axis_size", "pad_nodes", "both_directions", "scatter_partial",
           "fold_degrees", "fold_z", "combine_partials", "stream_fold",
           "gee_streamed_sharded", "default_mesh", "finalize",
           "LOCAL_BACKENDS"]
