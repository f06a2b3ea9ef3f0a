"""Graph Encoder Embedding -- every backend the paper compares, plus ours.

Backends (all numerically equivalent; tested against each other):

  gee_python_loop   the *original GEE* reference: a pure-Python loop over the
                    edge list (the implementation the paper benchmarks
                    against -- its ~10 us/edge constant is why the paper's
                    GEE column reads 52 s at 5.6M edges).
  gee_scipy         the paper's contribution: SciPy DOK -> CSR sparse
                    pipeline, faithful to Table 1 formulas.
  gee_dense_jax     dense-matmul oracle  Z = A @ W  (materializes A; used as
                    the numerical ground truth and as the dense baseline for
                    the sparsity benchmarks).
  gee_sparse_jax    the TPU-native adaptation: O(E) edge-list segment-sum,
                    jit-able, static shapes, zero dense intermediates.  This
                    is the core-library path used by distributed GEE and the
                    Pallas kernel wraps the same contract.

Two more live in their own modules and are reachable through ``gee``'s
``backend=`` switch: ``chunked`` (``repro.core.chunked``: the out-of-core
two-pass stream over disk-resident edge lists) and ``pallas``
(``repro.kernels.gee_fused``: the ELL-tiled MXU kernel with the epilogue
fused in).

Shared semantics
----------------
* labels: int32 [N], -1 = unknown (zero W row, still gets a Z row).
* options order (matches the reference GEE implementation): diagonal
  augmentation first (A <- A + I), then Laplacian normalization using the
  degrees of the *augmented* graph, then Z = A_hat @ W, then optional row
  L2 normalization ("correlation").
* The Laplacian path never materializes D: d_i^{-1/2} d_j^{-1/2} is folded
  into each edge weight (a beyond-paper micro-optimization; the SciPy
  backend keeps the paper's explicit D_s^{-1/2} matrices for fidelity).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.epilogue import (EPS_NORM, inv_sqrt_degrees,
                                 row_l2_normalize_jnp, row_l2_normalize_np)
from repro.graph.containers import EdgeList, add_self_loops, to_dense


@dataclasses.dataclass(frozen=True)
class GEEOptions:
    laplacian: bool = False
    diag_aug: bool = False
    correlation: bool = False

    def tag(self) -> str:
        return (f"Lap={'T' if self.laplacian else 'F'},"
                f"Diag={'T' if self.diag_aug else 'F'},"
                f"Cor={'T' if self.correlation else 'F'}")


ALL_OPTION_SETTINGS = tuple(
    GEEOptions(laplacian=l, diag_aug=d, correlation=c)
    for l in (True, False) for d in (True, False) for c in (True, False)
)


# ---------------------------------------------------------------------------
# shared small pieces
# ---------------------------------------------------------------------------

def class_counts(labels: jax.Array, num_classes: int) -> jax.Array:
    """n_k for k in [0, K); unknown (-1) labels are not counted."""
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    return jax.ops.segment_sum(
        valid.astype(jnp.float32), safe, num_segments=num_classes)


def class_weight_inv(labels: jax.Array, num_classes: int) -> jax.Array:
    """1/n_k per class (0 for empty classes): the W-matrix row scaling."""
    nk = class_counts(labels, num_classes)
    return jnp.where(nk > 0, 1.0 / jnp.maximum(nk, 1.0), 0.0)


def weight_matrix_dense(labels: jax.Array, num_classes: int) -> jax.Array:
    """W [N, K]: row j = one_hot(y_j) / n_{y_j}; zero row for unknown."""
    nk = class_counts(labels, num_classes)
    inv = jnp.where(nk > 0, 1.0 / jnp.maximum(nk, 1.0), 0.0)
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    return onehot * inv[None, :]


# Deprecated alias: the correlation row normalization (and the rest of the
# O(N*K) epilogue) moved to ``repro.core.epilogue``, the single numerics
# source of truth shared by every backend.
_row_l2_normalize = row_l2_normalize_jnp


# ---------------------------------------------------------------------------
# backend 1: original GEE (pure-Python edge loop) -- benchmark fidelity only
# ---------------------------------------------------------------------------

def gee_python_loop(src: np.ndarray, dst: np.ndarray, weight: np.ndarray,
                    labels: np.ndarray, num_classes: int,
                    opts: GEEOptions = GEEOptions(),
                    num_nodes: int | None = None) -> np.ndarray:
    """Reference original-GEE: per-edge Python loop, as in the upstream
    Python implementation the paper times.  O(E) with a Python constant."""
    n = int(num_nodes if num_nodes is not None else labels.shape[0])
    k = int(num_classes)
    src = [int(x) for x in src]
    dst = [int(x) for x in dst]
    weight = [float(x) for x in weight]
    y = [int(x) for x in labels]

    if opts.diag_aug:
        src = src + list(range(n))
        dst = dst + list(range(n))
        weight = weight + [1.0] * n

    nk = [0] * k
    for yj in y:
        if yj >= 0:
            nk[yj] += 1
    winv = [1.0 / c if c > 0 else 0.0 for c in nk]

    if opts.laplacian:
        deg = [0.0] * n
        for s, w in zip(src, weight):
            deg[s] += w
        dinv = [d ** -0.5 if d > 0 else 0.0 for d in deg]
        weight = [w * dinv[s] * dinv[d]
                  for s, d, w in zip(src, dst, weight)]

    z = [[0.0] * k for _ in range(n)]
    for s, d, w in zip(src, dst, weight):
        yd = y[d]
        if yd >= 0 and w != 0.0:
            z[s][yd] += w * winv[yd]

    out = np.asarray(z, np.float64)
    if opts.correlation:
        out = row_l2_normalize_np(out)     # shared epilogue semantics
    return out.astype(np.float32)


# ---------------------------------------------------------------------------
# backend 2: sparse GEE (SciPy CSR) -- the paper's method, faithful
# ---------------------------------------------------------------------------

def gee_scipy(src: np.ndarray, dst: np.ndarray, weight: np.ndarray,
              labels: np.ndarray, num_classes: int,
              opts: GEEOptions = GEEOptions(),
              num_nodes: int | None = None,
              return_sparse: bool = False):
    """Paper-faithful sparse GEE: DOK-style construction, CSR compute,
    Table 1 formulas (explicit I_s and D_s^{-1/2} diagonal CSR matrices)."""
    import scipy.sparse as sp

    n = int(num_nodes if num_nodes is not None else labels.shape[0])
    k = int(num_classes)
    a = sp.csr_array((weight.astype(np.float64),
                      (src.astype(np.int64), dst.astype(np.int64))),
                     shape=(n, n))
    if opts.diag_aug:
        a = a + sp.identity(n, format="csr")
    if opts.laplacian:
        deg = np.asarray(a.sum(axis=1)).ravel()
        with np.errstate(divide="ignore"):
            dinv = np.where(deg > 0, deg ** -0.5, 0.0)
        d_s = sp.diags_array(dinv, format="csr")   # D_s^{-1/2}, as in Table 1
        a = d_s @ a @ d_s

    y = labels.astype(np.int64)
    valid = y >= 0
    nk = np.bincount(y[valid], minlength=k).astype(np.float64)
    winv = np.where(nk > 0, 1.0 / np.maximum(nk, 1.0), 0.0)
    rows = np.nonzero(valid)[0]
    w_s = sp.csr_array((winv[y[valid]], (rows, y[valid])), shape=(n, k))

    z = a @ w_s                                    # CSR x CSR -> CSR
    if opts.correlation:
        # Same semantics as repro.core.epilogue.row_l2_normalize: rows with
        # norm > 0 divide by max(norm, EPS_NORM).  This backend computes in
        # float64, so without the shared clamp a denormal-float32-scale row
        # would renormalize to unit norm here while every other backend
        # (float32, clamped) returns a tiny row -- a real cross-backend
        # divergence until the epsilons were unified.
        nrm = sp.linalg.norm(z, axis=1)
        inv = np.where(nrm > 0, 1.0 / np.maximum(nrm, EPS_NORM), 0.0)
        z = sp.diags_array(inv, format="csr") @ z
    if return_sparse:
        return z
    return np.asarray(z.todense(), np.float32)


# ---------------------------------------------------------------------------
# backend 3: dense-matmul oracle in JAX
# ---------------------------------------------------------------------------

def gee_dense_jax(edges: EdgeList, labels: jax.Array, num_classes: int,
                  opts: GEEOptions = GEEOptions()) -> jax.Array:
    a = to_dense(edges)
    if opts.diag_aug:
        a = a + jnp.eye(edges.num_nodes, dtype=a.dtype)
    if opts.laplacian:
        dinv = inv_sqrt_degrees(a.sum(axis=1))
        a = dinv[:, None] * a * dinv[None, :]
    w = weight_matrix_dense(labels, num_classes)
    z = a @ w
    if opts.correlation:
        z = row_l2_normalize_jnp(z)
    return z


# ---------------------------------------------------------------------------
# backend 4: TPU-native sparse GEE (segment-sum) -- the core library path
# ---------------------------------------------------------------------------

def laplacian_edge_weights(edges: EdgeList) -> jax.Array:
    """w_ij <- w_ij * d_i^{-1/2} * d_j^{-1/2} without materializing D."""
    deg = jax.ops.segment_sum(edges.weight, edges.src,
                              num_segments=edges.num_nodes)
    dinv = inv_sqrt_degrees(deg)
    return edges.weight * dinv[edges.src] * dinv[edges.dst]


@partial(jax.jit, static_argnames=("num_classes", "opts"))
def gee_sparse_jax(edges: EdgeList, labels: jax.Array, num_classes: int,
                   opts: GEEOptions = GEEOptions()) -> jax.Array:
    """O(E) segment-sum GEE.  Static shapes; padding edges (weight 0) are
    exact no-ops; jit/pjit friendly."""
    if opts.diag_aug:
        edges = add_self_loops(edges)
    w = laplacian_edge_weights(edges) if opts.laplacian else edges.weight

    n, k = edges.num_nodes, num_classes
    winv = class_weight_inv(labels, k)

    yd = labels[edges.dst]                       # class of each neighbor
    valid = yd >= 0
    yd_safe = jnp.where(valid, yd, 0)
    contrib = jnp.where(valid, w * winv[yd_safe], 0.0)
    flat_idx = edges.src * k + yd_safe           # scatter target in [0, N*K)
    z = jax.ops.segment_sum(contrib, flat_idx, num_segments=n * k)
    z = z.reshape(n, k)
    if opts.correlation:
        z = row_l2_normalize_jnp(z)
    return z


def select_backend(edges: EdgeList, num_classes: int) -> str:
    """Deprecated shim: backend selection moved to
    ``repro.core.plan.select_backend``, which adds the memory-footprint
    route to ``chunked``.  Kept so external callers of the old location
    keep working."""
    from repro.core.plan import select_backend as _select  # deferred: cycle

    return _select(edges, num_classes)


def gee(edges, labels, num_classes: int,
        opts: GEEOptions = GEEOptions(), backend: str = "sparse_jax"):
    """Dispatch front-end: a thin consumer of ``repro.core.plan.GEEPlan``.

    ``edges`` is an ``EdgeList`` or a ``repro.core.plan.PreparedGraph``;
    pass the latter (and reuse it across calls) to share every prep
    artifact -- self-loop augmentation, Laplacian fold, ELL packing,
    chunk manifest -- between fits, option settings and backends.

    Backends: ``sparse_jax`` (production default), ``pallas`` (ELL + Pallas
    kernel), ``chunked`` (bounded-memory streaming, see
    ``repro.core.chunked``), ``streamed_sharded`` (bounded-memory
    streaming split across all devices, see ``repro.core.fold``),
    ``dense_jax`` (oracle), ``scipy``
    (paper-faithful), and ``python_loop`` (original-GEE reference).
    ``auto`` picks via the ``repro.core.plan.select_backend`` cost model.
    See ``docs/backends.md`` for the full decision guide.

    >>> import numpy as np
    >>> from repro.graph.containers import edge_list_from_numpy, symmetrize
    >>> edges = symmetrize(edge_list_from_numpy(      # path graph 0-1-2
    ...     np.array([0, 1]), np.array([1, 2]), None, 3))
    >>> z = gee(edges, np.array([0, 1, -1], np.int32), 2)
    >>> z.shape                  # one embedding row per node, K columns
    (3, 2)
    >>> np.asarray(z)[0].tolist()  # node 0 sees neighbor 1 (class 1, n_1=1)
    [0.0, 1.0]
    """
    from repro.core.plan import GEEPlan    # deferred: plan builds on gee

    return GEEPlan.build(edges, num_classes, opts,
                         backend=backend).execute(labels)
