"""Pallas TPU kernels: tiled masked similarity scoring for vertex retrieval.

The search subsystem (``repro.search``) ranks database embeddings against
query embeddings under two metrics:

  l2       s[q, m] = -||q - x_m||^2          (higher = closer)
  cosine   s[q, m] = <q, x_m> / (||q|| ||x_m||), 0 when either norm is 0

Two access patterns cover every retrieval path:

  * ``pairwise_scores``  -- one shared database matrix for all queries.
    Used for brute-force search and for probing the coarse cell centroids.
    The contraction ``q @ x.T`` lands on the MXU one (block_q, block_m)
    tile at a time; the norm terms are lane reductions on the same tiles.
  * ``gathered_scores``  -- per-query candidate matrices (the IVF path:
    each query gathers the members of its probed cells).  The kernel is a
    matvec per query, a multiply and lane sum on the VPU.

Dots run at f32 precision: the l2 scores of near neighbors differ far
below bf16's resolution, so a bf16 MXU pass would reorder them.

Both kernels mask *inside* the kernel: padding / invalid slots (cell-table
``-1`` entries, inactive centroids) score ``NEG_INF`` and therefore never
survive a top-k.  K is padded to the 128-lane boundary with zeros, which
leave dots and norms unchanged, so padded and unpadded inputs agree.

Block sizes are shape-bucketed exactly like ``repro.kernels.gee_spmm``:
a measured table keyed on pow2 buckets of (Q, M, K), with a VMEM-budget
formula fallback, all behind an ``lru_cache`` so a sweep over many batch
shapes stays within a handful of entries.

On CPU the kernels run in interpret mode; ``impl="auto"`` therefore routes
to the pure-JAX fallback (the same dot helpers, tested equivalent) on CPU and
to the kernels on TPU (:func:`repro.kernels.platform.interpret_mode`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.autotune import (REGISTRY, ceil_to, pow2_at_least,
                                    pow2_bucket)
from repro.kernels.platform import interpret_mode

LANE = 128          # TPU lane width: last-dim alignment unit
SUBLANE = 8         # f32 sublane height
NEG_INF = float(np.finfo(np.float32).min)   # masked-slot score (finite, so
                                            # later arithmetic cannot NaN)
_VMEM_BUDGET = 4 * 1024 * 1024   # cap for the [bq, bm, K] gathered candidates
_COS_EPS = 1e-30

METRICS = ("l2", "cosine")

# Deprecated aliases: moved to ``repro.kernels.autotune`` (``ceil_to`` /
# ``pow2_at_least``); kept for external callers of the old private names.
_ceil_to = ceil_to
_pow2_at_least = pow2_at_least


def _check_metric(metric: str):
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}; pick one of {METRICS}")


def _resolve_impl(impl: str) -> str:
    """'auto' -> pallas on TPU, the pure-JAX fallback on CPU (where the
    kernels would run in interpret mode, strictly slower)."""
    if impl == "auto":
        return "jax" if interpret_mode() else "pallas"
    if impl not in ("pallas", "jax"):
        raise ValueError(f"unknown impl {impl!r}; 'auto', 'pallas' or 'jax'")
    return impl


# ---------------------------------------------------------------------------
# block-size autotuning (the shared repro.kernels.autotune registry:
# pow2-bucketed table + budget-formula fallback, memoized + persistable)
# ---------------------------------------------------------------------------

# (q_bucket, m_bucket, k_bucket) -> (block_q, block_m)
_PAIRWISE_TABLE = {
    # centroid probing: tiny M, batch of queries
    (64, 4, 4): (64, 8),
    (256, 4, 4): (128, 8),
    # brute-force scoring against SBM-sized databases, K <= 8
    (256, 1024, 4): (128, 256),
    (256, 16384, 4): (128, 512),
    # wide-K regimes
    (256, 4096, 128): (128, 256),
}

# (q_bucket, m_bucket, k_bucket) -> (block_q, block_m)
_GATHERED_TABLE = {
    # default service batches probing a few hundred candidates
    (64, 256, 4): (16, 256),
    (256, 512, 4): (16, 256),
    (256, 2048, 4): (8, 512),
    # wide-K keeps the 3D candidate block small
    (256, 512, 128): (8, 128),
}


def _pairwise_formula(key: tuple[int, ...]) -> tuple[int, int]:
    q_b, m_b, k_b = key
    # tiles: q [bq, K] + x [bm, K] + out [bq, bm]; K is lane-padded.
    block_q = min(128, ceil_to(q_b, SUBLANE))
    block_m = min(512, ceil_to(m_b, SUBLANE))
    k_pad = ceil_to(k_b, LANE)
    while block_m > SUBLANE and \
            (block_q + block_m) * k_pad * 4 + block_q * block_m * 4 \
            > _VMEM_BUDGET:
        block_m //= 2
    return block_q, max(block_m, SUBLANE)


def _gathered_formula(key: tuple[int, ...]) -> tuple[int, int]:
    q_b, m_b, k_b = key
    k_pad = ceil_to(k_b, LANE)
    block_q = min(16, ceil_to(q_b, SUBLANE))
    block_m = min(512, ceil_to(m_b, LANE))
    while block_m > LANE and block_q * block_m * k_pad * 4 > _VMEM_BUDGET:
        block_m //= 2
    return block_q, max(block_m, SUBLANE)


PAIRWISE_KERNEL = "topk_pairwise"
GATHERED_KERNEL = "topk_gathered"
REGISTRY.register(PAIRWISE_KERNEL, table=_PAIRWISE_TABLE,
                  fallback=_pairwise_formula)
REGISTRY.register(GATHERED_KERNEL, table=_GATHERED_TABLE,
                  fallback=_gathered_formula)


def choose_pairwise_blocks(num_queries: int, num_points: int,
                           dim: int) -> tuple[int, int]:
    """(block_q, block_m) for the shared-database kernel, clamped to the
    actual (padded) operand sizes."""
    bq, bm = REGISTRY.lookup(PAIRWISE_KERNEL,
                             pow2_bucket(num_queries, num_points, dim))
    bq = min(bq, ceil_to(max(num_queries, 1), SUBLANE))
    bm = min(bm, ceil_to(max(num_points, 1), SUBLANE))
    return bq, bm


def choose_gathered_blocks(num_queries: int, num_cand: int,
                           dim: int) -> tuple[int, int]:
    """(block_q, block_m) for the per-query-candidates kernel; the 3D
    [bq, bm, K] candidate block dominates VMEM, so it drives the budget."""
    bq, bm = REGISTRY.lookup(GATHERED_KERNEL,
                             pow2_bucket(num_queries, num_cand, dim))
    bq = min(bq, ceil_to(max(num_queries, 1), SUBLANE))
    bm = min(bm, ceil_to(max(num_cand, 1), SUBLANE))
    return bq, bm


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _scores_from_parts(dot, qn2, xn2, metric: str):
    """Combine the MXU dot tile with the norm reductions.  ``qn2`` [BQ, 1]
    and ``xn2`` [..., BM] broadcast against ``dot`` [..., BQ/BM]."""
    if metric == "l2":
        return 2.0 * dot - qn2 - xn2             # = -||q - x||^2
    denom = jnp.sqrt(qn2) * jnp.sqrt(xn2)
    return jnp.where(denom > 0, dot / jnp.maximum(denom, _COS_EPS), 0.0)


def _pairwise_dot(q, x):
    """[BQ, K] x [BM, K] -> [BQ, BM] at f32 precision: near neighbors differ
    in the fifth decimal of an l2 score, far below bf16's resolution."""
    return jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _gathered_dot(cand, q):
    """[BQ, BM, K] . [BQ, K] -> [BQ, BM], the per-query candidate dot, as
    an f32 multiply and lane sum on the VPU (no MXU rounding)."""
    return jnp.sum(cand * q[:, None, :], axis=2)


def _pairwise_kernel(q_ref, x_ref, valid_ref, out_ref, *, metric: str):
    """One (block_q, block_m) tile of the shared-database score matrix."""
    q = q_ref[...]                               # [BQ, K_pad] f32
    x = x_ref[...]                               # [BM, K_pad] f32
    v = valid_ref[...]                           # [1, BM] f32 (1 = live)
    dot = _pairwise_dot(q, x)
    qn2 = jnp.sum(q * q, axis=1, keepdims=True)  # [BQ, 1]
    xn2 = jnp.sum(x * x, axis=1)[None, :]        # [1, BM]
    s = _scores_from_parts(dot, qn2, xn2, metric)
    out_ref[...] = jnp.where(v > 0, s, NEG_INF)


def _gathered_kernel(cand_ref, q_ref, mask_ref, out_ref, *, metric: str):
    """One (block_q, block_m) tile of per-query candidate scores: a
    matvec per query (:func:`_gathered_dot`)."""
    cand = cand_ref[...]                         # [BQ, BM, K_pad] f32
    q = q_ref[...]                               # [BQ, K_pad] f32
    m = mask_ref[...]                            # [BQ, BM] f32 (1 = live)
    dot = _gathered_dot(cand, q)                 # [BQ, BM]
    qn2 = jnp.sum(q * q, axis=1, keepdims=True)  # [BQ, 1]
    cn2 = jnp.sum(cand * cand, axis=2)           # [BQ, BM]
    s = _scores_from_parts(dot, qn2, cn2, metric)
    out_ref[...] = jnp.where(m > 0, s, NEG_INF)


# ---------------------------------------------------------------------------
# public entry points (pad -> pallas_call / jnp fallback -> slice)
# ---------------------------------------------------------------------------

def pairwise_scores(queries: jax.Array, database: jax.Array,
                    valid: jax.Array | None = None, *, metric: str = "l2",
                    impl: str = "auto", block_q: int | None = None,
                    block_m: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Masked [Q, M] score matrix of ``queries`` [Q, K] against a shared
    ``database`` [M, K].  ``valid`` [M] (bool/float, nonzero = live) masks
    database rows to ``NEG_INF``; ``None`` means all live."""
    _check_metric(metric)
    impl = _resolve_impl(impl)
    q, m = queries.shape[0], database.shape[0]
    if block_q is None or block_m is None:
        auto = choose_pairwise_blocks(q, m, queries.shape[1])
        block_q = auto[0] if block_q is None else block_q
        block_m = auto[1] if block_m is None else block_m
    if valid is None:
        valid = jnp.ones((m,), jnp.float32)
    if interpret is None:
        interpret = interpret_mode()
    if impl == "jax":
        return _pairwise_jax(queries, database, valid, metric)
    return _pairwise_pallas(queries, database, valid, metric, block_q,
                            block_m, interpret)


@functools.partial(jax.jit, static_argnames=("metric",))
def _pairwise_jax(queries, database, valid, metric):
    q = queries.astype(jnp.float32)
    x = database.astype(jnp.float32)
    dot = _pairwise_dot(q, x)
    qn2 = jnp.sum(q * q, axis=1, keepdims=True)
    xn2 = jnp.sum(x * x, axis=1)[None, :]
    s = _scores_from_parts(dot, qn2, xn2, metric)
    return jnp.where(valid.astype(jnp.float32)[None, :] > 0, s, NEG_INF)


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_m",
                                             "interpret"))
def _pairwise_pallas(queries, database, valid, metric, block_q, block_m,
                     interpret):
    q, k = queries.shape
    m = database.shape[0]
    k_pad = _ceil_to(max(k, 1), LANE)
    q_pad = _ceil_to(max(q, 1), block_q)
    m_pad = _ceil_to(max(m, 1), block_m)
    qp = jnp.zeros((q_pad, k_pad), jnp.float32)
    qp = qp.at[:q, :k].set(queries.astype(jnp.float32))
    xp = jnp.zeros((m_pad, k_pad), jnp.float32)
    xp = xp.at[:m, :k].set(database.astype(jnp.float32))
    vp = jnp.zeros((1, m_pad), jnp.float32)
    vp = vp.at[0, :m].set(valid.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_pairwise_kernel, metric=metric),
        grid=(q_pad // block_q, m_pad // block_m),
        in_specs=[
            pl.BlockSpec((block_q, k_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, k_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q_pad, m_pad), jnp.float32),
        interpret=interpret,
    )(qp, xp, vp)
    return out[:q, :m]


def gathered_scores(queries: jax.Array, cand: jax.Array, mask: jax.Array, *,
                    metric: str = "l2", impl: str = "auto",
                    block_q: int | None = None, block_m: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Masked [Q, M] scores of ``queries`` [Q, K] against *per-query*
    candidates ``cand`` [Q, M, K] (the IVF gather).  ``mask`` [Q, M]
    (nonzero = live) sends padding slots to ``NEG_INF``."""
    _check_metric(metric)
    impl = _resolve_impl(impl)
    q, m, k = cand.shape
    if block_q is None or block_m is None:
        auto = choose_gathered_blocks(q, m, k)
        block_q = auto[0] if block_q is None else block_q
        block_m = auto[1] if block_m is None else block_m
    if interpret is None:
        interpret = interpret_mode()
    if impl == "jax":
        return _gathered_jax(queries, cand, mask, metric)
    return _gathered_pallas(queries, cand, mask, metric, block_q, block_m,
                            interpret)


@functools.partial(jax.jit, static_argnames=("metric",))
def _gathered_jax(queries, cand, mask, metric):
    q = queries.astype(jnp.float32)
    c = cand.astype(jnp.float32)
    dot = _gathered_dot(c, q)
    qn2 = jnp.sum(q * q, axis=1, keepdims=True)
    cn2 = jnp.sum(c * c, axis=2)
    s = _scores_from_parts(dot, qn2, cn2, metric)
    return jnp.where(mask.astype(jnp.float32) > 0, s, NEG_INF)


@functools.partial(jax.jit, static_argnames=("metric", "block_q", "block_m",
                                             "interpret"))
def _gathered_pallas(queries, cand, mask, metric, block_q, block_m,
                     interpret):
    q, m, k = cand.shape
    k_pad = _ceil_to(max(k, 1), LANE)
    q_pad = _ceil_to(max(q, 1), block_q)
    m_pad = _ceil_to(max(m, 1), block_m)
    cp = jnp.zeros((q_pad, m_pad, k_pad), jnp.float32)
    cp = cp.at[:q, :m, :k].set(cand.astype(jnp.float32))
    qp = jnp.zeros((q_pad, k_pad), jnp.float32)
    qp = qp.at[:q, :k].set(queries.astype(jnp.float32))
    mp = jnp.zeros((q_pad, m_pad), jnp.float32)
    mp = mp.at[:q, :m].set(mask.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_gathered_kernel, metric=metric),
        grid=(q_pad // block_q, m_pad // block_m),
        in_specs=[
            pl.BlockSpec((block_q, block_m, k_pad), lambda i, j: (i, j, 0)),
            pl.BlockSpec((block_q, k_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, block_m), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q_pad, m_pad), jnp.float32),
        interpret=interpret,
    )(cp, qp, mp)
    return out[:q, :m]


# ---------------------------------------------------------------------------
# fused score-and-top-k (scores never materialize as a [Q, M] matrix)
# ---------------------------------------------------------------------------
#
# The staged serving path computes the full [Q, M] score matrix, writes it
# out, then reads it back for ``masked_topk``.  The fused kernels below keep
# a [Q_tile, k_slots] running top-k (scores + ids) in the revisited output
# block instead: each m-tile's scores merge into it (:func:`_merge_topk`),
# so nothing M-sized ever leaves VMEM.
#
# Mosaic has no ``top_k``, so the merge is a selection loop of ``kk`` steps
# (the live result width): row max, lowest position holding it, record,
# mask.  Tie behavior matches ``masked_topk`` exactly: the running entries
# are searched before the tile and always carry smaller global m (tiles
# arrive in ascending m), and the lowest position wins among equal scores,
# which is ``lax.top_k``'s stable order -- the same order a full-row
# ``top_k`` produces.  The pure-JAX fallback *is* the staged compose
# (scores + masked_topk), so CPU/CI results are identical by construction.


def fused_topk_enabled(impl: str = "auto") -> bool:
    """Whether the serving path should route through the fused kernels:
    ``REPRO_GEE_FUSED`` wins when set; otherwise fused iff the resolved
    impl is ``pallas`` (i.e. a real TPU under ``auto``)."""
    from repro.kernels.gee_fused import fused_override  # deferred: no cycle

    override = fused_override()
    if override is not None:
        return bool(override)
    return _resolve_impl(impl) == "pallas"


def _k_slots(k: int, m: int) -> tuple[int, int]:
    """(kk, k_slots): live result width and its lane-padded kernel width."""
    kk = max(min(int(k), int(m)), 1)
    return kk, ceil_to(kk, LANE)


def _finalize_topk(scores, ids, q: int, kk: int, k: int):
    """Slice kernel output to [Q, kk], apply the masked-slot convention
    (id -1 at NEG_INF scores), pad to k -- ``masked_topk``'s contract."""
    scores = scores[:q, :kk]
    ids = jnp.where(scores > NEG_INF / 2, ids[:q, :kk].astype(jnp.int32), -1)
    if kk < k:
        ids = jnp.concatenate(
            [ids, jnp.full((q, k - kk), -1, jnp.int32)], axis=1)
        scores = jnp.concatenate(
            [scores, jnp.full((q, k - kk), NEG_INF, jnp.float32)], axis=1)
    return ids, scores


def _lowest_lane(hit, lanes):
    """Per row, the lowest lane where ``hit`` holds (``lanes.shape[1]`` if
    none), as an [rows, 1] int32 column."""
    return jnp.min(jnp.where(hit, lanes, lanes.shape[1]), axis=1,
                   keepdims=True)


def _merge_topk(run_s, run_i, tile_s, tile_i, kk: int):
    """Top ``kk`` of the running entries followed by one tile, in
    ``lax.top_k`` order (descending score, lowest position first on ties).

    ``run_*`` are the [R, k_slots] running blocks (ascending global m),
    ``tile_*`` the [R, BM] tile; returns new [R, k_slots] blocks whose
    slots past ``kk`` hold ``NEG_INF`` / -1.  Chosen entries are knocked
    out with -inf, below the finite ``NEG_INF`` sentinel, so each step
    picks a fresh one.
    """
    run_lane = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)
    tile_lane = jax.lax.broadcasted_iota(jnp.int32, tile_s.shape, 1)
    out_s = jnp.full(run_s.shape, NEG_INF, jnp.float32)
    out_i = jnp.full(run_i.shape, -1, jnp.int32)
    for t in range(kk):
        best = jnp.maximum(jnp.max(run_s, axis=1, keepdims=True),
                           jnp.max(tile_s, axis=1, keepdims=True))
        pr = _lowest_lane(run_s == best, run_lane)
        pt = _lowest_lane(tile_s == best, tile_lane)
        from_run = pr < run_s.shape[1]
        pick_r = run_lane == pr
        pick_t = (tile_lane == pt) & ~from_run
        chosen = jnp.where(
            from_run,
            jnp.sum(jnp.where(pick_r, run_i, 0), axis=1, keepdims=True),
            jnp.sum(jnp.where(pick_t, tile_i, 0), axis=1, keepdims=True))
        out_s = jnp.where(run_lane == t, best, out_s)
        out_i = jnp.where(run_lane == t, chosen, out_i)
        run_s = jnp.where(pick_r, -jnp.inf, run_s)
        tile_s = jnp.where(pick_t, -jnp.inf, tile_s)
    return out_s, out_i


def _pairwise_topk_kernel(q_ref, x_ref, valid_ref, scores_ref, ids_ref, *,
                          metric: str, block_m: int, kk: int):
    """One (q_tile, m_tile) step: score the tile, merge into the running
    top-k held in the revisited output blocks."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        scores_ref[...] = jnp.full_like(scores_ref, NEG_INF)
        ids_ref[...] = jnp.full_like(ids_ref, -1)

    q = q_ref[...]                               # [BQ, K_pad] f32
    x = x_ref[...]                               # [BM, K_pad] f32
    v = valid_ref[...]                           # [1, BM] f32
    dot = _pairwise_dot(q, x)
    qn2 = jnp.sum(q * q, axis=1, keepdims=True)
    xn2 = jnp.sum(x * x, axis=1)[None, :]
    s = jnp.where(v > 0, _scores_from_parts(dot, qn2, xn2, metric), NEG_INF)
    tile_ids = j * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    scores_ref[...], ids_ref[...] = _merge_topk(
        scores_ref[...], ids_ref[...], s, tile_ids, kk)


def _gathered_topk_kernel(cand_ref, q_ref, mask_ref, ids_ref, scores_out_ref,
                          ids_out_ref, *, metric: str, kk: int):
    """Gathered-candidate twin: per-query candidate tiles carry their own
    database ids (the IVF table gather), merged the same way."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        scores_out_ref[...] = jnp.full_like(scores_out_ref, NEG_INF)
        ids_out_ref[...] = jnp.full_like(ids_out_ref, -1)

    cand = cand_ref[...]                         # [BQ, BM, K_pad] f32
    q = q_ref[...]                               # [BQ, K_pad] f32
    m = mask_ref[...]                            # [BQ, BM] f32
    tile_ids = ids_ref[...]                      # [BQ, BM] int32
    dot = _gathered_dot(cand, q)
    qn2 = jnp.sum(q * q, axis=1, keepdims=True)
    cn2 = jnp.sum(cand * cand, axis=2)
    s = jnp.where(m > 0, _scores_from_parts(dot, qn2, cn2, metric), NEG_INF)
    scores_out_ref[...], ids_out_ref[...] = _merge_topk(
        scores_out_ref[...], ids_out_ref[...], s, tile_ids, kk)


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_q",
                                             "block_m", "interpret"))
def _pairwise_topk_pallas(queries, database, valid, k, metric, block_q,
                          block_m, interpret):
    q, kdim = queries.shape
    m = database.shape[0]
    kk, k_slots = _k_slots(k, m)
    k_pad = _ceil_to(max(kdim, 1), LANE)
    q_pad = _ceil_to(max(q, 1), block_q)
    m_pad = _ceil_to(max(m, 1), block_m)
    qp = jnp.zeros((q_pad, k_pad), jnp.float32)
    qp = qp.at[:q, :kdim].set(queries.astype(jnp.float32))
    xp = jnp.zeros((m_pad, k_pad), jnp.float32)
    xp = xp.at[:m, :kdim].set(database.astype(jnp.float32))
    vp = jnp.zeros((1, m_pad), jnp.float32)
    vp = vp.at[0, :m].set(valid.astype(jnp.float32))
    scores, ids = pl.pallas_call(
        functools.partial(_pairwise_topk_kernel, metric=metric,
                          block_m=block_m, kk=kk),
        grid=(q_pad // block_q, m_pad // block_m),
        in_specs=[
            pl.BlockSpec((block_q, k_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, k_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k_slots), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k_slots), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k_slots), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k_slots), jnp.int32),
        ],
        interpret=interpret,
    )(qp, xp, vp)
    return _finalize_topk(scores, ids, q, kk, k)


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_q",
                                             "block_m", "interpret"))
def _gathered_topk_pallas(queries, cand, mask, ids, k, metric, block_q,
                          block_m, interpret):
    q, m, kdim = cand.shape
    kk, k_slots = _k_slots(k, m)
    k_pad = _ceil_to(max(kdim, 1), LANE)
    q_pad = _ceil_to(max(q, 1), block_q)
    m_pad = _ceil_to(max(m, 1), block_m)
    cp = jnp.zeros((q_pad, m_pad, k_pad), jnp.float32)
    cp = cp.at[:q, :m, :kdim].set(cand.astype(jnp.float32))
    qp = jnp.zeros((q_pad, k_pad), jnp.float32)
    qp = qp.at[:q, :kdim].set(queries.astype(jnp.float32))
    mp = jnp.zeros((q_pad, m_pad), jnp.float32)
    mp = mp.at[:q, :m].set(mask.astype(jnp.float32))
    ip = jnp.full((q_pad, m_pad), -1, jnp.int32)
    ip = ip.at[:q, :m].set(ids.astype(jnp.int32))
    scores, out_ids = pl.pallas_call(
        functools.partial(_gathered_topk_kernel, metric=metric, kk=kk),
        grid=(q_pad // block_q, m_pad // block_m),
        in_specs=[
            pl.BlockSpec((block_q, block_m, k_pad), lambda i, j: (i, j, 0)),
            pl.BlockSpec((block_q, k_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, block_m), lambda i, j: (i, j)),
            pl.BlockSpec((block_q, block_m), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k_slots), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k_slots), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k_slots), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k_slots), jnp.int32),
        ],
        interpret=interpret,
    )(cp, qp, mp, ip)
    return _finalize_topk(scores, out_ids, q, kk, k)


def scored_topk(queries: jax.Array, database: jax.Array,
                valid: jax.Array | None, k: int, *, metric: str = "l2",
                impl: str = "auto", fused: bool | None = None,
                block_q: int | None = None, block_m: int | None = None,
                interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Top-``k`` of ``queries`` [Q, K] against a shared ``database``
    [M, K]: exactly ``masked_topk(pairwise_scores(...), None, k)``, with
    the [Q, M] score matrix never materialized when the fused kernel
    runs.  ``fused=None`` resolves via :func:`fused_topk_enabled`; the
    fused route needs the pallas impl (pure-JAX callers get the staged
    compose, which is the fallback's definition of correct)."""
    _check_metric(metric)
    resolved = _resolve_impl(impl)
    if fused is None:
        fused = fused_topk_enabled(impl)
    if fused and resolved == "pallas":
        q, m = queries.shape[0], database.shape[0]
        if block_q is None or block_m is None:
            auto = choose_pairwise_blocks(q, m, queries.shape[1])
            block_q = auto[0] if block_q is None else block_q
            block_m = auto[1] if block_m is None else block_m
        if valid is None:
            valid = jnp.ones((m,), jnp.float32)
        if interpret is None:
            interpret = interpret_mode()
        return _pairwise_topk_pallas(queries, database, valid, int(k),
                                     metric, block_q, block_m, interpret)
    scores = pairwise_scores(queries, database, valid, metric=metric,
                             impl=impl, block_q=block_q, block_m=block_m,
                             interpret=interpret)
    return masked_topk(scores, None, int(k))


def scored_topk_gathered(queries: jax.Array, cand: jax.Array,
                         mask: jax.Array, ids: jax.Array, k: int, *,
                         metric: str = "l2", impl: str = "auto",
                         fused: bool | None = None,
                         block_q: int | None = None,
                         block_m: int | None = None,
                         interpret: bool | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """Per-query-candidates twin of :func:`scored_topk` (the IVF path):
    ``masked_topk(gathered_scores(...), ids, k)`` without the [Q, M]
    intermediate on the fused route."""
    _check_metric(metric)
    resolved = _resolve_impl(impl)
    if fused is None:
        fused = fused_topk_enabled(impl)
    if fused and resolved == "pallas":
        q, m, kdim = cand.shape
        if block_q is None or block_m is None:
            auto = choose_gathered_blocks(q, m, kdim)
            block_q = auto[0] if block_q is None else block_q
            block_m = auto[1] if block_m is None else block_m
        if interpret is None:
            interpret = interpret_mode()
        return _gathered_topk_pallas(queries, cand, mask, ids, int(k),
                                     metric, block_q, block_m, interpret)
    scores = gathered_scores(queries, cand, mask, metric=metric, impl=impl,
                             block_q=block_q, block_m=block_m,
                             interpret=interpret)
    return masked_topk(scores, ids, int(k))


def masked_topk(scores: jax.Array, ids: jax.Array | None,
                k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k over the last axis of a masked score matrix.

    Returns ``(ids [Q, k] int32, scores [Q, k] f32)``; slots whose best
    available score is the mask sentinel come back as id ``-1`` with
    ``NEG_INF`` score (fewer than k live candidates).  ``ids=None`` means
    candidate m *is* database row m (the brute-force layout)."""
    q, m = scores.shape
    kk = min(k, m)
    top, pos = jax.lax.top_k(scores, kk)
    out_ids = pos.astype(jnp.int32) if ids is None \
        else jnp.take_along_axis(ids, pos, axis=1).astype(jnp.int32)
    out_ids = jnp.where(top > NEG_INF / 2, out_ids, -1)
    if kk < k:
        pad_i = jnp.full((q, k - kk), -1, jnp.int32)
        pad_s = jnp.full((q, k - kk), NEG_INF, jnp.float32)
        out_ids = jnp.concatenate([out_ids, pad_i], axis=1)
        top = jnp.concatenate([top, pad_s], axis=1)
    return out_ids, top
