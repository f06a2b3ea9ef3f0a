"""Fused GEE epilogue megakernel: scatter + diag-aug + row-norm in VMEM.

The staged Pallas path (``repro.kernels.ops``) materializes the full
[N, K] embedding twice: once between the ``gee_spmm`` scatter and the
epilogue (diag-aug fold, row L2 norm), and once more inside the epilogue
itself.  One-Hot GEE (arXiv 2109.13098) shows the method is
memory-bandwidth-bound at scale and Edge-Parallel GEE (arXiv 2402.04403)
that the scatter is the only stage needing global memory -- so this
module fuses the whole O(N*K) epilogue into the scatter's resident
output tile:

  * the contraction is ``_gee_spmm_kernel``'s own
    (:func:`repro.kernels.gee_spmm.contract_tile`);
  * at the *last* degree tile of each row tile -- while the output block
    is still in VMEM -- the kernel adds the diagonal-augmentation term
    ``z[i, y_i] += dinv_i^2 * winv[y_i]`` (the streaming backends' trick
    from ``repro.core.epilogue.diag_aug_epilogue``: degrees get +1, no
    self-loop edges are ever packed) and row-L2-normalizes with the
    shared ``EPS_NORM`` clamp.

The numerics are the ones in :mod:`repro.core.epilogue` verbatim; the
staged path stays untouched as the differential reference
(``tests/test_fused_differential.py`` holds the two to <= 1e-5 under all
8 option settings).

Degree-0 rows appear in *no* ELL bucket (see ``repro.graph.ell``), so a
per-bucket fused launch can never visit them; ``gee_fused_from_bucketed``
applies the identical shared-epilogue arithmetic to those few rows as an
O(#isolated * K) residual fixup.

``REPRO_GEE_FUSED=0/1`` overrides the plan-layer cost model
(``repro.core.plan.select_fused``); unset defers to it.  On CPU the
kernels run in interpret mode (:mod:`repro.kernels.platform`), so the
cost model only selects the fused stage on a TPU.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.epilogue import EPS_NORM, apply_epilogue, inv_sqrt_degrees
from repro.core.gee import GEEOptions, class_weight_inv
from repro.graph.containers import ELL
from repro.graph.ell import BucketedELL, ell_planes
from repro.kernels.autotune import REGISTRY, ceil_to, pow2_bucket
from repro.kernels.gee_spmm import (LANE, _block_sizes_formula, clamp_blocks,
                                    contract_tile, measured_block_search,
                                    measure_enabled)
from repro.kernels.platform import interpret_mode

ENV_FUSED = "REPRO_GEE_FUSED"

KERNEL_NAME = "gee_spmm_fused"
# The fused kernel's tile geometry matches gee_spmm (the epilogue adds no
# VMEM-resident operand bigger than the output block itself), so it shares
# the formula; measured entries are recorded under its own name so
# on-device search can diverge where the epilogue tail matters.
REGISTRY.register(KERNEL_NAME, fallback=_block_sizes_formula)


def fused_override() -> bool | None:
    """The ``REPRO_GEE_FUSED`` env override: True/False when set, None
    when unset (defer to the cost model)."""
    raw = os.environ.get(ENV_FUSED)
    if raw is None or raw == "":
        return None
    return raw not in ("0", "false", "False", "no")


# ---------------------------------------------------------------------------
# block-size selection (shared autotune registry, own kernel name)
# ---------------------------------------------------------------------------

def choose_fused_block_sizes(n: int, max_degree: int,
                             num_classes: int) -> tuple[int, int, int]:
    """(block_rows, block_deg, deg_sub) for the fused kernel: recorded
    measurement > formula, with the opt-in measured search
    (``REPRO_AUTOTUNE_MEASURE=1``) timing candidates through the fused
    kernel itself."""
    key = pow2_bucket(n, max_degree, num_classes)
    if measure_enabled() and key not in REGISTRY.recorded(KERNEL_NAME):
        measured_block_search(
            n, max_degree, num_classes, kernel=KERNEL_NAME,
            runner_factory=_fused_measure_runner)
    return clamp_blocks(*REGISTRY.lookup(KERNEL_NAME, key), n, max_degree)


def _fused_measure_runner(ylab, contrib, num_classes, interpret):
    """Build the measured-search runner: candidate blocks -> one fused
    launch over synthetic planes (rowlab/dadd exercise the epilogue)."""
    n = ylab.shape[0]
    rowlab = jnp.asarray(np.arange(n) % max(num_classes, 1), jnp.int32)
    dadd = jnp.ones((n,), jnp.float32)

    def run(cand):
        br, bd, ds = cand
        return gee_spmm_fused(ylab, contrib, rowlab, dadd, num_classes,
                              correlation=True, block_rows=br, block_deg=bd,
                              deg_sub=ds, interpret=interpret)
    return run


# ---------------------------------------------------------------------------
# the megakernel
# ---------------------------------------------------------------------------

def _gee_fused_kernel(ylab_ref, contrib_ref, rowlab_ref, dadd_ref, out_ref, *,
                      num_classes_pad: int, deg_sub: int, diag_aug: bool,
                      correlation: bool, eps: float):
    """One (row_tile, deg_tile) step; the epilogue runs at the last deg
    tile while the output block is still resident.

    Padding lanes k in [K, K_pad) stay exactly zero -- neighbor classes
    and row labels both live in [-1, K), so neither the scatter nor the
    diag-aug term can touch them; the row norm over K_pad therefore
    equals the norm over K.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += contract_tile(ylab_ref[...], contrib_ref[...],
                                  num_classes_pad, deg_sub)

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        z = out_ref[...]                       # [R, K_pad], fully accumulated
        if diag_aug:
            rowlab = rowlab_ref[...]           # [R, 1] int32, -1 = skip
            dadd = dadd_ref[...]               # [R, 1] f32 (dinv^2 * winv[y])
            kio = jax.lax.broadcasted_iota(
                jnp.int32, (z.shape[0], num_classes_pad), 1)
            z = z + jnp.where(kio == rowlab, dadd, 0.0)
        if correlation:
            norm = jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))
            z = jnp.where(norm > 0, z / jnp.maximum(norm, eps), 0.0)
        out_ref[...] = z


def gee_spmm_fused(ylab: jax.Array, contrib: jax.Array, rowlab: jax.Array,
                   dadd: jax.Array, num_classes: int, *,
                   correlation: bool = True,
                   block_rows: int | None = None,
                   block_deg: int | None = None,
                   deg_sub: int | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """ELL contraction with the epilogue fused into the output tile.

    ``ylab``/``contrib`` are the [N, D] kernel planes of ``ell_planes``;
    ``rowlab`` [N] int32 is each *row's own* label (-1 = no diag term)
    and ``dadd`` [N] f32 the per-row diag-aug addend ``dinv^2 * winv[y]``
    (pass all -1 / zeros to disable diagonal augmentation).  Returns
    [N, num_classes] f32, row-normalized when ``correlation``.
    """
    n, d = ylab.shape
    if interpret is None:
        interpret = interpret_mode()
    if block_rows is None or block_deg is None or deg_sub is None:
        auto = choose_fused_block_sizes(n, d, num_classes)
        block_rows = auto[0] if block_rows is None else block_rows
        block_deg = auto[1] if block_deg is None else block_deg
        deg_sub = auto[2] if deg_sub is None else deg_sub
    diag_aug = bool(rowlab.size)        # static: empty rowlab disables it
    return _gee_fused_jit(ylab, contrib,
                          rowlab if diag_aug else jnp.zeros((n,), jnp.int32),
                          dadd if diag_aug else jnp.zeros((n,), jnp.float32),
                          num_classes, bool(correlation), diag_aug,
                          block_rows, block_deg, deg_sub, interpret)


@functools.partial(jax.jit, static_argnames=(
    "num_classes", "correlation", "diag_aug", "block_rows", "block_deg",
    "deg_sub", "interpret"))
def _gee_fused_jit(ylab, contrib, rowlab, dadd, num_classes: int,
                   correlation: bool, diag_aug: bool, block_rows: int,
                   block_deg: int, deg_sub: int,
                   interpret: bool) -> jax.Array:
    n, d = ylab.shape
    k_pad = ceil_to(max(num_classes, 1), LANE)
    n_pad = ceil_to(max(n, 1), block_rows)
    d_pad = ceil_to(max(d, 1), block_deg)
    deg_sub = min(deg_sub, d_pad)

    ylab_p = jnp.full((n_pad, d_pad), -1, jnp.int32)
    ylab_p = ylab_p.at[:n, :d].set(ylab.astype(jnp.int32))
    contrib_p = jnp.zeros((n_pad, d_pad), jnp.float32)
    contrib_p = contrib_p.at[:n, :d].set(contrib.astype(jnp.float32))
    # per-row epilogue operands, [N_pad, 1] so they block along rows;
    # padding rows carry label -1 / addend 0 (exact epilogue no-ops)
    rowlab_p = jnp.full((n_pad, 1), -1, jnp.int32)
    rowlab_p = rowlab_p.at[:n, 0].set(rowlab.astype(jnp.int32))
    dadd_p = jnp.zeros((n_pad, 1), jnp.float32)
    dadd_p = dadd_p.at[:n, 0].set(dadd.astype(jnp.float32))

    grid = (n_pad // block_rows, d_pad // block_deg)
    out = pl.pallas_call(
        functools.partial(_gee_fused_kernel, num_classes_pad=k_pad,
                          deg_sub=deg_sub, diag_aug=diag_aug,
                          correlation=correlation, eps=EPS_NORM),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_deg), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_deg), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, k_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        interpret=interpret,
    )(ylab_p, contrib_p, rowlab_p, dadd_p)
    return out[:n, :num_classes]


# ---------------------------------------------------------------------------
# full-pipeline drivers (what the plan layer executes)
# ---------------------------------------------------------------------------

def _diag_addend(labels, winv, dinv, diag_aug: bool):
    """Per-row (rowlab, dadd) epilogue operands; disabled -> empty/zero."""
    if not diag_aug:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32)
    valid = labels >= 0
    ys = jnp.where(valid, labels, 0)
    dadd = jnp.where(valid, dinv * dinv * winv[ys], 0.0)
    return labels.astype(jnp.int32), dadd.astype(jnp.float32)


def gee_fused_from_ell(ell: ELL, labels: jax.Array, num_classes: int,
                       opts: GEEOptions = GEEOptions(), *,
                       block_rows: int | None = None,
                       block_deg: int | None = None,
                       interpret: bool | None = None) -> jax.Array:
    """Fused GEE from a flat ELL packing of the *base* graph (no appended
    self loops: diagonal augmentation folds in as degrees+1 and the
    in-kernel ``dinv^2 * winv[y]`` addend, exactly like the streaming
    backends)."""
    if interpret is None:
        interpret = interpret_mode()
    labels = jnp.asarray(labels, jnp.int32)
    n = ell.num_nodes
    vals, cols = ell.vals, ell.cols
    n_rows = vals.shape[0]                 # row-padded plane height
    winv = class_weight_inv(labels, num_classes)
    labels_rows = jnp.full((n_rows,), -1, jnp.int32).at[:n].set(labels)

    if opts.laplacian:
        deg = jnp.sum(vals, axis=1)        # padding rows -> 0
        if opts.diag_aug:
            deg = deg + 1.0                # the un-packed self loop
        dinv = inv_sqrt_degrees(deg)
        vals = vals * dinv[:, None] * dinv[jnp.clip(cols, 0, n_rows - 1)]
    else:
        dinv = jnp.ones((n_rows,), jnp.float32)

    ylab, contrib = ell_planes(cols, vals, labels, winv)
    rowlab, dadd = _diag_addend(labels_rows, winv, dinv, opts.diag_aug)
    z = gee_spmm_fused(ylab, contrib, rowlab, dadd, num_classes,
                       correlation=opts.correlation, block_rows=block_rows,
                       block_deg=block_deg, interpret=interpret)
    return z[:n]


def gee_fused_from_bucketed(bell: BucketedELL, labels: jax.Array,
                            num_classes: int,
                            opts: GEEOptions = GEEOptions(), *,
                            block_rows: int | None = None,
                            block_deg: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Fused GEE from a degree-bucketed packing of the *base* graph.

    One fused launch per bucket: rows are disjoint across buckets, so
    each real row's full contraction -- and therefore its whole epilogue
    -- completes inside a single launch, and results scatter back with
    ``.set`` (never ``.add``).  Degree-0 rows live in no bucket; the
    residual fixup below applies the shared epilogue arithmetic to them
    host-free in O(#isolated * K).
    """
    if interpret is None:
        interpret = interpret_mode()
    labels = jnp.asarray(labels, jnp.int32)
    n = bell.num_nodes
    winv = class_weight_inv(labels, num_classes)
    labels_ext = jnp.concatenate(        # dump row n -> label -1 (no-op)
        [labels, jnp.full((1,), -1, jnp.int32)])

    if opts.laplacian or opts.diag_aug:
        deg = jnp.zeros((n + 1,), jnp.float32)
        for b in bell.buckets:
            deg = deg.at[b.row_ids].add(jnp.sum(b.vals, axis=1))
        deg = deg[:n]
        if opts.diag_aug:
            deg = deg + 1.0
    if opts.laplacian:
        dinv = inv_sqrt_degrees(deg)
    else:
        dinv = jnp.ones((n,), jnp.float32)
    dinv_ext = jnp.concatenate([dinv, jnp.zeros((1,), jnp.float32)])

    z = jnp.zeros((n + 1, num_classes), jnp.float32)
    for b in bell.buckets:
        vals = b.vals
        if opts.laplacian:
            safe_rows = jnp.minimum(b.row_ids, n - 1)
            vals = vals * dinv[safe_rows][:, None] \
                        * dinv[jnp.clip(b.cols, 0, n - 1)]
        ylab, contrib = ell_planes(b.cols, vals, labels, winv)
        rowlab, dadd = _diag_addend(labels_ext[b.row_ids], winv,
                                    dinv_ext[b.row_ids], opts.diag_aug)
        br, bd, ds = choose_fused_block_sizes(int(b.cols.shape[0]), b.width,
                                              num_classes)
        out = gee_spmm_fused(
            ylab, contrib, rowlab, dadd, num_classes,
            correlation=opts.correlation,
            block_rows=block_rows if block_rows is not None else br,
            block_deg=block_deg if block_deg is not None else bd,
            deg_sub=ds, interpret=interpret)
        # disjoint real rows; bucket-padding rows all target the dump row
        # with all-zero planes and a -1 rowlab, so they write exact zeros
        z = z.at[b.row_ids].set(out)
    z = z[:n]

    # Residual fixup: degree-0 rows (no bucket) still owe the diag-aug
    # term and the row norm -- the identical shared-epilogue arithmetic.
    covered = jnp.zeros((n + 1,), bool)
    for b in bell.buckets:
        covered = covered.at[b.row_ids].set(True)
    uncovered = ~covered[:n]
    if opts.diag_aug or opts.correlation:
        z_res = apply_epilogue(jnp.zeros((n, num_classes), jnp.float32),
                               labels, winv, dinv, opts=opts, impl="jnp")
        z = jnp.where(uncovered[:, None], z_res, z)
    return z


__all__ = ["ENV_FUSED", "KERNEL_NAME", "fused_override",
           "choose_fused_block_sizes", "gee_spmm_fused", "gee_fused_from_ell",
           "gee_fused_from_bucketed"]
