"""The Pallas backend's fit: ELL contraction + diag-aug + row-norm in VMEM.

One-Hot GEE (arXiv 2109.13098) shows the method is memory-bandwidth-bound
at scale and Edge-Parallel GEE (arXiv 2402.04403) that the scatter is the
only stage needing global memory -- so this module fuses the whole O(N*K)
epilogue into the scatter's resident output tile, and never materializes
an un-normalized [N, K] embedding:

  * the contraction is ``_gee_spmm_kernel``'s own
    (:func:`repro.kernels.gee_spmm.contract_tile`), on (ylab, contrib)
    planes that either arrive from HBM, made by XLA's gather
    ``labels[cols]`` (:func:`repro.graph.ell.ell_planes`), or are made in
    VMEM from ``cols``/``vals`` and a packed label table the kernel holds
    resident (the lookup route, chosen from N and K by
    :func:`lookup_routes`);
  * at the *last* degree tile of each row tile -- while the output block
    is still in VMEM -- the kernel adds the diagonal-augmentation term
    ``z[i, y_i] += dinv_i^2 * winv[y_i]`` (the streaming backends' trick
    from ``repro.core.epilogue.diag_aug_epilogue``: degrees get +1, no
    self-loop edges are ever packed) and row-L2-normalizes with the
    shared ``EPS_NORM`` clamp.  Either step is static: a fit without
    diag-aug passes an empty ``rowlab``, one without correlation skips
    the norm, so one driver serves all 8 option settings.

The numerics are the ones in :mod:`repro.core.epilogue` verbatim
(``tests/test_fused_differential.py`` holds the fit to ``gee_scipy`` to
<= 1e-5 under all 8 option settings).

Degree-0 rows appear in *no* ELL bucket (see ``repro.graph.ell``), so a
per-bucket fused launch can never visit them; when a packing has any
(a count known when its scaling is built), ``gee_fused_from_bucketed``
gives those rows the identical shared-epilogue arithmetic applied to
zero rows.  On CPU the kernels run in interpret mode
(:mod:`repro.kernels.platform`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.epilogue import EPS_NORM, apply_epilogue, inv_sqrt_degrees
from repro.core.gee import GEEOptions, class_weight_inv
from repro.graph.ell import BucketedELL, ell_planes
from repro.kernels.autotune import REGISTRY, ceil_to, pow2_bucket
from repro.kernels.gee_spmm import (LANE, _block_sizes_formula, clamp_blocks,
                                    contract_tile, measured_block_search,
                                    measure_enabled)
from repro.kernels.platform import interpret_mode
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

KERNEL_NAME = "gee_spmm_fused"
# The fused kernel's tile geometry matches gee_spmm (the epilogue adds no
# VMEM-resident operand bigger than the output block itself), so it shares
# the formula; measured entries are recorded under its own name so
# on-device search can diverge where the epilogue tail matters.
REGISTRY.register(KERNEL_NAME, fallback=_block_sizes_formula)


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

def _fused_step(planes, rowlab_ref, dadd_ref, out_ref, *,
                num_classes_pad: int, deg_sub: int, diag_aug: bool,
                correlation: bool, eps: float):
    """One (row_tile, deg_tile) step of either fused kernel: contract the
    tile's (ylab, contrib) planes, which ``planes()`` yields, into the
    resident output block; the epilogue runs at the last deg tile.

    Padding lanes k in [K, K_pad) stay exactly zero -- neighbor classes
    and row labels both live in [-1, K), so neither the scatter nor the
    diag-aug term can touch them; the row norm over K_pad therefore
    equals the norm over K.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ylab, contrib = planes()
    out_ref[...] += contract_tile(ylab, contrib, num_classes_pad, deg_sub)

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


def _gee_fused_kernel(ylab_ref, contrib_ref, rowlab_ref, dadd_ref, out_ref,
                      **step):
    """The gather route's kernel: the planes arrive from HBM."""
    _fused_step(lambda: (ylab_ref[...], contrib_ref[...]), rowlab_ref,
                dadd_ref, out_ref, **step)


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
    return gee_spmm_fused_padded(
        ylab, contrib, rowlab, dadd, num_classes, correlation=correlation,
        block_rows=block_rows, block_deg=block_deg, deg_sub=deg_sub,
        interpret=interpret)[:, :num_classes]


def gee_spmm_fused_padded(ylab: jax.Array, contrib: jax.Array,
                          rowlab: jax.Array, dadd: jax.Array,
                          num_classes: int, *, correlation: bool = True,
                          block_rows: int | None = None,
                          block_deg: int | None = None,
                          deg_sub: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """:func:`gee_spmm_fused` with the kernel's lane padding kept:
    [N, K_pad] f32, ``K_pad`` the 128-multiple above ``num_classes``,
    lanes ``K..K_pad`` exactly zero.  A caller that moves whole rows
    moves them lane-aligned and slices the K lanes once."""
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
    call = pl.pallas_call(
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
        name=KERNEL_NAME,
    )
    with jax.named_scope(KERNEL_NAME):
        out = call(ylab_p, contrib_p, rowlab_p, dadd_p)
    return out[:n]


# ---------------------------------------------------------------------------
# the in-kernel label lookup: a packed label table held in VMEM
# ---------------------------------------------------------------------------
#
# The gather route reads each slot's neighbour label with an XLA gather
# ``labels[cols]`` over every ELL slot, which a v5e runs at a rate set by
# the count of indices, and writes the planes to HBM for the kernel to read
# back.  The lookup route hands the kernel ``cols`` and ``vals`` instead,
# with the labels packed into a small int32 table that stays in VMEM: a
# slot's word is found by a scan over the table's rows, one lane gather
# (``take_along_axis`` -> ``tpu.dynamic_gather``) and one select a row.
# The scan costs a few vector ops a table row for every 1,024 slots, so it
# wins only while the table is small; the route is chosen per fit from N
# and K (:func:`lookup_routes`).

LOOKUP_KERNEL_NAME = "gee_spmm_fused_lookup"
# Most table rows whose scan beats the XLA gather: on a TPU v5e the two
# cross near 1,820 rows at K = 5 and K = 47 (benchmarks/bench_label_lookup.py;
# PERF.md section 6).
LOOKUP_MAX_TABLE_ROWS = 1800
# table rows a step of the scan's loop takes (8 ran 1.3x faster than 1 at
# 91 rows, 1.6x at 977, on a v5e; Mosaic unrolls a loop only wholly)
_LOOKUP_UNROLL = 8


def label_code_bits(num_classes: int) -> int:
    """Bits a packed label code takes: codes are ``y + 1`` (0 = unlabelled),
    so 4 bits hold K <= 15 and 8 bits K <= 255; 0 when K is too large to
    pack."""
    if num_classes <= 15:
        return 4
    return 8 if num_classes <= 255 else 0


def label_table_rows(num_nodes: int, num_classes: int) -> int:
    """Rows of the [T, 128] int32 table that packs ``num_nodes`` codes
    (0 when K cannot be packed)."""
    bits = label_code_bits(num_classes)
    if not bits:
        return 0
    return -(-max(num_nodes, 1) // (LANE * (32 // bits)))


def lookup_routes(num_nodes: int, num_classes: int,
                  block_degs) -> Tuple[bool, ...]:
    """Per bucket, whether its labels are looked up in the kernel (True)
    or gathered by XLA (False): in the kernel when the packed table has at
    most :data:`LOOKUP_MAX_TABLE_ROWS` rows and the bucket's degree block
    is whole 128-lane vregs (a lane gather narrower than a vreg does not
    lower)."""
    rows = label_table_rows(num_nodes, num_classes)
    small = 0 < rows <= LOOKUP_MAX_TABLE_ROWS
    return tuple(small and bd % LANE == 0 for bd in block_degs)


def label_table(labels: jax.Array, num_classes: int) -> jax.Array:
    """Pack labels in [-1, K) into the [T, 128] int32 table the lookup
    kernel reads: code ``y + 1`` (0 = unlabelled), ``32 // bits`` codes a
    word.  Vertex v sits at row ``v >> (7 + log2(codes a word))``, lane
    ``v & 127``, field ``(v >> 7) mod codes a word``."""
    bits = label_code_bits(num_classes)
    per_word = 32 // bits
    rows = label_table_rows(labels.shape[0], num_classes)
    codes = jnp.where(labels >= 0, labels + 1, 0).astype(jnp.int32)
    codes = jnp.pad(codes, (0, rows * per_word * LANE - labels.shape[0]))
    codes = codes.reshape(rows, per_word, LANE)
    word = codes[:, 0]
    for f in range(1, per_word):
        word = word | (codes[:, f] << (f * bits))
    return word


def _scan_table(table_ref, rows, lanes):
    """``table[row, lane]`` element-wise, for each pair of [R, 128] arrays
    in ``rows`` and ``lanes``: one lane gather and one select a table row
    for each pair, every pair sharing the row's load and broadcast (a
    one-row table needs neither loop nor select)."""
    def hits(t):
        src = jnp.broadcast_to(table_ref[pl.ds(t, 1), :], lanes[0].shape)
        return [jnp.take_along_axis(src, lane, axis=1,
                                    mode="promise_in_bounds")
                for lane in lanes]

    def scan(t0, count, accs):          # table rows t0 .. t0 + count - 1
        for i in range(count):
            accs = tuple(jnp.where(row == t0 + i, hit, acc)
                         for row, hit, acc in zip(rows, hits(t0 + i), accs))
        return accs

    nrows, u = table_ref.shape[0], _LOOKUP_UNROLL
    if nrows == 1:
        return hits(0)
    accs = jax.lax.fori_loop(
        0, nrows // u, lambda i, accs: scan(i * u, u, accs),
        tuple(jnp.zeros(lane.shape, table_ref.dtype) for lane in lanes))
    return list(scan(nrows - nrows % u, nrows % u, accs))


def _lookup_planes(cols, vals, table_ref, winv_ref, *, num_nodes: int,
                   code_bits: int):
    """The (ylab, contrib) planes of [R, 128] pieces of ``cols`` and
    ``vals``, as :func:`repro.graph.ell.ell_planes` makes them: a slot
    counts iff its weight is nonzero and its neighbour is labelled, with
    ``w * winv[y]``."""
    per_word_log2 = (32 // code_bits).bit_length() - 1
    shr = jax.lax.shift_right_logical
    cols = [jnp.clip(c, 0, num_nodes - 1) for c in cols]
    words = _scan_table(table_ref, [shr(c, 7 + per_word_log2) for c in cols],
                        [c & (LANE - 1) for c in cols])
    codes = [shr(w, (shr(c, 7) & ((1 << per_word_log2) - 1)) * code_bits)
             & ((1 << code_bits) - 1) for w, c in zip(words, cols)]
    ys = [jnp.maximum(code - 1, 0) for code in codes]
    winv = _scan_table(winv_ref, [shr(y, 7) for y in ys],
                       [y & (LANE - 1) for y in ys])
    planes = []
    for code, v, w in zip(codes, vals, winv):
        valid = (v != 0) & (code > 0)
        planes.append((jnp.where(valid, code - 1, -1),
                       jnp.where(valid, v * w, 0.0).astype(jnp.float32)))
    return planes


def _gee_lookup_kernel(cols_ref, vals_ref, table_ref, winv_ref, rowlab_ref,
                       dadd_ref, out_ref, *, num_nodes: int, code_bits: int,
                       **step):
    """The lookup route's kernel: a tile's planes are made in VMEM from
    ``cols``, ``vals`` and the resident label table, by one scan of the
    table for the whole tile (a scan of a 128 x 128 tile ran 5x faster
    than one 8-row vreg a scan on a v5e)."""
    def planes():
        pieces = range(0, cols_ref.shape[1], LANE)
        made = _lookup_planes([cols_ref[:, d:d + LANE] for d in pieces],
                              [vals_ref[:, d:d + LANE] for d in pieces],
                              table_ref, winv_ref, num_nodes=num_nodes,
                              code_bits=code_bits)
        return tuple(jnp.concatenate([p[i] for p in made], axis=1)
                     for i in (0, 1))

    _fused_step(planes, rowlab_ref, dadd_ref, out_ref, **step)


@functools.partial(jax.jit, static_argnames=(
    "num_nodes", "num_classes", "correlation", "diag_aug", "block_rows",
    "block_deg", "deg_sub", "interpret"))
def gee_lookup_fused_padded(cols, vals, table, winv, rowlab, dadd, *,
                            num_nodes: int, num_classes: int,
                            correlation: bool, diag_aug: bool,
                            block_rows: int, block_deg: int, deg_sub: int,
                            interpret: bool) -> jax.Array:
    """The fused kernel on one bucket's ``cols``/``vals`` planes with the
    label lookup inside: ``table`` from :func:`label_table` over
    ``num_nodes`` labels, ``winv`` the [K] class weights, ``rowlab`` and
    ``dadd`` as for :func:`gee_spmm_fused` (ignored unless
    ``diag_aug``).  Returns [R, K_pad] f32, bit for bit
    :func:`gee_spmm_fused_padded` on ``ell_planes(cols, vals, ...)``.

    ``block_deg`` must be a multiple of 128 and divide the plane's width;
    a row count that ``block_rows`` does not divide leaves a ragged last
    row block, whose rows past the plane are never written."""
    r, d = cols.shape
    k_pad = ceil_to(max(num_classes, 1), LANE)
    winv_t = jnp.zeros((k_pad,), jnp.float32).at[:num_classes].set(winv)
    if not diag_aug:
        rowlab = jnp.zeros((r,), jnp.int32)
        dadd = jnp.zeros((r,), jnp.float32)
    call = pl.pallas_call(
        functools.partial(_gee_lookup_kernel,
                          num_nodes=num_nodes,
                          code_bits=label_code_bits(num_classes),
                          num_classes_pad=k_pad, deg_sub=deg_sub,
                          diag_aug=diag_aug, correlation=correlation,
                          eps=EPS_NORM),
        grid=(pl.cdiv(r, block_rows), d // block_deg),
        in_specs=[
            pl.BlockSpec((block_rows, block_deg), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_deg), lambda i, j: (i, j)),
            # constant block index: fetched once, resident across the grid
            pl.BlockSpec(table.shape, lambda i, j: (0, 0)),
            pl.BlockSpec((k_pad // LANE, LANE), lambda i, j: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, k_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, k_pad), jnp.float32),
        interpret=interpret,
        name=LOOKUP_KERNEL_NAME,
    )
    with jax.named_scope(LOOKUP_KERNEL_NAME):
        return call(cols, vals, table,
                    winv_t.reshape(k_pad // LANE, LANE),
                    rowlab.astype(jnp.int32)[:, None],
                    dadd.astype(jnp.float32)[:, None])


# ---------------------------------------------------------------------------
# the fit driver (what the plan layer executes)
# ---------------------------------------------------------------------------

def labels_span(labels, n: int, num_classes: int):
    """The ``plan.labels`` span (tags ``n``, ``k``) of a bucketed fit's
    host label step: the upload of host labels (the fit program computes
    the class weights).  Moves ``plan.labels.vertices`` by ``n`` and, when
    ``labels`` is a host array, ``plan.labels.known`` by its known (>= 0)
    labels; a device array is never read back."""
    reg = obs_metrics.get_registry()
    reg.counter("plan.labels.vertices").inc(n)
    if isinstance(labels, np.ndarray):
        reg.counter("plan.labels.known").inc(
            int(np.count_nonzero(labels >= 0)))
    return obs_trace.span("plan.labels", n=n, k=num_classes)


def _diag_addend(labels, winv, dinv, diag_aug: bool):
    """Per-row (rowlab, dadd) epilogue operands; disabled -> empty/zero."""
    if not diag_aug:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32)
    valid = labels >= 0
    ys = jnp.where(valid, labels, 0)
    dadd = jnp.where(valid, dinv * dinv * winv[ys], 0.0)
    return labels.astype(jnp.int32), dadd.astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class BucketScaling:
    """The label-independent part of a bucketed fit, for one packing and
    one ``(laplacian, diag_aug)`` pair: built once per prepared graph
    (``PreparedGraph.bucket_scaling``), reused by every fit.

    dinv:     [N] f32 ``d^{-1/2}`` of the (loop-augmented) degrees; ones
              when ``laplacian`` is off.
    vals:     per bucket, the Laplacian-scaled ``vals`` plane (the
              bucket's own ``vals`` when ``laplacian`` is off).
    row_dinv: per bucket, ``dinv`` at its ``row_ids`` (0 at the dump row),
              the diag-aug addend's row scale.
    uncovered: [N] bool, the degree-0 rows no bucket holds.
    num_uncovered: their count, from the packing's row counts on the host.
    row_order: [N] int32, each row's position among the buckets' real
              rows taken in bucket order (0 for a degree-0 row): the
              gather that writes the fused fit's Z.
    """

    dinv: jax.Array
    vals: Tuple[jax.Array, ...]
    row_dinv: Tuple[jax.Array, ...]
    uncovered: jax.Array
    num_uncovered: int
    row_order: jax.Array
    laplacian: bool
    diag_aug: bool

    def check(self, opts: GEEOptions) -> None:
        if (self.laplacian, self.diag_aug) != (bool(opts.laplacian),
                                               bool(opts.diag_aug)):
            raise ValueError(
                f"scaling built for laplacian={self.laplacian}, "
                f"diag_aug={self.diag_aug}; options ask {opts.tag()}")


def scale_buckets(bell: BucketedELL, *, laplacian: bool,
                  diag_aug: bool) -> BucketScaling:
    """Build the :class:`BucketScaling` of a packing: the degree fold,
    the degree-0 mask and the row order under ``plan.bucket.degrees``,
    and each bucket's scaled plane under a
    ``plan.bucket.scale`` span tagged with its ``idx``."""
    n = bell.num_nodes
    with obs_trace.span("plan.bucket.degrees", buckets=len(bell.buckets)):
        dinv = jnp.ones((n,), jnp.float32)
        if laplacian:
            # degree = total out-weight per node, assembled across buckets
            deg = jnp.zeros((n + 1,), jnp.float32)
            for b in bell.buckets:
                deg = deg.at[b.row_ids].add(jnp.sum(b.vals, axis=1))
            deg = deg[:n]
            if diag_aug:
                deg = deg + 1.0            # the un-packed self loop
            dinv = inv_sqrt_degrees(deg)
        dinv_ext = jnp.concatenate([dinv, jnp.zeros((1,), jnp.float32)])
        covered = jnp.zeros((n + 1,), bool)
        row_order = jnp.zeros((n + 1,), jnp.int32)
        lo = 0
        for b in bell.buckets:
            covered = covered.at[b.row_ids].set(True)
            row_order = row_order.at[b.row_ids[:b.num_rows]].set(
                jnp.arange(lo, lo + b.num_rows, dtype=jnp.int32))
            lo += b.num_rows
    vals, row_dinv = [], []
    for i, b in enumerate(bell.buckets):
        with obs_trace.span("plan.bucket.scale", idx=i):
            v = b.vals
            if laplacian:
                safe_rows = jnp.minimum(b.row_ids, n - 1)
                v = v * dinv[safe_rows][:, None] \
                      * dinv[jnp.clip(b.cols, 0, n - 1)]
            vals.append(v)
            row_dinv.append(dinv_ext[b.row_ids])
    return BucketScaling(dinv=dinv, vals=tuple(vals),
                         row_dinv=tuple(row_dinv), uncovered=~covered[:n],
                         num_uncovered=n - lo, row_order=row_order[:n],
                         laplacian=bool(laplacian), diag_aug=bool(diag_aug))


def fit_span(bell: BucketedELL, lookup: Tuple[bool, ...]):
    """The ``plan.fit`` span around the dispatch of one fused fit program,
    tagged with the packing's totals -- ``buckets``, packed ``rows``,
    ``slots`` and real ``edges`` -- and the buckets' label ``lookup``
    route (``vmem``, ``gather`` or ``mixed``; :func:`lookup_routes`).
    Moves ``plan.fit.lookup.vmem`` and ``plan.fit.lookup.gather`` by the
    buckets that take each route."""
    vmem = sum(lookup)
    reg = obs_metrics.get_registry()
    reg.counter("plan.fit.lookup.vmem").inc(vmem)
    reg.counter("plan.fit.lookup.gather").inc(len(lookup) - vmem)
    route = ("mixed" if 0 < vmem < len(lookup)
             else "vmem" if vmem else "gather")
    return obs_trace.span(
        "plan.fit", buckets=len(bell.buckets),
        rows=sum(int(b.cols.shape[0]) for b in bell.buckets),
        slots=bell.total_slots, edges=bell.total_edges, lookup=route)


def gee_fused_from_bucketed(bell: BucketedELL, labels: jax.Array,
                            num_classes: int,
                            opts: GEEOptions = GEEOptions(), *,
                            scaling: BucketScaling | None = None,
                            block_rows: int | None = None,
                            block_deg: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Fused GEE from a degree-bucketed packing of the *base* graph.

    The whole fit is one compiled program (:func:`_fused_fit`), dispatched
    once: the label step, per bucket its fused launch, then one write of
    every real row into Z.  Each bucket's neighbour labels are looked up
    inside its kernel from a packed table in VMEM, or gathered by XLA
    into planes first, as :func:`lookup_routes` decides from N, K and the
    bucket's degree block.  Rows are disjoint across buckets,
    so each real row's full contraction -- and therefore its whole
    epilogue -- completes inside a single launch.  Degree-0 rows live in
    no bucket: when the packing has any, they take the shared epilogue
    applied to zero rows (their diag-aug term and row norm).  ``scaling``
    is the packing's label-independent :class:`BucketScaling` (built here
    when absent), so a fit does only label-dependent work.

    The host's label handling runs under :func:`labels_span`, the dispatch
    under :func:`fit_span`; each bucket's device ops carry the named scope
    ``bucket<i>``.  ``plan.fit_program.calls`` counts dispatches and
    ``plan.fit_program.traces`` traces of the program: one per set of
    bucket shapes, options and K, however many embedders and plans fit.
    """
    if interpret is None:
        interpret = interpret_mode()
    if scaling is None:
        scaling = scale_buckets(bell, laplacian=opts.laplacian,
                                diag_aug=opts.diag_aug)
    scaling.check(opts)
    with labels_span(labels, bell.num_nodes, num_classes):
        labels = jnp.asarray(labels, jnp.int32)
    blocks = []
    for b in bell.buckets:
        br, bd, ds = choose_fused_block_sizes(int(b.cols.shape[0]), b.width,
                                              num_classes)
        blocks.append((block_rows if block_rows is not None else br,
                       block_deg if block_deg is not None else bd, ds))
    lookup = lookup_routes(bell.num_nodes, num_classes,
                           [bd for _, bd, _ in blocks])
    obs_metrics.get_registry().counter("plan.fit_program.calls").inc()
    with fit_span(bell, lookup):
        return _fused_fit(
            labels, tuple(b.cols for b in bell.buckets),
            tuple(b.row_ids for b in bell.buckets), scaling.vals,
            scaling.row_dinv, scaling.dinv, scaling.uncovered,
            scaling.row_order, num_classes=num_classes, opts=opts,
            blocks=tuple(blocks), lookup=lookup,
            num_rows=tuple(b.num_rows for b in bell.buckets),
            residual=scaling.num_uncovered > 0, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "num_classes", "opts", "blocks", "lookup", "num_rows", "residual",
    "interpret"))
def _fused_fit(labels, cols, row_ids, vals, row_dinv, dinv, uncovered,
               row_order, *, num_classes: int, opts: GEEOptions, blocks,
               lookup, num_rows, residual: bool,
               interpret: bool) -> jax.Array:
    """The body of one bucketed fit.  The packing's arrays are arguments,
    never closed over, so one executable serves every prepared graph of
    the same bucket shapes.

    Labels of K or more are taken as unlabelled, so both label routes see
    labels in [-1, K).  ``lookup`` (static, one flag a bucket) says which
    buckets look their labels up in the kernel: for those the packed
    table is built once, and no planes are made.

    Z is written once: the buckets' real rows (``num_rows``, static) are
    concatenated lane-padded in bucket order and gathered into vertex
    order by ``row_order``, then sliced to K lanes -- a row gather, where
    a scatter of K-wide rows into Z runs an order of magnitude slower on
    a v5e.  ``residual`` (static) says whether any row lies in no bucket;
    only then does the shared epilogue run for those rows."""
    obs_metrics.get_registry().counter("plan.fit_program.traces").inc()
    n = labels.shape[0]
    with jax.named_scope("labels"):
        labels = jnp.where(labels < num_classes, labels, -1)
        winv = class_weight_inv(labels, num_classes)
        labels_ext = jnp.concatenate(    # dump row n -> label -1 (no-op)
            [labels, jnp.full((1,), -1, jnp.int32)])
        table = label_table(labels, num_classes) if any(lookup) else None
    outs = []
    for i, (c, r, v, rd, (br, bd, ds), vmem, m) in enumerate(
            zip(cols, row_ids, vals, row_dinv, blocks, lookup, num_rows)):
        with jax.named_scope(f"bucket{i}"):
            rowlab, dadd = _diag_addend(labels_ext[r], winv, rd,
                                        opts.diag_aug)
            if vmem:
                out = gee_lookup_fused_padded(
                    c, v, table, winv, rowlab, dadd, num_nodes=n,
                    num_classes=num_classes, correlation=opts.correlation,
                    diag_aug=opts.diag_aug, block_rows=br, block_deg=bd,
                    deg_sub=ds, interpret=interpret)
            else:
                ylab, contrib = ell_planes(c, v, labels, winv)
                out = gee_spmm_fused_padded(
                    ylab, contrib, rowlab, dadd, num_classes,
                    correlation=opts.correlation, block_rows=br,
                    block_deg=bd, deg_sub=ds, interpret=interpret)
            outs.append(out[:m])
    with jax.named_scope("write"):
        if outs:
            z = jnp.concatenate(outs).at[row_order].get(
                mode="promise_in_bounds")[:, :num_classes]
        else:
            z = jnp.zeros((n, num_classes), jnp.float32)
        if residual:
            # degree-0 rows gathered row 0; they owe zeros, the diag-aug
            # term and the row norm: the identical shared-epilogue
            # arithmetic
            z_res = apply_epilogue(jnp.zeros((n, num_classes), jnp.float32),
                                   labels, winv, dinv, opts=opts, impl="jnp")
            z = jnp.where(uncovered[:, None], z_res, z)
    return z


__all__ = ["KERNEL_NAME", "LOOKUP_KERNEL_NAME", "choose_fused_block_sizes",
           "gee_spmm_fused", "gee_spmm_fused_padded",
           "gee_lookup_fused_padded", "label_table", "label_code_bits",
           "label_table_rows", "lookup_routes", "gee_fused_from_bucketed",
           "BucketScaling", "scale_buckets", "labels_span"]
