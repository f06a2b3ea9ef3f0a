"""Pallas TPU kernel: GEE sparse matmul as a masked dense contraction.

TPU adaptation of the paper's CSR SpMM (DESIGN.md section 2, tier 2): CSR's
pointer-walk is serial and gather-heavy -- hostile to the MXU.  We re-block
the sparse structure as fixed-width ELL tiles and turn the scatter into a
batched matvec:

    z[r, k] = sum_d contrib[r, d] * onehot(ylab[r, d])[k]

Per grid step the kernel loads one (ROWS x DEG) tile of neighbor classes
(``ylab``, int32) and contributions (``contrib``, f32) into VMEM, builds the
one-hot mask via an iota comparison (no K-sized table in memory), and
contracts over the degree axis as a batch-over-rows product
``[R, 1, ds] x [R, ds, K] -> [R, 1, K]`` (:func:`contract_tile`).  The unit
middle dim is what lets Mosaic lower it: a 2-D ``[R, ds]`` lhs batched over
rows would keep no non-contracting dim, which Mosaic's matmul cannot
express.  The product runs at f32 precision (``Precision.HIGHEST``) so the
contributions are never rounded to bf16.  The K axis is padded to the
128-lane boundary.

Grid: (row_tiles, deg_tiles); the output block is revisited along the degree
axis (accumulate pattern: initialize at j == 0, add afterwards).  A degree
block is either the whole (padded) plane width or a multiple of 128 lanes,
the two shapes Mosaic accepts for a last block dim (:func:`lane_block`).

VMEM budget per step (defaults ROWS=256, DEG=128, K<=128):
  ylab 256*128*4 = 128 KiB, contrib 128 KiB, out 256*128*4 = 128 KiB
  ->  < 0.5 MiB of ~16 MiB VMEM; the one-hot [ROWS, DEG, K] f32
  intermediate is 256*128*128*4 = 16 MiB worst case, so the kernel
  contracts in DEG-sub-chunks (``deg_sub``) to keep live state small.

On CPU the kernel runs in Pallas interpret mode (tests only); on TPU it
compiles to a Mosaic ``tpu_custom_call`` (``tests/test_tpu_compile.py``
compiles it for a described v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.autotune import (REGISTRY, ceil_to, measure_enabled,
                                    pow2_at_least, pow2_bucket)
from repro.kernels.platform import interpret_mode

LANE = 128          # TPU lane width: last-dim alignment unit
SUBLANE = 8         # f32 sublane height
# cap for the [rows, deg_sub, K_pad] f32 one-hot of one contraction chunk;
# at 2 MiB every resolved tile compiles for v5e within its scoped VMEM
_ONEHOT_BUDGET = 2 * 1024 * 1024
_DEG_SUB = 32       # slots per chunk: fewer chunks compile faster

# Deprecated aliases: these helpers moved to ``repro.kernels.autotune``
# (``ceil_to`` / ``pow2_at_least``); kept so external callers of the old
# private names keep working.
_ceil_to = ceil_to
_pow2_at_least = pow2_at_least


# ---------------------------------------------------------------------------
# block-size autotuning (via the shared repro.kernels.autotune registry)
# ---------------------------------------------------------------------------
#
# Keyed on pow2-bucketed (N, max degree, K) so the cache stays tiny across a
# sweep of graph sizes.  Recorded on-device measurements win; otherwise the
# VMEM-budget formula below decides.  Entries are
# (n_bucket, deg_bucket, k_bucket) -> (block_rows, block_deg, deg_sub).


def choose_block_sizes(n: int, max_degree: int,
                       num_classes: int) -> tuple[int, int, int]:
    """Heuristic (block_rows, block_deg, deg_sub) for a [n, max_degree] plane.

    Resolved through the shared ``repro.kernels.autotune.REGISTRY``
    (memoized per pow2 bucket of the key, so a sweep over many graph sizes
    stays within a handful of cache entries): recorded measurements win,
    then the VMEM-budget formula.  The result is clamped so tiles never
    exceed the actual (padded) plane (:func:`clamp_blocks`).

    With ``REPRO_AUTOTUNE_MEASURE=1``, an unrecorded key first runs the
    on-device measured search (:func:`measured_block_search`); the winner
    lands in the registry's recorded tier (and the
    ``REPRO_AUTOTUNE_CACHE`` file, if set), so it is timed exactly once
    per key per cache lifetime.
    """
    key = pow2_bucket(n, max_degree, num_classes)
    if measure_enabled() and key not in REGISTRY.recorded(KERNEL_NAME):
        measured_block_search(n, max_degree, num_classes)
    return clamp_blocks(*REGISTRY.lookup(KERNEL_NAME, key), n, max_degree)


def clamp_blocks(block_rows: int, block_deg: int, deg_sub: int, n: int,
                 width: int) -> tuple[int, int, int]:
    """Clamp a (block_rows, block_deg, deg_sub) tile to an [n, width] plane
    in a shape Mosaic accepts: rows to the 8-row padded height, and the
    degree block either to the whole 8-padded width or to a multiple of
    128 lanes (a narrower block over a wider plane is refused)."""
    block_rows = min(block_rows, ceil_to(max(n, 1), SUBLANE))
    block_deg = min(ceil_to(block_deg, LANE), ceil_to(max(width, 1), SUBLANE))
    return block_rows, block_deg, min(deg_sub, block_deg)


def _budget_rows(deg_sub: int, k: int, cap: int = 128) -> int:
    """Most rows (a multiple of 8, at most ``cap``) whose [rows, deg_sub,
    K_pad] one-hot fits ``_ONEHOT_BUDGET``."""
    rows = _ONEHOT_BUDGET // (deg_sub * ceil_to(max(k, 1), LANE) * 4)
    return max(SUBLANE, min(cap, rows) // SUBLANE * SUBLANE)


def _block_sizes_formula(key: tuple[int, ...]) -> tuple[int, int, int]:
    """VMEM-budget fallback on pow2-bucketed (N, D, K): degree tiles stop
    at one LANE, chunks of ``_DEG_SUB`` slots, and row tiles (at most 128)
    sized so the [rows, deg_sub, K] one-hot stays under _ONEHOT_BUDGET."""
    n_b, d_b, k_b = key
    block_deg = min(LANE, ceil_to(d_b, SUBLANE))
    deg_sub = min(_DEG_SUB, block_deg)
    block_rows = min(_budget_rows(deg_sub, k_b), ceil_to(n_b, SUBLANE))
    return block_rows, block_deg, deg_sub


KERNEL_NAME = "gee_spmm"
REGISTRY.register(KERNEL_NAME, fallback=_block_sizes_formula)


def _choose_block_sizes_bucketed(n_b: int, d_b: int,
                                 k_b: int) -> tuple[int, int, int]:
    """Deprecated: resolve through ``repro.kernels.autotune.REGISTRY``
    (kept so external callers of the old private name keep working)."""
    return REGISTRY.lookup(KERNEL_NAME, (n_b, d_b, k_b))


# ---------------------------------------------------------------------------
# on-device measured search (opt-in via REPRO_AUTOTUNE_MEASURE=1)
# ---------------------------------------------------------------------------

# the candidate ladder the measured search sweeps, before clamping (rows
# are cut to the one-hot budget for the key's K)
_CANDIDATE_LADDER = ((64, 128, 32), (128, 128, 32), (128, 128, 16),
                     (64, 256, 32), (128, 256, 32))

# synthetic operand caps: candidates rank the same on an 8k-row slice of a
# huge bucket, and timing 7 shapes on the full plane would dwarf the run
# the tuning is meant to speed up
_MEASURE_MAX_ROWS = 8192
_MEASURE_MAX_DEG = 1024


def candidate_blocks(key: tuple[int, ...],
                     registry=REGISTRY, kernel: str = None
                     ) -> list[tuple[int, int, int]]:
    """The measured search's candidate set for one pow2-bucketed key:
    the current registry resolution first (so a recorded winner can only
    beat or match what the formula would have picked), the formula, then
    the ladder -- all clamped to the bucketed plane and deduplicated
    preserving order (ties break toward the front)."""
    n_b, d_b, k_b = key
    raw = [tuple(registry.lookup(kernel or KERNEL_NAME, key)),
           _block_sizes_formula(key)]
    raw += [(min(br, _budget_rows(ds, k_b, cap=br)), bd, ds)
            for br, bd, ds in _CANDIDATE_LADDER]
    out: list[tuple[int, int, int]] = []
    for br, bd, ds in raw:
        c = clamp_blocks(br, bd, ds, n_b, d_b)
        if c not in out:
            out.append(c)
    return out


def _synthetic_planes(n_b: int, d_b: int, k_b: int):
    """Deterministic (ylab, contrib) planes shaped like one bucket: every
    slot live with a rotating class label, so the kernel does full work
    (an all-padding plane would time the skip path, not the contraction)."""
    import numpy as np

    rows = min(n_b, _MEASURE_MAX_ROWS)
    deg = min(d_b, _MEASURE_MAX_DEG)
    lab = (np.arange(rows * deg, dtype=np.int64) * 7919) % max(k_b, 1)
    ylab = jnp.asarray(lab.reshape(rows, deg), jnp.int32)
    contrib = jnp.ones((rows, deg), jnp.float32)
    return ylab, contrib


def _spmm_measure_runner(ylab, contrib, num_classes, interpret):
    def run(cand):
        br, bd, ds = cand
        return gee_spmm(ylab, contrib, num_classes, block_rows=br,
                        block_deg=bd, deg_sub=ds, interpret=interpret)
    return run


def measured_block_search(n: int, max_degree: int, num_classes: int, *,
                          kernel: str = KERNEL_NAME,
                          runner_factory=_spmm_measure_runner,
                          registry=REGISTRY, warmup: int = 1,
                          repeats: int = 3, interpret: bool | None = None):
    """Time the candidate block shapes on synthetic planes of this key's
    bucketed shape and record the winner in ``registry``.

    Returns ``(winner, {candidate: seconds})``; a key already in the
    recorded tier returns instantly with empty timings (the determinism
    contract of ``AutotuneRegistry.measured_search``).  ``kernel`` /
    ``runner_factory`` let the fused kernel reuse the same sweep with its
    own launch.
    """
    key = pow2_bucket(n, max_degree, num_classes)
    n_b, d_b, k_b = key
    if interpret is None:
        interpret = interpret_mode()
    cands = candidate_blocks(key, registry=registry, kernel=kernel)
    ylab, contrib = _synthetic_planes(n_b, d_b, k_b)
    runner = runner_factory(ylab, contrib, k_b, interpret)
    return registry.measured_search(kernel, key, cands, runner,
                                    warmup=warmup, repeats=repeats)


def contract_tile(ylab, contrib, num_classes_pad: int, deg_sub: int):
    """``sum_d contrib[r, d] * [ylab[r, d] == k]`` for one [R, D] tile,
    as an [R, K_pad] f32 block.

    The degree axis is contracted ``deg_sub`` slots at a time so the
    [R, deg_sub, K_pad] one-hot stays small.  Each chunk is a batch-over-
    rows product with a unit middle dim, ``[R, 1, ds] x [R, ds, K]``,
    which Mosaic lowers (a bare [R, ds] lhs would have no non-contracting
    dim); HIGHEST precision keeps the f32 contributions exact.
    """
    rows, deg = ylab.shape
    acc = jnp.zeros((rows, num_classes_pad), jnp.float32)
    for d0 in range(0, deg, deg_sub):
        ds = min(deg_sub, deg - d0)          # final chunk may be ragged
        yl = ylab[:, d0:d0 + ds]                               # [R, ds]
        cb = contrib[:, d0:d0 + ds]                            # [R, ds]
        iota = jax.lax.broadcasted_iota(
            jnp.int32, (rows, ds, num_classes_pad), 2)
        onehot = (yl[:, :, None] == iota).astype(jnp.float32)  # [R, ds, K]
        acc = acc + jnp.einsum(
            "rqd,rdk->rqk", cb[:, None, :], onehot,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)[:, 0, :]
    return acc


def _gee_spmm_kernel(ylab_ref, contrib_ref, out_ref, *, num_classes_pad: int,
                     deg_sub: int):
    """One (row_tile, deg_tile) step: out[r, k] += sum_d c[r,d]*[ylab==k]."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += contract_tile(ylab_ref[...], contrib_ref[...],
                                  num_classes_pad, deg_sub)


def gee_spmm(ylab: jax.Array, contrib: jax.Array, num_classes: int,
             block_rows: int | None = 256, block_deg: int | None = 128,
             deg_sub: int | None = 8,
             interpret: bool | None = None) -> jax.Array:
    """ELL GEE contraction.  ylab [N, D] int32 (-1 pad), contrib [N, D] f32.

    Returns [N, num_classes] f32.  Padding slots (ylab == -1) match no class
    and contribute exactly 0, so padded and unpadded inputs agree bitwise.
    Pass ``None`` for any block size to let ``choose_block_sizes`` pick it
    from the (N, max degree, K) heuristic table; ``interpret=None`` follows
    :func:`repro.kernels.platform.interpret_mode`.
    """
    if interpret is None:
        interpret = interpret_mode()
    n, d = ylab.shape
    if block_rows is None or block_deg is None or deg_sub is None:
        auto = choose_block_sizes(n, d, num_classes)
        block_rows = auto[0] if block_rows is None else block_rows
        block_deg = auto[1] if block_deg is None else block_deg
        deg_sub = auto[2] if deg_sub is None else deg_sub
    return _gee_spmm_jit(ylab, contrib, num_classes, block_rows, block_deg,
                         deg_sub, interpret)


@functools.partial(jax.jit, static_argnames=("num_classes", "block_rows",
                                             "block_deg", "deg_sub",
                                             "interpret"))
def _gee_spmm_jit(ylab: jax.Array, contrib: jax.Array, num_classes: int,
                  block_rows: int, block_deg: int, deg_sub: int,
                  interpret: bool) -> jax.Array:
    n, d = ylab.shape
    k_pad = _ceil_to(max(num_classes, 1), LANE)
    n_pad = _ceil_to(max(n, 1), block_rows)
    d_pad = _ceil_to(max(d, 1), block_deg)
    deg_sub = min(deg_sub, d_pad)

    ylab_p = jnp.full((n_pad, d_pad), -1, jnp.int32)
    ylab_p = ylab_p.at[:n, :d].set(ylab.astype(jnp.int32))
    contrib_p = jnp.zeros((n_pad, d_pad), jnp.float32)
    contrib_p = contrib_p.at[:n, :d].set(contrib.astype(jnp.float32))

    grid = (n_pad // block_rows, d_pad // block_deg)
    out = pl.pallas_call(
        functools.partial(_gee_spmm_kernel, num_classes_pad=k_pad,
                          deg_sub=deg_sub),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_deg), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_deg), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, k_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        interpret=interpret,
    )(ylab_p, contrib_p)
    return out[:n, :num_classes]
