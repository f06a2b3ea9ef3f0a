"""Compile-only checks of the main-path Pallas kernels for a described v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (shapes
Mosaic cannot lower, tiles that overflow VMEM).  Shapes are those of the
paper's largest Table-2 graph (``cl-100k-1d8-l5``: 92,482 rows, degree
buckets up to 512 wide, K=5), one wide-K case (K=172), and the ogbn-products
deployment's K=47 at its fullest bucket (1,147,712 rows, 64 wide) and its
widest (8 rows, 65,536 wide); the fused kernel with the label lookup
inside at ``cl-100k-1d8-l5``'s widths and 91-row label table, and at the
largest table its route admits (K=15, 172 and 255).  Each test
asserts the kernel reached the program as a Mosaic ``tpu_custom_call``.

The topology is described only inside the fixture below, never at import,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gee import GEEOptions
from repro.kernels.gee_fused import KERNEL_NAME as FUSED_NAME
from repro.kernels.gee_fused import (LOOKUP_KERNEL_NAME,
                                     LOOKUP_MAX_TABLE_ROWS, _fused_fit)
from repro.kernels.gee_fused import (choose_fused_block_sizes,
                                     gee_lookup_fused_padded, gee_spmm_fused,
                                     label_code_bits, label_table_rows,
                                     lookup_routes)
from repro.kernels.gee_spmm import KERNEL_NAME as STAGED_NAME
from repro.kernels.gee_spmm import LANE, choose_block_sizes, gee_spmm
from repro.kernels.row_norm import row_norm
from repro.kernels.topk_score import (choose_gathered_blocks,
                                      choose_pairwise_blocks, scored_topk,
                                      scored_topk_gathered)

N_ROWS = 92_482          # cl-100k-1d8-l5 node count
BUCKET_WIDTHS = (8, 128, 256, 512)
# (width, rows) of ogbn-products buckets (2,449,029 vertices, K=47)
OGBN_BUCKETS = ((64, 1_147_712), (65_536, 8))
# (k, width, rows), ids "<k>-<width>"
SPMM_CASES = ([pytest.param(k, w, N_ROWS, id=f"{k}-{w}")
               for k in (5, 172) for w in BUCKET_WIDTHS]
              + [pytest.param(47, w, rows, id=f"47-{w}")
                 for w, rows in OGBN_BUCKETS])


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache entirely
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("k,width,rows", SPMM_CASES)
def test_gee_spmm_compiles(one_chip, width, k, rows):
    br, bd, ds = choose_block_sizes(rows, width, k)
    fn = functools.partial(gee_spmm, num_classes=k, block_rows=br,
                           block_deg=bd, deg_sub=ds, interpret=False)
    _compile(fn, one_chip, ((rows, width), jnp.int32),
             ((rows, width), jnp.float32))


@pytest.mark.parametrize("k,width,rows", SPMM_CASES)
def test_gee_spmm_fused_compiles(one_chip, width, k, rows):
    br, bd, ds = choose_fused_block_sizes(rows, width, k)
    fn = functools.partial(gee_spmm_fused, num_classes=k, correlation=True,
                           block_rows=br, block_deg=bd, deg_sub=ds,
                           interpret=False)
    _compile(fn, one_chip, ((rows, width), jnp.int32),
             ((rows, width), jnp.float32), ((rows,), jnp.int32),
             ((rows,), jnp.float32))


@pytest.mark.parametrize("fused", [True, False])
def test_kernel_named_in_compiled_hlo(one_chip, fused):
    """The kernel's custom call carries the kernel's own name inside any
    enclosing jit, so a device trace's op line names it."""
    width, k = 128, 5
    if fused:
        name = FUSED_NAME
        br, bd, ds = choose_fused_block_sizes(N_ROWS, width, k)

        def outer(ylab, contrib, rowlab, dadd):
            return 2.0 * gee_spmm_fused(
                ylab, contrib, rowlab, dadd, k, correlation=True,
                block_rows=br, block_deg=bd, deg_sub=ds, interpret=False)
        shapes = (((N_ROWS, width), jnp.int32),
                  ((N_ROWS, width), jnp.float32),
                  ((N_ROWS,), jnp.int32), ((N_ROWS,), jnp.float32))
    else:
        name = STAGED_NAME
        br, bd, ds = choose_block_sizes(N_ROWS, width, k)

        def outer(ylab, contrib):
            return 2.0 * gee_spmm(ylab, contrib, k, block_rows=br,
                                  block_deg=bd, deg_sub=ds, interpret=False)
        shapes = (((N_ROWS, width), jnp.int32),
                  ((N_ROWS, width), jnp.float32))
    text = _compile(outer, one_chip, *shapes)
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    instr = re.match(r"\s*(?:ROOT )?%?([\w.-]+) = ", calls[0]).group(1)
    assert instr.split(".")[0] == name, instr
    assert f"/{name}/" in re.search(r'op_name="([^"]*)"', calls[0]).group(1)


FIT_BUCKETS = [
    pytest.param(5, ((128, 2_000), (256, 1_000), (8_192, 8)), id="5"),
    pytest.param(47, ((8, 800), (64, 4_000), (8_192, 8)), id="47"),
]


def _compile_fit(one_chip, k, buckets, opts, lookup=None):
    """Compile one bucketed fit program for a described chip, each
    bucket's labels looked up as ``lookup`` says (by default as the
    program would route them); return its text after checking one kernel
    launch per bucket."""
    n = N_ROWS
    blocks = tuple(choose_fused_block_sizes(r, w, k) for w, r in buckets)
    if lookup is None:
        lookup = lookup_routes(n, k, [bd for _, bd, _ in blocks])
    args = [((n,), jnp.int32)]
    args += [((r, w), jnp.int32) for w, r in buckets]      # cols
    args += [((r,), jnp.int32) for _, r in buckets]        # row_ids
    args += [((r, w), jnp.float32) for w, r in buckets]    # scaled vals
    args += [((r,), jnp.float32) for _, r in buckets]      # row_dinv
    args += [((n,), jnp.float32), ((n,), jnp.bool_),      # dinv, uncovered
             ((n,), jnp.int32)]                           # row_order
    nb = len(buckets)

    def fit(labels, *flat):
        cols, rows, vals, rdinv = (tuple(flat[i * nb:(i + 1) * nb])
                                   for i in range(4))
        return _fused_fit(
            labels, cols, rows, vals, rdinv, *flat[-3:], num_classes=k,
            opts=opts, blocks=blocks, lookup=lookup,
            num_rows=tuple(r - 3 for _, r in buckets), residual=True,
            interpret=False)
    text = _compile(fit, one_chip, *args)
    assert text.count('custom_call_target="tpu_custom_call"') == nb
    return text


@pytest.mark.parametrize("k,buckets", FIT_BUCKETS)
def test_fused_fit_program_compiles(one_chip, k, buckets):
    """The whole bucketed fit compiles as one program on the gather
    route: one kernel launch per bucket, and each bucket's label gather
    ``labels[cols]`` compiled exactly once, named by its bucket's scope
    (XLA does not duplicate it into the plane consumers)."""
    text = _compile_fit(one_chip, k, buckets, GEEOptions(
        laplacian=True, diag_aug=True, correlation=True),
        lookup=(False,) * len(buckets))
    gathers = _label_gathers(text)
    for i, (w, r) in enumerate(buckets):
        assert gathers[i].count(r * w) == 1, (i, gathers[i])


def _label_gathers(text):
    """{bucket: [sizes of its s32 gathers]} in a compiled fit program."""
    gathers = {}
    for m in re.finditer(r"= s32\[([\d,]*)\]\S* gather\(.*?"
                         r'op_name="[^"]*/bucket(\d+)/', text):
        size = math.prod(int(x) for x in m.group(1).split(",") if x)
        gathers.setdefault(int(m.group(2)), []).append(size)
    return gathers


# cl-100k-1d8-l5's widths around a hub bucket, K=5: T = 91 table rows
LOOKUP_BUCKETS = ((8, 800), (128, 2_000), (256, 1_000), (512, 1_000),
                  (8_192, 8))


def test_fused_fit_program_lookup_compiles(one_chip):
    """With the route the program takes at ``cl-100k-1d8-l5``'s N and K,
    every bucket of whole 128-lane degree blocks launches the lookup
    kernel and gathers no label into a slot-sized array; the 8-wide
    bucket keeps XLA's gather."""
    k = 5
    assert label_table_rows(N_ROWS, k) == 91
    text = _compile_fit(one_chip, k, LOOKUP_BUCKETS, GEEOptions(
        laplacian=True, diag_aug=True, correlation=True))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = [re.match(r"\s*(?:ROOT )?%?([\w.-]+) = ", c).group(1)
             .split(".")[0] for c in calls]
    assert sorted(names) == sorted([FUSED_NAME] + [LOOKUP_KERNEL_NAME] * 4)
    gathers = _label_gathers(text)
    for i, (w, r) in enumerate(LOOKUP_BUCKETS):
        assert gathers.get(i, []).count(r * w) == int(w < 128), (i, gathers)


NO_EPILOGUE = pytest.mark.parametrize(
    "opts", [GEEOptions(), GEEOptions(laplacian=True)], ids=lambda o: o.tag())


@NO_EPILOGUE
@pytest.mark.parametrize("k,buckets", FIT_BUCKETS)
def test_fit_program_without_epilogue_compiles(one_chip, k, buckets, opts):
    """The settings with neither diag-aug nor correlation run the same
    fit program, with the kernel's epilogue compiled out; Mosaic takes
    that variant too, on the route the program picks at this N (the
    lookup for whole-vreg degree blocks)."""
    _compile_fit(one_chip, k, buckets, opts)


@NO_EPILOGUE
@pytest.mark.parametrize("k,buckets", FIT_BUCKETS)
def test_gather_route_without_epilogue_compiles(one_chip, k, buckets, opts):
    """The same, with every bucket on the gather route, as graphs past
    the lookup's table limit run it."""
    _compile_fit(one_chip, k, buckets, opts, lookup=(False,) * len(buckets))


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
def test_gee_lookup_fused_compiles(one_chip, width):
    """The fused kernel with the label lookup inside compiles at
    ``cl-100k-1d8-l5``'s bucket shapes (K=5, a 91-row table) wherever the
    program routes a bucket to it; the 8-wide bucket, whose degree block
    is narrower than a vreg, is routed to the gather kernel, which
    compiles there."""
    k = 5
    br, bd, ds = choose_fused_block_sizes(N_ROWS, width, k)
    (vmem,) = lookup_routes(N_ROWS, k, [bd])
    assert vmem == (width >= 128)
    if not vmem:
        test_gee_spmm_fused_compiles(one_chip, width, k, N_ROWS)
        return
    fn = functools.partial(gee_lookup_fused_padded, num_nodes=N_ROWS,
                           num_classes=k, correlation=True, diag_aug=True,
                           block_rows=br, block_deg=bd, deg_sub=ds,
                           interpret=False)
    text = _compile(fn, one_chip, ((N_ROWS, width), jnp.int32),
                    ((N_ROWS, width), jnp.float32),
                    ((label_table_rows(N_ROWS, k), 128), jnp.int32),
                    ((k,), jnp.float32), ((N_ROWS,), jnp.int32),
                    ((N_ROWS,), jnp.float32))
    assert f"/{LOOKUP_KERNEL_NAME}/" in text


@pytest.mark.parametrize("width", [128, 512])
@pytest.mark.parametrize("k", [15, 172, 255])
def test_gee_lookup_fused_compiles_at_table_limit(one_chip, k, width):
    """The lookup kernel compiles with the largest table the route admits
    (``LOOKUP_MAX_TABLE_ROWS`` rows, double-buffered in VMEM beside the
    tiles): 4-bit codes at K=15, 8-bit codes at K=172 and at K=255, the
    most an 8-bit code holds."""
    n = LOOKUP_MAX_TABLE_ROWS * LANE * (32 // label_code_bits(k))
    assert label_table_rows(n, k) == LOOKUP_MAX_TABLE_ROWS
    br, bd, ds = choose_fused_block_sizes(N_ROWS, width, k)
    assert lookup_routes(n, k, [bd]) == (True,)
    fn = functools.partial(gee_lookup_fused_padded, num_nodes=n,
                           num_classes=k, correlation=True, diag_aug=True,
                           block_rows=br, block_deg=bd, deg_sub=ds,
                           interpret=False)
    text = _compile(fn, one_chip, ((N_ROWS, width), jnp.int32),
                    ((N_ROWS, width), jnp.float32),
                    ((LOOKUP_MAX_TABLE_ROWS, LANE), jnp.int32),
                    ((k,), jnp.float32), ((N_ROWS,), jnp.int32),
                    ((N_ROWS,), jnp.float32))
    assert f"/{LOOKUP_KERNEL_NAME}/" in text


@pytest.mark.parametrize("k", [5, 172])
def test_row_norm_compiles(one_chip, k):
    fn = functools.partial(row_norm, interpret=False)
    _compile(fn, one_chip, ((N_ROWS, k), jnp.float32))


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_fused_pairwise_topk_compiles(one_chip, metric):
    q, dim, top = 1024, 5, 10
    bq, bm = choose_pairwise_blocks(q, N_ROWS, dim)
    fn = functools.partial(scored_topk, k=top, metric=metric,
                           impl="pallas", fused=True, block_q=bq,
                           block_m=bm, interpret=False)
    _compile(fn, one_chip, ((q, dim), jnp.float32),
             ((N_ROWS, dim), jnp.float32), ((N_ROWS,), jnp.float32))


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_fused_gathered_topk_compiles(one_chip, metric):
    q, m, dim, top = 32, 3 * 19_200, 5, 10   # nprobe 3 of 5 class cells
    bq, bm = choose_gathered_blocks(q, m, dim)
    fn = functools.partial(scored_topk_gathered, k=top, metric=metric,
                           impl="pallas", fused=True, block_q=bq,
                           block_m=bm, interpret=False)
    _compile(fn, one_chip, ((q, dim), jnp.float32),
             ((q, m, dim), jnp.float32), ((q, m), jnp.float32),
             ((q, m), jnp.int32))
