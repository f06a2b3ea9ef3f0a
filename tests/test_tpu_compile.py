"""Compile-only checks of the main-path Pallas kernels for a described v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (shapes
Mosaic cannot lower, tiles that overflow VMEM).  Shapes are those of the
paper's largest Table-2 graph (``cl-100k-1d8-l5``: 92,482 rows, degree
buckets up to 512 wide, K=5) plus one wide-K case (K=172).  Each test
asserts the kernel reached the program as a Mosaic ``tpu_custom_call``.

The topology is described only inside the fixture below, never at import,
so every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gee_fused import choose_fused_block_sizes, gee_spmm_fused
from repro.kernels.gee_spmm import choose_block_sizes, gee_spmm
from repro.kernels.row_norm import row_norm
from repro.kernels.topk_score import (choose_gathered_blocks,
                                      choose_pairwise_blocks, scored_topk,
                                      scored_topk_gathered)

N_ROWS = 92_482          # cl-100k-1d8-l5 node count
BUCKET_WIDTHS = (8, 128, 256, 512)


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


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
@pytest.mark.parametrize("k", [5, 172])
def test_gee_spmm_compiles(one_chip, width, k):
    br, bd, ds = choose_block_sizes(N_ROWS, width, k)
    fn = functools.partial(gee_spmm, num_classes=k, block_rows=br,
                           block_deg=bd, deg_sub=ds, interpret=False)
    _compile(fn, one_chip, ((N_ROWS, width), jnp.int32),
             ((N_ROWS, width), jnp.float32))


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
@pytest.mark.parametrize("k", [5, 172])
def test_gee_spmm_fused_compiles(one_chip, width, k):
    br, bd, ds = choose_fused_block_sizes(N_ROWS, width, k)
    fn = functools.partial(gee_spmm_fused, num_classes=k, correlation=True,
                           block_rows=br, block_deg=bd, deg_sub=ds,
                           interpret=False)
    _compile(fn, one_chip, ((N_ROWS, width), jnp.int32),
             ((N_ROWS, width), jnp.float32), ((N_ROWS,), jnp.int32),
             ((N_ROWS,), jnp.float32))


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
