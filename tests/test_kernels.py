"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracle, swept
over shapes, dtypes and block sizes (the assignment's kernel contract)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import gee_spmm, row_norm
from repro.kernels.ref import gee_spmm_ref, row_norm_ref

pytestmark = pytest.mark.pallas_interpret


def _rand_ell(rng, n, d, k, dtype=np.float32, pad_frac=0.3):
    ylab = rng.integers(0, k, size=(n, d)).astype(np.int32)
    contrib = rng.random((n, d)).astype(dtype) + 0.1
    pad = rng.random((n, d)) < pad_frac
    ylab[pad] = -1
    contrib[pad] = 0.0
    return jnp.asarray(ylab), jnp.asarray(contrib)


@pytest.mark.parametrize("n", [1, 7, 64, 300])
@pytest.mark.parametrize("d", [1, 5, 130])
@pytest.mark.parametrize("k", [1, 3, 9])
def test_gee_spmm_shape_sweep(n, d, k):
    rng = np.random.default_rng(n * 1000 + d * 10 + k)
    ylab, contrib = _rand_ell(rng, n, d, k)
    out = gee_spmm(ylab, contrib, k, interpret=True)
    ref = gee_spmm_ref(ylab, contrib, k)
    assert out.shape == (n, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("k", [100, 128, 130, 200])
def test_gee_spmm_wide_classes(k):
    """K crossing the 128-lane boundary."""
    rng = np.random.default_rng(k)
    ylab, contrib = _rand_ell(rng, 50, 16, k)
    out = gee_spmm(ylab, contrib, k, interpret=True)
    ref = gee_spmm_ref(ylab, contrib, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_gee_spmm_dtypes(dtype):
    rng = np.random.default_rng(0)
    ylab, contrib = _rand_ell(rng, 64, 32, 5, dtype=dtype)
    out = gee_spmm(ylab, contrib, 5, interpret=True)
    ref = gee_spmm_ref(ylab, contrib, 5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-2 if dtype == np.float16 else 1e-5)


def test_gee_spmm_bf16():
    rng = np.random.default_rng(1)
    ylab, contrib = _rand_ell(rng, 32, 16, 4)
    contrib = contrib.astype(jnp.bfloat16)
    out = gee_spmm(ylab, contrib, 4, interpret=True)
    ref = gee_spmm_ref(ylab, contrib, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


@pytest.mark.parametrize("block_rows,block_deg,deg_sub",
                         [(8, 8, 8), (64, 128, 8), (256, 128, 16),
                          (128, 256, 32)])
def test_gee_spmm_block_shapes(block_rows, block_deg, deg_sub):
    """Block-shape independence: tiling must never change the result."""
    rng = np.random.default_rng(7)
    ylab, contrib = _rand_ell(rng, 200, 70, 6)
    ref = gee_spmm_ref(ylab, contrib, 6)
    out = gee_spmm(ylab, contrib, 6, block_rows=block_rows,
                   block_deg=block_deg, deg_sub=deg_sub, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_gee_spmm_all_padding():
    """A fully-padded tile contributes exactly zero."""
    ylab = jnp.full((16, 8), -1, jnp.int32)
    contrib = jnp.zeros((16, 8), jnp.float32)
    out = gee_spmm(ylab, contrib, 3, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("n", [1, 5, 100, 513])
@pytest.mark.parametrize("k", [1, 3, 128, 200])
def test_row_norm_sweep(n, k):
    rng = np.random.default_rng(n + k)
    z = rng.standard_normal((n, k)).astype(np.float32)
    z[rng.random(n) < 0.2] = 0.0           # some zero rows
    out = row_norm(jnp.asarray(z), interpret=True)
    ref = row_norm_ref(jnp.asarray(z))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-5)


def test_row_norm_bf16_input():
    rng = np.random.default_rng(3)
    z = jnp.asarray(rng.standard_normal((64, 10)), jnp.bfloat16)
    out = row_norm(z, interpret=True)
    ref = row_norm_ref(z)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-2)

