"""backend="pallas" as a first-class GEE path: dispatch equivalence against
gee_sparse_jax across every option setting, plus the gee_spmm edge cases the
ELL pipeline can produce (tile-boundary K, tiny N, all-padding tiles, and
bitwise padded-vs-unpadded agreement)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.api import GEEEmbedder
from repro.core.gee import (ALL_OPTION_SETTINGS, gee,
                            gee_sparse_jax, select_backend)
from repro.graph.containers import edge_list_from_numpy, symmetrize
from repro.kernels import choose_block_sizes, gee_spmm
from repro.kernels.ref import gee_spmm_ref

pytestmark = pytest.mark.pallas_interpret


# ---------------------------------------------------------------------------
# the acceptance criterion: gee(..., backend="pallas") == gee_sparse_jax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS,
                         ids=[o.tag() for o in ALL_OPTION_SETTINGS])
def test_pallas_backend_matches_sparse_jax(sbm_small, opts):
    s = sbm_small
    zp = np.asarray(gee(s.edges, s.labels, s.num_classes, opts,
                        backend="pallas"))
    zr = np.asarray(gee_sparse_jax(s.edges, jnp.asarray(s.labels),
                                   s.num_classes, opts))
    np.testing.assert_allclose(zp, zr, atol=1e-5, err_msg=opts.tag())


def test_auto_backend_dispatches(sbm_small):
    s = sbm_small
    b = select_backend(s.edges, s.num_classes)
    assert b in ("pallas", "sparse_jax")
    za = np.asarray(gee(s.edges, s.labels, s.num_classes, backend="auto"))
    zr = np.asarray(gee_sparse_jax(s.edges, jnp.asarray(s.labels),
                                   s.num_classes))
    np.testing.assert_allclose(za, zr, atol=1e-5)


def test_embedder_pallas_backend(sbm_small):
    s = sbm_small
    pred_p = np.asarray(GEEEmbedder(num_classes=s.num_classes,
                                    backend="pallas")
                        .fit(s.edges, s.labels).predict())
    pred_r = np.asarray(GEEEmbedder(num_classes=s.num_classes,
                                    backend="sparse_jax")
                        .fit(s.edges, s.labels).predict())
    # identical downstream classification as the production path
    assert np.mean(pred_p == pred_r) > 0.99
    assert np.mean(pred_p == s.labels) > 0.5   # far above the 0.33 prior


def test_pallas_weighted_unknown_labels():
    """Weighted graph + unlabeled nodes through the full dispatch."""
    rng = np.random.default_rng(3)
    n, e = 150, 600
    src = rng.integers(0, n, e)
    dst = (src + 1 + rng.integers(0, n - 1, e)) % n
    w = rng.random(e).astype(np.float32) + 0.1
    edges = symmetrize(edge_list_from_numpy(src, dst, w, n))
    labels = rng.integers(0, 4, n).astype(np.int32)
    labels[rng.random(n) < 0.3] = -1
    for opts in ALL_OPTION_SETTINGS:
        zp = np.asarray(gee(edges, labels, 4, opts, backend="pallas"))
        zr = np.asarray(gee_sparse_jax(edges, jnp.asarray(labels), 4, opts))
        np.testing.assert_allclose(zp, zr, atol=1e-5, err_msg=opts.tag())


# ---------------------------------------------------------------------------
# gee_spmm edge cases
# ---------------------------------------------------------------------------

def _rand_planes(rng, n, d, k, pad_frac=0.3):
    ylab = rng.integers(0, k, size=(n, d)).astype(np.int32)
    contrib = rng.random((n, d)).astype(np.float32) + 0.1
    pad = rng.random((n, d)) < pad_frac
    ylab[pad] = -1
    contrib[pad] = 0.0
    return jnp.asarray(ylab), jnp.asarray(contrib)


@pytest.mark.parametrize("k", [127, 129, 200, 250])
def test_k_not_multiple_of_lane(k):
    rng = np.random.default_rng(k)
    ylab, contrib = _rand_planes(rng, 40, 12, k)
    out = gee_spmm(ylab, contrib, k, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gee_spmm_ref(ylab, contrib, k)),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n", [1, 2, 7])
def test_n_smaller_than_row_tile(n):
    """N far below block_rows: the single partial row tile must be exact."""
    rng = np.random.default_rng(n)
    ylab, contrib = _rand_planes(rng, n, 9, 4)
    out = gee_spmm(ylab, contrib, 4, block_rows=256, interpret=True)
    assert out.shape == (n, 4)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gee_spmm_ref(ylab, contrib, 4)),
                               atol=1e-5, rtol=1e-5)


def test_all_padding_degree_tiles():
    """Real entries only in the first slots, D padded across several degree
    tiles: the revisited output block must pass through untouched."""
    rng = np.random.default_rng(0)
    n, d, k = 32, 300, 5                       # 3 deg tiles at block_deg=128
    ylab = np.full((n, d), -1, np.int32)
    contrib = np.zeros((n, d), np.float32)
    ylab[:, :4] = rng.integers(0, k, size=(n, 4))
    contrib[:, :4] = rng.random((n, 4)) + 0.1
    ylab, contrib = jnp.asarray(ylab), jnp.asarray(contrib)
    out = gee_spmm(ylab, contrib, k, block_deg=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gee_spmm_ref(ylab, contrib, k)),
                               atol=1e-5, rtol=1e-5)


def test_padded_vs_unpadded_bitwise():
    """Appending -1/0 padding rows and slots must not change any bit of the
    real rows (padding slots match no class, so they add exact zeros)."""
    rng = np.random.default_rng(5)
    n, d, k = 50, 20, 6
    ylab, contrib = _rand_planes(rng, n, d, k)
    base = np.asarray(gee_spmm(ylab, contrib, k, interpret=True))

    ylab_p = jnp.full((n + 30, d + 44), -1, jnp.int32)
    ylab_p = ylab_p.at[:n, :d].set(ylab)
    contrib_p = jnp.zeros((n + 30, d + 44), jnp.float32)
    contrib_p = contrib_p.at[:n, :d].set(contrib)
    padded = np.asarray(gee_spmm(ylab_p, contrib_p, k, interpret=True))
    assert np.array_equal(padded[:n], base)
    assert np.all(padded[n:] == 0.0)


def test_auto_block_sizes():
    """block size resolution: None triggers the heuristic, result unchanged."""
    rng = np.random.default_rng(9)
    ylab, contrib = _rand_planes(rng, 100, 33, 7)
    ref = np.asarray(gee_spmm(ylab, contrib, 7, interpret=True))
    auto = np.asarray(gee_spmm(ylab, contrib, 7, block_rows=None,
                               block_deg=None, deg_sub=None, interpret=True))
    np.testing.assert_allclose(auto, ref, atol=1e-6)


@pytest.mark.parametrize("n,d,k", [(1, 1, 1), (400, 63, 3), (10_000, 500, 40),
                                   (64, 8, 1000)])
def test_choose_block_sizes_sane(n, d, k):
    br, bd, ds = choose_block_sizes(n, d, k)
    assert br % 8 == 0 and br >= 8
    assert bd % 8 == 0 and bd >= 8
    assert 1 <= ds <= bd
    assert br <= ((n + 7) // 8) * 8 or br <= 512
    # cached: second call returns the identical tuple
    assert choose_block_sizes(n, d, k) == (br, bd, ds)
