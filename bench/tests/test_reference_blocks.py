"""The reference and the check taken by row blocks give, bit for bit, what
the whole-array form gives: the same reference rows, the same
``max_abs_err``.  The whole-array form is kept below as it stood before
the check went by blocks, as the oracle."""

import itertools

import numpy as np
import pytest

from harness import check
from harness.reference import Reference, _round

N, K, LIVE = 301, 5, 260         # vertices LIVE..N-1 have no edge
SETTINGS = [dict(laplacian=a, diag_aug=b, correlation=c)
            for a, b, c in itertools.product((False, True), repeat=3)]


def whole_array_embed(src, dst, n, options, labels, k,
                      precision="float64"):
    """The reference's embedding over all N x K at once, as it was."""
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    deg = np.bincount(src, minlength=n).astype(np.float64)
    if options["diag_aug"]:
        deg += 1.0
    if options["laplacian"]:
        dinv = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-300)), 0.0)
        w_hat = dinv[src] * dinv[dst]
    else:
        dinv = np.ones(n)
        w_hat = np.ones(src.shape[0])
    labels = np.asarray(labels, np.int64)
    known = labels >= 0
    counts = np.bincount(labels[known], minlength=k).astype(np.float64)
    winv = np.where(counts > 0, 1.0 / np.maximum(counts, 1.0), 0.0)
    yd = labels[dst]
    keep = yd >= 0
    contrib = w_hat[keep] * winv[yd[keep]]
    control = precision != "float64"
    if control:
        contrib = _round(contrib, precision)
    z = np.bincount(src[keep] * k + yd[keep], weights=contrib,
                    minlength=n * k).reshape(n, k)
    if control:
        z = z.astype(np.float32)
    if options["diag_aug"]:
        rows = np.flatnonzero(known)
        z[rows, labels[rows]] += (dinv[rows] ** 2
                                  * winv[labels[rows]]).astype(z.dtype)
    if options["correlation"]:
        norm = np.sqrt((z * z).sum(axis=1, keepdims=True))
        z = np.divide(z, norm, out=np.zeros_like(z), where=norm > 0)
    return z


def graph(seed=0, edges=4000):
    """Directed edge arrays of a random undirected graph on vertices
    0..LIVE-1, both directions, in a shuffled order."""
    rng = np.random.default_rng(seed)
    s = rng.integers(0, LIVE, edges).astype(np.int32)
    d = rng.integers(0, LIVE, edges).astype(np.int32)
    src, dst = np.concatenate([s, d]), np.concatenate([d, s])
    order = rng.permutation(src.shape[0])
    return src[order], dst[order]


def labels_of(kind, seed=1):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, K, N).astype(np.int32)
    if kind == "mostly_unlabelled":
        y[rng.random(N) < 0.9] = -1
    return y


def by_blocks(ref, y, rows, precision="float64"):
    blocks = ref.blocks(K, rows)
    return blocks, np.concatenate(
        [ref.embed_rows(y, K, lo, hi, precision) for lo, hi in blocks])


@pytest.mark.parametrize("rows", [1, 64, 97, N, None])
@pytest.mark.parametrize("kind", ["mostly_unlabelled", "all_labelled"])
@pytest.mark.parametrize("opts", SETTINGS,
                         ids=lambda o: "".join("LDC"[i] if v else "-"
                                               for i, v in
                                               enumerate(o.values())))
def test_blocks_match_the_whole_array(opts, kind, rows):
    src, dst = graph()
    y = labels_of(kind)
    whole = whole_array_embed(src, dst, N, opts, y, K)
    ref = Reference(src, dst, N, opts)
    blocks, z_ref = by_blocks(ref, y, rows)
    assert blocks[0][0] == 0 and blocks[-1][1] == N
    assert np.array_equal(z_ref, whole)
    assert np.array_equal(ref.embed(y, K), whole)
    assert not whole[LIVE:].any() or opts["diag_aug"]   # degree-0 rows

    noise = np.random.default_rng(2).normal(0, 1e-6, whole.shape)
    z = (whole + noise).astype(np.float32)
    got = check.answer_gaps(
        z, lambda lo, hi: ref.embed_rows(y, K, lo, hi), blocks)
    assert got == check.gaps(z, whole)
    assert got["max_abs_err"] > 0


@pytest.mark.parametrize("precision", ["high", "bfloat16"])
@pytest.mark.parametrize("opts", SETTINGS[::3])
def test_control_blocks_match_the_whole_array(opts, precision):
    src, dst = graph(3)
    y = labels_of("mostly_unlabelled", 4)
    whole = whole_array_embed(src, dst, N, opts, y, K, precision)
    ref = Reference(src, dst, N, opts)
    blocks, z_ctl = by_blocks(ref, y, 64, precision)
    assert z_ctl.dtype == np.float32
    assert np.array_equal(z_ctl, whole)
    got = check.block_gaps(
        lambda lo, hi: ref.embed_rows(y, K, lo, hi, precision),
        lambda lo, hi: ref.embed_rows(y, K, lo, hi), blocks)
    assert got == check.gaps(whole, whole_array_embed(src, dst, N, opts,
                                                      y, K))


def test_regrouping_keeps_each_rows_order():
    """Blocks of one size after blocks of another still give the whole
    array's rows bit for bit."""
    opts = SETTINGS[-1]
    src, dst = graph(5)
    y = labels_of("all_labelled", 6)
    whole = whole_array_embed(src, dst, N, opts, y, K)
    ref = Reference(src, dst, N, opts)
    for rows in (50, 7, N, 33):
        assert np.array_equal(by_blocks(ref, y, rows)[1], whole)
    assert np.array_equal(ref.embed_rows(y, K, 10, 200), whole[10:200])


def test_one_entry_moved_in_the_last_block_is_caught():
    opts = SETTINGS[-1]
    src, dst = graph(7)
    y = labels_of("all_labelled", 8)
    ref = Reference(src, dst, N, opts)
    blocks = ref.blocks(K, 64)
    z = ref.embed(y, K).astype(np.float32)
    row = blocks[-1][0] + 3
    z[row, 2] += 1e-3
    got = check.answer_gaps(
        z, lambda lo, hi: ref.embed_rows(y, K, lo, hi), blocks)
    assert got["max_abs_err"] == pytest.approx(1e-3, rel=1e-3)
    assert not check.verdict([got], {"max_abs_err": 2e-05})[0]


@pytest.mark.parametrize("bad", ["nan", "rows", "classes"])
def test_broken_answers_read_inf(bad):
    src, dst = graph()
    y = labels_of("all_labelled")
    ref = Reference(src, dst, N, SETTINGS[-1])
    blocks = ref.blocks(K, 64)
    z = ref.embed(y, K).astype(np.float32)
    z = {"nan": np.where(np.arange(N)[:, None] == N - 1, np.nan, z),
         "rows": z[:-1], "classes": z[:, :-1]}[bad]
    got = check.answer_gaps(
        z, lambda lo, hi: ref.embed_rows(y, K, lo, hi), blocks)
    assert got == {"max_abs_err": float("inf")}


def test_default_block_holds_256_mib_and_never_more_than_n():
    src, dst = graph()
    ref = Reference(src, dst, N, SETTINGS[-1])
    assert ref.blocks(K) == [(0, N)]
    big = Reference(np.zeros(1, np.int32), np.ones(1, np.int32), 3_000_000,
                    SETTINGS[0])
    blocks = big.blocks(172)
    assert blocks[0] == (0, (256 << 20) // (8 * 172))
    assert blocks[-1][1] == 3_000_000


@pytest.mark.parametrize("k", [47, 172])
def test_wide_rows_match_the_whole_array(k):
    """The row norm sums K entries a row; at K of 47 and 172 too, a block's
    rows equal the whole array's."""
    opts = SETTINGS[-1]
    src, dst = graph(9, edges=20_000)
    rng = np.random.default_rng(10)
    y = rng.integers(0, k, N).astype(np.int32)
    y[rng.random(N) < 0.5] = -1
    whole = whole_array_embed(src, dst, N, opts, y, k)
    ref = Reference(src, dst, N, opts)
    blocks = ref.blocks(k, 40)
    z_ref = np.concatenate([ref.embed_rows(y, k, lo, hi)
                            for lo, hi in blocks])
    assert np.array_equal(z_ref, whole)
