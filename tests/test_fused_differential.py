"""Differential fuzz harness for the Pallas backend's fused fit.

The fused path (``repro.kernels.gee_fused``) re-derives the whole
O(N*K) epilogue inside the scatter kernel, so every numerics bug it
could introduce is a *divergence* from an existing reference.  This
module holds it to two of them:

  * ``gee_scipy`` -- the paper-faithful ground truth, for whole fits;
  * a pure-numpy oracle for the raw kernel contract (tile boundaries,
    padding lanes, ragged tails).

Graphs come from a hypothesis strategy that deliberately concentrates
on the paper's glossed-over corners: isolated vertices, hub/star degree
skew, self-loops, empty classes, -1 (unknown) labels, and zero-weight
padded tails.  Every kernel launch here forces ``interpret=True`` so
the suite runs on plain CPU CI (the ``pallas_interpret`` marker gates
the dedicated CI leg).
"""

import json
import os

import numpy as np
import jax.numpy as jnp
import pytest

try:                                       # only the fuzz test needs it
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                        # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core.epilogue import EPS_NORM
from repro.core.gee import ALL_OPTION_SETTINGS, GEEOptions, gee, gee_scipy
from repro.core.plan import KNOWN_BACKENDS, GEEPlan, PreparedGraph
from repro.graph.containers import edge_list_from_numpy, symmetrize
from repro.graph.ell import edges_to_bucketed_ell
from repro.kernels.autotune import AutotuneRegistry
from repro.kernels import gee_fused
from repro.kernels.gee_fused import (gee_fused_from_bucketed, gee_spmm_fused,
                                     scale_buckets)
from repro.obs.metrics import MetricsRegistry, set_registry
from repro.kernels.topk_score import (gathered_scores, masked_topk,
                                      pairwise_scores, scored_topk,
                                      scored_topk_gathered)

pytestmark = pytest.mark.pallas_interpret

OPT_IDS = [o.tag() for o in ALL_OPTION_SETTINGS]


# ---------------------------------------------------------------------------
# adversarial graph strategy
# ---------------------------------------------------------------------------

if not HAVE_HYPOTHESIS:                    # stub so the decorator below parses
    class st:                              # noqa: N801 - mirrors the module
        @staticmethod
        def composite(f):
            return f


@st.composite
def adversarial_graphs(draw):
    """(EdgeList, labels, num_classes) biased toward the nasty corners."""
    n = draw(st.integers(min_value=1, max_value=28))
    k = draw(st.integers(min_value=1, max_value=5))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))

    m = draw(st.integers(min_value=0, max_value=3 * n))
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    if draw(st.booleans()) and n >= 2:          # hub/star degree skew
        hub_deg = draw(st.integers(1, 2 * n))
        src = np.concatenate([src, np.zeros(hub_deg, np.int64)])
        dst = np.concatenate([dst, rng.integers(1, n, hub_deg)])
    if draw(st.booleans()):                      # explicit self-loops
        loops = rng.integers(0, n, draw(st.integers(1, 3)))
        src = np.concatenate([src, loops])
        dst = np.concatenate([dst, loops])
    # leave a tail of nodes untouched -> isolated vertices
    weight = rng.uniform(0.2, 2.0, src.shape[0]).astype(np.float32)

    labels = rng.integers(0, k, n).astype(np.int32)
    unknown = rng.random(n) < draw(st.floats(0.0, 0.6))
    labels[unknown] = -1                         # -1 = unknown
    if draw(st.booleans()) and k >= 2:           # force an empty class
        labels[labels == k - 1] = -1

    edges = symmetrize(edge_list_from_numpy(src, dst, weight, n))
    if draw(st.booleans()):                      # zero-weight padded tail
        edges = edges.with_padding(64)
    return edges, labels, k


def _scipy_ref(edges, labels, k, opts):
    src, dst, w = edges.valid_arrays()
    return np.asarray(gee_scipy(src, dst, w, np.asarray(labels), k, opts,
                                num_nodes=edges.num_nodes))


# ---------------------------------------------------------------------------
# the fused fit vs scipy, all 8 settings
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _fuzz = lambda f: settings(max_examples=12, deadline=None)(  # noqa: E731
        given(adversarial_graphs())(f))
    # the edgeless graph Hypothesis once recorded as a failure (N=1, E=0,
    # K=1): a base-graph packing must still apply diag-aug
    _edgeless = example(graph=(
        edge_list_from_numpy(np.zeros(0, np.int32), np.zeros(0, np.int32),
                             None, 1),
        np.array([0], np.int32), 1))
else:                                      # pragma: no cover
    _fuzz = lambda f: pytest.mark.skip(    # noqa: E731
        reason="hypothesis not installed")(f)
    _edgeless = lambda f: f                # noqa: E731


@_fuzz
@_edgeless
def test_fused_matches_scipy(graph):
    edges, labels, k = graph
    labels_j = jnp.asarray(labels)
    bell = edges_to_bucketed_ell(edges)
    for opts in ALL_OPTION_SETTINGS:
        fused = np.asarray(gee_fused_from_bucketed(
            bell, labels_j, k, opts, interpret=True))
        np.testing.assert_allclose(
            fused, _scipy_ref(edges, labels, k, opts), atol=1e-5,
            err_msg=f"fused vs scipy, {opts.tag()}, "
                    f"n={edges.num_nodes} k={k}")


def _fixed_adversarial():
    """One deterministic graph hitting every corner at once: hub node 0,
    a self loop, isolated tail 8..22, -1 labels, empty class 3."""
    src = np.concatenate([np.zeros(6, np.int64), [1, 2, 7]])
    dst = np.concatenate([np.arange(1, 7), [2, 3, 7]])
    w = np.linspace(0.5, 2.0, src.shape[0]).astype(np.float32)
    labels = np.array([0, 1, 2, 0, 1, 2, 0, 1] + [0] * 15, np.int32)
    labels[10:] = -1
    edges = symmetrize(edge_list_from_numpy(src, dst, w, 23)).with_padding(64)
    return edges, labels, 4


@pytest.mark.parametrize("backend", KNOWN_BACKENDS)
@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS, ids=OPT_IDS)
def test_every_backend_matches_fused(backend, opts):
    edges, labels, k = _fixed_adversarial()
    ref = _scipy_ref(edges, labels, k, opts)
    fused = np.asarray(gee_fused_from_bucketed(
        edges_to_bucketed_ell(edges), jnp.asarray(labels), k, opts,
        interpret=True))
    np.testing.assert_allclose(fused, ref, atol=1e-5)
    out = np.asarray(gee(edges, labels, k, opts, backend=backend))
    np.testing.assert_allclose(out, fused, atol=1e-5,
                               err_msg=f"{backend} vs fused, {opts.tag()}")


@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS, ids=OPT_IDS)
def test_plan_scaling_matches_inline_build(opts):
    """The plan hands the fused driver the prepared graph's memoized
    scaling; Z on the building fit and on the reusing one is bit for bit
    the driver's with the scaling built inline."""
    edges, labels, k = _fixed_adversarial()
    prep = PreparedGraph.wrap(edges)
    plan = GEEPlan.build(prep, k, opts, backend="pallas")
    zs = [np.asarray(plan.execute(labels)) for _ in range(2)]
    inline = gee_fused_from_bucketed(prep.bucketed_ell(False),
                                     jnp.asarray(labels), k, opts,
                                     interpret=True)
    for z in zs:
        assert np.array_equal(z, np.asarray(inline)), opts.tag()


def test_scaling_for_other_options_is_refused():
    edges, labels, k = _fixed_adversarial()
    bell = edges_to_bucketed_ell(edges)
    sc = scale_buckets(bell, laplacian=True, diag_aug=False)
    with pytest.raises(ValueError, match="scaling built for"):
        gee_fused_from_bucketed(bell, jnp.asarray(labels), k,
                                GEEOptions(laplacian=True, diag_aug=True),
                                scaling=sc, interpret=True)


# ---------------------------------------------------------------------------
# one compiled program a fit
# ---------------------------------------------------------------------------

def _no_degree0_graph(n=40, k=3, seed=5):
    """A ring plus random chords: every vertex has an edge."""
    rng = np.random.default_rng(seed)
    src = np.concatenate([np.arange(n), rng.integers(0, n, 2 * n)])
    dst = np.concatenate([(np.arange(n) + 1) % n, rng.integers(0, n, 2 * n)])
    w = rng.uniform(0.5, 2.0, src.shape[0]).astype(np.float32)
    labels = rng.integers(0, k, n).astype(np.int32)
    labels[::5] = -1
    return symmetrize(edge_list_from_numpy(src, dst, w, n)), labels, k


def test_fresh_embedders_share_one_traced_program():
    """Three fits with fresh labels, each through a fresh embedder and
    plan over one prepared graph, dispatch the fit program three times
    and trace it once."""
    from repro.core.api import GEEEmbedder

    edges, _, k = _fixed_adversarial()
    prep = PreparedGraph.wrap(edges)
    rng = np.random.default_rng(0)
    gee_fused._fused_fit.clear_cache()
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        for _ in range(3):
            y = rng.integers(-1, k, edges.num_nodes).astype(np.int32)
            emb = GEEEmbedder(num_classes=k, backend="pallas")
            z = emb.fit(prep, y).transform()
            assert emb.plan.fused
            np.testing.assert_allclose(
                np.asarray(z), _scipy_ref(edges, y, k, emb.options),
                atol=1e-5)
    finally:
        set_registry(prev)
    counters = reg.snapshot()["counters"]
    assert counters["plan.fit_program.calls"] == 3
    assert counters["plan.fit_program.traces"] == 1


@pytest.mark.parametrize("degree0", [False, True],
                         ids=["no-degree0", "degree0"])
def test_residual_traced_only_with_degree0_rows(monkeypatch, degree0):
    """The degree-0 residual is static: a packing that covers every row
    traces no epilogue outside the kernel; one with degree-0 rows traces
    it once.  Both match the SciPy reference."""
    edges, labels, k = (_fixed_adversarial() if degree0
                        else _no_degree0_graph())
    opts = GEEOptions(laplacian=True, diag_aug=True, correlation=True)
    bell = edges_to_bucketed_ell(edges)
    sc = scale_buckets(bell, laplacian=True, diag_aug=True)
    assert sc.num_uncovered == int(np.asarray(sc.uncovered).sum())
    assert (sc.num_uncovered > 0) == degree0
    traced = []
    shared = gee_fused.apply_epilogue
    monkeypatch.setattr(gee_fused, "apply_epilogue",
                        lambda *a, **kw: traced.append(1) or shared(*a, **kw))
    gee_fused._fused_fit.clear_cache()
    try:
        z = gee_fused_from_bucketed(bell, jnp.asarray(labels), k, opts,
                                    scaling=sc, interpret=True)
    finally:
        gee_fused._fused_fit.clear_cache()
    assert len(traced) == int(degree0)
    np.testing.assert_allclose(np.asarray(z),
                               _scipy_ref(edges, labels, k, opts), atol=1e-5)


@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS, ids=OPT_IDS)
def test_degree0_rows_match_scipy(opts):
    edges, labels, k = _fixed_adversarial()      # isolated tail 8..22
    bell = edges_to_bucketed_ell(edges)
    assert scale_buckets(bell, laplacian=opts.laplacian,
                         diag_aug=opts.diag_aug).num_uncovered == 15
    fused = gee_fused_from_bucketed(bell, jnp.asarray(labels), k, opts,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(fused),
                               _scipy_ref(edges, labels, k, opts),
                               atol=1e-5, err_msg=opts.tag())


def _relabelled(edges, labels, seed=3):
    """The graph with its vertex ids permuted, and its labels moved with
    them: the same degree sequence, so the same bucket shapes."""
    n = edges.num_nodes
    perm = np.random.default_rng(seed).permutation(n)
    src, dst, w = edges.valid_arrays()
    moved = np.empty_like(labels)
    moved[perm] = labels
    return (edge_list_from_numpy(perm[src], perm[dst], w, n).with_padding(64),
            moved, perm)


@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS, ids=OPT_IDS)
def test_same_shaped_graphs_share_one_trace(opts):
    """Two prepared graphs of the same bucket shapes -- one graph and a
    vertex-id permutation of it, as the benchmark's seeds are -- run one
    traced fit program: the packing's arrays are its arguments, never
    closed over.  Each Z is its own graph's."""
    edges, labels, k = _fixed_adversarial()
    edges2, labels2, perm = _relabelled(edges, labels)
    shapes = [[b.cols.shape for b in edges_to_bucketed_ell(e).buckets]
              for e in (edges, edges2)]
    assert shapes[0] == shapes[1]
    gee_fused._fused_fit.clear_cache()
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        zs = [np.asarray(GEEPlan.build(PreparedGraph.wrap(e), k, opts,
                                       backend="pallas").execute(y))
              for e, y in ((edges, labels), (edges2, labels2))]
    finally:
        set_registry(prev)
    counters = reg.snapshot()["counters"]
    assert counters["plan.fit_program.calls"] == 2
    assert counters["plan.fit_program.traces"] == 1
    for z, e, y in zip(zs, (edges, edges2), (labels, labels2)):
        np.testing.assert_allclose(z, _scipy_ref(e, y, k, opts), atol=1e-5,
                                   err_msg=opts.tag())
    np.testing.assert_allclose(zs[1][perm], zs[0], atol=1e-6)


@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS, ids=OPT_IDS)
def test_pallas_plan_dispatches_one_program_a_fit(opts):
    """Every option setting -- diag-aug and correlation on or off -- runs
    the ``pallas`` plan as one fit program: one dispatch an execute, and
    Z matches the SciPy reference."""
    edges, labels, k = _no_degree0_graph()
    plan = GEEPlan.build(edges, k, opts, backend="pallas")
    assert plan.fused
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        for fit in (1, 2):
            z = np.asarray(plan.execute(labels))
            assert reg.counter("plan.fit_program.calls").value == fit
            np.testing.assert_allclose(z, _scipy_ref(edges, labels, k, opts),
                                       atol=1e-5, err_msg=opts.tag())
    finally:
        set_registry(prev)


# ---------------------------------------------------------------------------
# the label lookup inside the kernel against XLA's gather
# ---------------------------------------------------------------------------

def _wide_graph(n=10_333, seed=11):
    """A ring over all but the last vertex (left at degree 0), 150 rows
    of degree ~70-120, 36 of ~140-220 and two of ~300: narrow buckets and
    128-, 256- and 512-wide ones, a wide bucket of more than one (ragged)
    row block, wide buckets of several degree tiles, and N a multiple of
    neither 8, 128 nor 1,024."""
    rng = np.random.default_rng(seed)
    ring = np.arange(n - 1)
    hubs = np.repeat(np.arange(188), np.concatenate(
        [rng.integers(70, 121, 150), rng.integers(140, 221, 36),
         [300, 310]]))
    src = np.concatenate([ring, hubs])
    dst = np.concatenate([(ring + 1) % (n - 1),
                          rng.integers(0, n - 1, hubs.size)])
    w = rng.uniform(0.5, 2.0, src.shape[0]).astype(np.float32)
    return symmetrize(edge_list_from_numpy(src, dst, w, n))


@pytest.fixture(scope="module")
def wide_graph():
    edges = _wide_graph()
    return edges, edges_to_bucketed_ell(edges)


@pytest.mark.parametrize("labelled", ["some", "all", "beyond-k"])
@pytest.mark.parametrize("k", [1, 5, 15, 16, 47])
@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS, ids=OPT_IDS)
def test_lookup_route_matches_gather_route(monkeypatch, wide_graph, opts, k,
                                           labelled):
    """Buckets whose labels are looked up in the kernel, from the packed
    VMEM table, give Z bit for bit as when XLA gathers them into planes,
    on both sides of the 4-bit/8-bit packing boundary (K = 15, 16), with
    and without unlabelled vertices, and with labels of K or more, which
    both routes take as unlabelled (no code of theirs spills into a
    neighbour's field); and both match SciPy."""
    edges, bell = wide_graph
    n = edges.num_nodes
    rng = np.random.default_rng(k)
    labels = rng.integers(0 if labelled == "all" else -1,
                          k + 20 if labelled == "beyond-k" else k, n)
    known = np.where(labels < k, labels, -1)
    labels = jnp.asarray(labels, jnp.int32)
    widths = [b.width for b in bell.buckets]
    assert min(widths) < 128 and {128, 256, 512} <= set(widths)
    # the scan's loop steps and its remainder both run
    assert gee_fused.label_table_rows(n, k) % gee_fused._LOOKUP_UNROLL
    assert gee_fused.label_table_rows(n, k) > gee_fused._LOOKUP_UNROLL
    sc = scale_buckets(bell, laplacian=opts.laplacian,
                       diag_aug=opts.diag_aug)
    assert sc.num_uncovered == 1                     # a degree-0 row

    def fit():
        reg = MetricsRegistry()
        prev = set_registry(reg)
        try:
            z = gee_fused_from_bucketed(bell, labels, k, opts, scaling=sc,
                                        interpret=True)
        finally:
            set_registry(prev)
        return np.asarray(z), reg.snapshot()["counters"]

    z_vmem, c_vmem = fit()
    monkeypatch.setattr(gee_fused, "LOOKUP_MAX_TABLE_ROWS", 0)
    z_gather, c_gather = fit()
    wide = sum(w >= 128 for w in widths)
    assert (c_vmem["plan.fit.lookup.vmem"],
            c_vmem["plan.fit.lookup.gather"]) == (wide, len(widths) - wide)
    assert (c_gather["plan.fit.lookup.vmem"],
            c_gather["plan.fit.lookup.gather"]) == (0, len(widths))
    assert np.array_equal(z_vmem, z_gather), opts.tag()
    np.testing.assert_allclose(
        z_vmem, _scipy_ref(edges, known, k, opts), atol=1e-5,
        err_msg=opts.tag())


@pytest.mark.parametrize("n,k,block_deg,routes", [
    (92_482, 5, (8, 128, 128), (False, True, True)),   # cl-100k-1d8-l5
    (2_449_029, 47, (64, 128), (False, False)),        # ogbn-products
    (92_482, 256, (128,), (False,)),                   # K too large to pack
])
def test_lookup_route_chosen_from_n_and_k(n, k, block_deg, routes):
    """``cl-100k-1d8-l5`` (T = 91 rows) looks its labels up in the kernel
    for every bucket of whole 128-lane degree blocks; ``ogbn-products``
    (T = 4,784) and a K beyond 8-bit codes keep XLA's gather."""
    assert gee_fused.lookup_routes(n, k, block_deg) == routes
    assert gee_fused.label_table_rows(92_482, 5) == 91
    assert gee_fused.label_table_rows(2_449_029, 47) == 4_784


@pytest.mark.parametrize("k", [1, 15, 16, 255])
def test_label_table_packs_codes(k):
    """Each vertex's code ``y + 1`` (0 = unlabelled) sits at the row,
    lane and field the kernel reads."""
    n = 3_001
    labels = np.random.default_rng(k).integers(-1, k, n).astype(np.int32)
    table = np.asarray(gee_fused.label_table(jnp.asarray(labels), k))
    bits = gee_fused.label_code_bits(k)
    per_word = 32 // bits
    assert table.shape == (gee_fused.label_table_rows(n, k), 128)
    v = np.arange(n)
    words = table.view(np.uint32)[v // (128 * per_word), v % 128]
    codes = (words >> ((v // 128) % per_word * bits)) & ((1 << bits) - 1)
    np.testing.assert_array_equal(codes, labels + 1)


# ---------------------------------------------------------------------------
# raw kernel contract: tile boundaries, padding lanes, ragged tails
# ---------------------------------------------------------------------------

def _fused_oracle(ylab, contrib, rowlab, dadd, k, correlation):
    ylab, contrib = np.asarray(ylab), np.asarray(contrib)
    n = ylab.shape[0]
    z = np.zeros((n, k), np.float64)
    for i in range(n):
        for j in range(ylab.shape[1]):
            y = int(ylab[i, j])
            if 0 <= y < k:
                z[i, y] += float(contrib[i, j])
    if rowlab.size:
        rowlab, dadd = np.asarray(rowlab), np.asarray(dadd)
        for i in range(n):
            y = int(rowlab[i])
            if 0 <= y < k:
                z[i, y] += float(dadd[i])
    if correlation:
        norm = np.linalg.norm(z, axis=1, keepdims=True)
        z = np.where(norm > 0, z / np.maximum(norm, EPS_NORM), 0.0)
    return z.astype(np.float32)


def _rand_planes(rng, n, d, k):
    ylab = rng.integers(-1, k, (n, d)).astype(np.int32)
    contrib = rng.uniform(0.1, 1.0, (n, d)).astype(np.float32)
    contrib[ylab < 0] = 0.0
    rowlab = rng.integers(-1, k, n).astype(np.int32)
    dadd = rng.uniform(0.1, 1.0, n).astype(np.float32)
    dadd[rowlab < 0] = 0.0
    return (jnp.asarray(ylab), jnp.asarray(contrib),
            jnp.asarray(rowlab), jnp.asarray(dadd))


# N and K deliberately avoid every candidate block size: N below a block,
# K = 1, pow2 +/- 1 rows, degree not a multiple of deg_sub.
@pytest.mark.parametrize("n,d,k", [
    (3, 1, 1), (7, 2, 2), (1, 5, 3), (129, 3, 3),
    (255, 7, 1), (63, 9, 5), (8, 8, 4),
])
@pytest.mark.parametrize("blocks", [(8, 8, 8), (64, 16, 8)],
                         ids=["small-blocks", "large-blocks"])
@pytest.mark.parametrize("correlation", [False, True],
                         ids=["raw", "rownorm"])
def test_fused_kernel_tile_boundaries(n, d, k, blocks, correlation):
    rng = np.random.default_rng(n * 1009 + d * 31 + k)
    ylab, contrib, rowlab, dadd = _rand_planes(rng, n, d, k)
    br, bd, ds = blocks
    out = gee_spmm_fused(ylab, contrib, rowlab, dadd, k,
                         correlation=correlation, block_rows=br,
                         block_deg=bd, deg_sub=ds, interpret=True)
    ref = _fused_oracle(ylab, contrib, rowlab, dadd, k, correlation)
    assert out.shape == (n, k)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_fused_kernel_padding_tail_is_noop():
    """Appending -1/zero padded columns (masked tail) changes nothing."""
    rng = np.random.default_rng(7)
    ylab, contrib, rowlab, dadd = _rand_planes(rng, 13, 5, 3)
    z0 = gee_spmm_fused(ylab, contrib, rowlab, dadd, 3,
                        block_rows=8, block_deg=8, deg_sub=8, interpret=True)
    ylab_p = jnp.concatenate([ylab, jnp.full((13, 11), -1, jnp.int32)], 1)
    contrib_p = jnp.concatenate([contrib, jnp.zeros((13, 11))], 1)
    z1 = gee_spmm_fused(ylab_p, contrib_p, rowlab, dadd, 3,
                        block_rows=8, block_deg=8, deg_sub=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))


def test_fused_kernel_no_diag_when_rowlab_empty():
    rng = np.random.default_rng(9)
    ylab, contrib, _, _ = _rand_planes(rng, 10, 4, 3)
    empty_i = jnp.zeros((0,), jnp.int32)
    empty_f = jnp.zeros((0,), jnp.float32)
    out = gee_spmm_fused(ylab, contrib, empty_i, empty_f, 3,
                         correlation=False, block_rows=8, block_deg=8,
                         deg_sub=8, interpret=True)
    ref = _fused_oracle(ylab, contrib, empty_i, empty_f, 3, False)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


# ---------------------------------------------------------------------------
# plan-layer surface
# ---------------------------------------------------------------------------

def test_plan_fused_matches_scipy_and_describes():
    edges, labels, k = _fixed_adversarial()
    opts = GEEOptions(laplacian=True, diag_aug=True, correlation=True)
    plan = GEEPlan.build(edges, k, opts, backend="pallas")
    np.testing.assert_allclose(np.asarray(plan.execute(labels)),
                               _scipy_ref(edges, labels, k, opts), atol=1e-5)
    assert plan.fused
    assert [(s.kind, s.name) for s in plan.stages if s.kind != "prep"] \
        == [("compute", "gee_spmm_fused")]
    # the epilogue lives in the kernel: no separate row-norm stage
    assert "gee_spmm_fused" in plan.describe()
    assert "epilogue" not in plan.describe()
    assert not GEEPlan.build(edges, k, opts, backend="sparse_jax").fused


# ---------------------------------------------------------------------------
# fused score-and-top-k
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,m,dim,k", [
    (5, 37, 3, 4),    # m not a multiple of any block
    (1, 1, 1, 3),     # k > m, single row/col
    (9, 6, 2, 10),    # k > m
    (3, 129, 4, 2),   # m = pow2 + 1
])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_fused_topk_matches_staged(q, m, dim, k, metric):
    rng = np.random.default_rng(q * 100 + m)
    Q = jnp.asarray(rng.normal(size=(q, dim)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(m, dim)), jnp.float32)
    valid = jnp.asarray(rng.integers(0, 2, m), jnp.float32)
    ids_f, s_f = scored_topk(Q, X, valid, k, metric=metric, impl="pallas",
                             fused=True, interpret=True)
    ids_s, s_s = masked_topk(
        pairwise_scores(Q, X, valid, metric=metric, impl="pallas",
                        interpret=True), None, k)
    np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_s))
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_s), atol=0)


def test_fused_topk_gathered_matches_staged():
    rng = np.random.default_rng(11)
    q, m, dim, k = 6, 20, 3, 4
    Q = jnp.asarray(rng.normal(size=(q, dim)), jnp.float32)
    cand = jnp.asarray(rng.normal(size=(q, m, dim)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, (q, m)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 99, (q, m)), jnp.int32)
    for metric in ("l2", "cosine"):
        idf, sf = scored_topk_gathered(Q, cand, mask, ids, k, metric=metric,
                                       impl="pallas", fused=True,
                                       interpret=True)
        ids_s, s_s = masked_topk(
            gathered_scores(Q, cand, mask, metric=metric, impl="pallas",
                            interpret=True), ids, k)
        np.testing.assert_array_equal(np.asarray(idf), np.asarray(ids_s))
        np.testing.assert_allclose(np.asarray(sf), np.asarray(s_s), atol=0)


def test_fused_topk_all_masked_row():
    Q = jnp.ones((2, 3), jnp.float32)
    X = jnp.ones((5, 3), jnp.float32)
    valid = jnp.asarray([0, 0, 0, 0, 0], jnp.float32)
    ids_f, _ = scored_topk(Q, X, valid, 3, metric="l2", impl="pallas",
                           fused=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(ids_f), -np.ones((2, 3), int))


# ---------------------------------------------------------------------------
# measured autotune: deterministic, persistent, beats-or-matches the seed
# ---------------------------------------------------------------------------

def _register_spmm(reg):
    from repro.kernels.gee_spmm import KERNEL_NAME, _block_sizes_formula
    reg.register(KERNEL_NAME, fallback=_block_sizes_formula)
    return KERNEL_NAME


def test_measured_search_records_and_skips_rerun(tmp_path, monkeypatch):
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(cache))
    calls = []
    fake_times = {(8, 8, 8): 3.0, (16, 8, 8): 1.0, (32, 8, 8): 2.0}
    # measured_search times runner(c) via measure_runtime; fake the clock
    # so the "winner" is fully deterministic for this test
    monkeypatch.setattr(
        "repro.kernels.autotune.measure_runtime",
        lambda fn, warmup=1, repeats=3: fake_times[fn()])

    def timed_runner(cand):
        calls.append(cand)
        return cand

    reg = AutotuneRegistry()
    kernel = _register_spmm(reg)
    cands = list(fake_times)
    winner, timings = reg.measured_search(kernel, (64, 8, 4), cands,
                                          timed_runner)
    assert winner == (16, 8, 8)
    assert timings == fake_times
    assert len(calls) == 3
    # recorded tier now resolves the key without re-timing
    w2, t2 = reg.measured_search(kernel, (64, 8, 4), cands, timed_runner)
    assert (w2, t2) == (winner, {}) and len(calls) == 3
    assert reg.lookup(kernel, (64, 8, 4)) == winner
    # persisted: a fresh registry reloads the recorded winner
    assert json.loads(cache.read_text())["recorded"][kernel]
    reg2 = AutotuneRegistry()
    _register_spmm(reg2)
    w3, t3 = reg2.measured_search(kernel, (64, 8, 4), cands, timed_runner)
    assert (w3, t3) == (winner, {}) and len(calls) == 3


def test_measured_block_search_deterministic_and_beats_seed(
        tmp_path, monkeypatch):
    from repro.kernels.gee_spmm import candidate_blocks, measured_block_search
    from repro.kernels.autotune import pow2_bucket
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    reg = AutotuneRegistry()
    kernel = _register_spmm(reg)
    key = pow2_bucket(60, 8, 3)
    seeded = candidate_blocks(key, registry=reg)[0]  # current resolution
    w1, t1 = measured_block_search(60, 8, 3, registry=reg, repeats=2)
    assert t1 and w1 in t1
    # the winner never regresses the seeded-table/formula resolution
    assert t1[w1] <= t1[seeded]
    assert reg.lookup(kernel, key) == w1
    # run-to-run with the same cache file: recorded tier, zero re-timing
    reg2 = AutotuneRegistry()
    _register_spmm(reg2)
    w2, t2 = measured_block_search(60, 8, 3, registry=reg2, repeats=2)
    assert (w2, t2) == (w1, {})


def test_choose_block_sizes_uses_measured_winner(tmp_path, monkeypatch):
    import importlib
    # the package __init__ re-exports a same-named function, so resolve
    # the submodule explicitly
    spmm = importlib.import_module("repro.kernels.gee_spmm")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE_MEASURE", "1")
    reg = AutotuneRegistry()
    _register_spmm(reg)
    monkeypatch.setattr(spmm, "REGISTRY", reg)
    blocks = spmm.choose_block_sizes(60, 8, 3)
    key = spmm.pow2_bucket(60, 8, 3)
    assert key in reg.recorded(spmm.KERNEL_NAME)
    want = reg.lookup(spmm.KERNEL_NAME, key)
    # clamps to the bucketed plane still apply on top of the winner
    assert blocks[0] <= want[0] and blocks[1] <= want[1]
