"""Plan/executor layer: PreparedGraph memoization, GEEPlan equivalence
across every backend, the cost-model auto selection, the shared epilogue
numerics, and the unified autotune registry."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import epilogue
from repro.core.gee import (ALL_OPTION_SETTINGS, GEEOptions, gee,
                            gee_sparse_jax)
from repro.core.plan import (GEEPlan, PreparedGraph, estimate_working_set_bytes,
                             select_backend, sweep_options)
from repro.graph.containers import (add_self_loops, edge_list_from_numpy,
                                    symmetrize)
from repro.kernels.autotune import (AutotuneRegistry, REGISTRY, ceil_to,
                                    pow2_at_least, pow2_bucket)

OPTS_ALL = GEEOptions(laplacian=True, diag_aug=True, correlation=True)


def _random_edges(n=60, e=240, seed=0, weighted=True):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = (src + 1 + rng.integers(0, n - 1, e)) % n
    w = (rng.random(e).astype(np.float32) + 0.1) if weighted else None
    return symmetrize(edge_list_from_numpy(src, dst, w, n))


def _random_labels(n=60, k=4, seed=0):
    return np.random.default_rng(seed).integers(-1, k, n).astype(np.int32)


# ---------------------------------------------------------------------------
# PreparedGraph: cached artifacts == fresh counterparts
# ---------------------------------------------------------------------------

def test_prepared_artifacts_match_fresh():
    edges = _random_edges()
    prep = PreparedGraph.wrap(edges)

    aug = prep.with_self_loops()
    fresh_aug = add_self_loops(edges)
    for f in ("src", "dst", "weight"):
        np.testing.assert_array_equal(np.asarray(getattr(aug, f)),
                                      np.asarray(getattr(fresh_aug, f)))
    assert aug.num_edges == fresh_aug.num_edges

    for diag in (False, True):
        e = fresh_aug if diag else edges
        deg = np.asarray(prep.degrees(diag))
        ref = np.zeros(edges.num_nodes, np.float32)
        np.add.at(ref, np.asarray(e.src), np.asarray(e.weight))
        np.testing.assert_allclose(deg, ref, rtol=1e-5, atol=1e-5)

    # effective edges: second call returns the identical cached object
    eff1 = prep.effective_edges(OPTS_ALL)
    eff2 = prep.effective_edges(GEEOptions(laplacian=True, diag_aug=True))
    assert eff1 is eff2            # correlation never invalidates prep
    info = prep.cache_info()
    assert info["hits"] >= 1


def test_prepared_effective_edges_numerics():
    """Scatter over cached effective edges == the fused one-jit path."""
    edges = _random_edges(seed=3)
    labels = _random_labels(seed=3)
    prep = PreparedGraph.wrap(edges)
    for opts in ALL_OPTION_SETTINGS:
        eff = prep.effective_edges(opts)
        z_prep = np.asarray(gee_sparse_jax(
            eff, jnp.asarray(labels), 4,
            GEEOptions(correlation=opts.correlation)))
        z_fused = np.asarray(gee_sparse_jax(edges, jnp.asarray(labels), 4,
                                            opts))
        np.testing.assert_allclose(z_prep, z_fused, atol=1e-6,
                                   err_msg=opts.tag())


def test_prepared_from_arrays_symmetrizes_once():
    src = np.array([0, 1, 2])
    dst = np.array([1, 2, 0])
    prep = PreparedGraph.from_arrays(src, dst, None, num_nodes=3)
    assert prep.num_edges == 6          # symmetrized
    direct = PreparedGraph.from_arrays(src, dst, None, num_nodes=3,
                                       undirected=False)
    assert direct.num_edges == 3


def test_prepared_wrap_idempotent_and_typed():
    edges = _random_edges()
    prep = PreparedGraph.wrap(edges)
    assert PreparedGraph.wrap(prep) is prep
    with pytest.raises(TypeError):
        PreparedGraph(prep)
    with pytest.raises(TypeError):
        PreparedGraph("not edges")


# ---------------------------------------------------------------------------
# hypothesis property: every cached artifact equals its fresh counterpart
# ---------------------------------------------------------------------------

def _check_cached_equals_fresh(edges, lap, diag):
    """PreparedGraph artifacts must be exactly what a cold path derives."""
    from repro.graph.ell import edges_to_bucketed_ell
    from repro.graph.io import ChunkedEdgeList

    prep = PreparedGraph.wrap(edges)
    opts = GEEOptions(laplacian=lap, diag_aug=diag)

    eff_cold_edges = add_self_loops(edges) if diag else edges
    if lap:
        from repro.core.gee import laplacian_edge_weights
        w_cold = np.asarray(laplacian_edge_weights(eff_cold_edges))
    else:
        w_cold = np.asarray(eff_cold_edges.weight)
    eff = prep.effective_edges(opts)
    eff_again = prep.effective_edges(opts)
    assert eff is eff_again
    np.testing.assert_allclose(np.asarray(eff.weight), w_cold, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(eff.src),
                                  np.asarray(eff_cold_edges.src))

    bell = prep.bucketed_ell(diag)
    bell_cold = edges_to_bucketed_ell(add_self_loops(edges) if diag
                                      else edges)
    assert len(bell.buckets) == len(bell_cold.buckets)
    for b, bc in zip(bell.buckets, bell_cold.buckets):
        np.testing.assert_array_equal(np.asarray(b.cols),
                                      np.asarray(bc.cols))
        np.testing.assert_allclose(np.asarray(b.vals), np.asarray(bc.vals),
                                   atol=0)

    ch = prep.chunked(16)
    ch_cold = ChunkedEdgeList.from_edge_list(edges, 16)
    np.testing.assert_array_equal(ch.src, ch_cold.src)
    np.testing.assert_array_equal(ch.weight, ch_cold.weight)
    assert prep.chunked(16) is ch      # memoized per window size


@pytest.mark.parametrize("lap,diag", [(False, False), (True, True)])
def test_cached_equals_fresh_deterministic(lap, diag):
    """Always-on twin of the hypothesis property below."""
    _check_cached_equals_fresh(_random_edges(n=30, e=80, seed=5), lap, diag)


try:                       # optional dep: only the property test needs it
    from hypothesis import given, settings, strategies as st

    @st.composite
    def small_graph(draw):
        n = draw(st.integers(2, 30))
        e = draw(st.integers(1, 80))
        src = draw(st.lists(st.integers(0, n - 1), min_size=e, max_size=e))
        dst = draw(st.lists(st.integers(0, n - 1), min_size=e, max_size=e))
        w = draw(st.lists(st.floats(0.1, 5.0, allow_nan=False), min_size=e,
                          max_size=e))
        return symmetrize(edge_list_from_numpy(
            np.array(src, np.int32), np.array(dst, np.int32),
            np.array(w, np.float32), n))

    @settings(max_examples=25, deadline=None)
    @given(small_graph(), st.booleans(), st.booleans())
    def test_property_cached_equals_fresh(edges, lap, diag):
        _check_cached_equals_fresh(edges, lap, diag)

except ImportError:        # pragma: no cover - minimal installs
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_cached_equals_fresh():
        pass


# ---------------------------------------------------------------------------
# GEEPlan: every backend numerically equivalent through the plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS,
                         ids=[o.tag() for o in ALL_OPTION_SETTINGS])
def test_all_backends_equivalent_through_plan(opts):
    edges = _random_edges(n=80, e=400, seed=7)
    labels = _random_labels(n=80, seed=7)
    prep = PreparedGraph.wrap(edges)
    ref = np.asarray(GEEPlan.build(prep, 4, opts,
                                   backend="dense_jax").execute(labels))
    for backend in ("sparse_jax", "pallas", "chunked", "scipy",
                    "python_loop"):
        z = np.asarray(GEEPlan.build(prep, 4, opts,
                                     backend=backend).execute(labels))
        assert np.abs(z - ref).max() <= 1e-5, (backend, opts.tag())


def test_plan_stages_and_describe():
    prep = PreparedGraph.wrap(_random_edges())
    plan = GEEPlan.build(prep, 4, OPTS_ALL, backend="sparse_jax")
    kinds = [s.kind for s in plan.stages]
    assert kinds == ["prep", "compute", "epilogue"]
    assert not plan.stages[0].cached
    plan.execute(_random_labels())
    # same plan after execution: the prep artifact is now resident
    assert GEEPlan.build(prep, 4, OPTS_ALL).stages[0].cached
    assert "segment_scatter" in plan.describe()


def test_plan_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown backend"):
        GEEPlan.build(_random_edges(), 4, backend="nope")


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_select_backend_cost_model():
    edges = _random_edges()
    # tiny budget -> out-of-core streaming (pin num_devices: the default
    # asks jax, and the ambient device count is the suite's, not ours)
    assert select_backend(edges, 4, budget_bytes=16,
                          num_devices=1) == "chunked"
    # ample budget off-TPU -> the segment-sum default
    assert select_backend(edges, 4, device="cpu",
                          budget_bytes=1 << 40) == "sparse_jax"
    # TPU with lane-sized K -> the kernel; huge K -> back to segment-sum
    assert select_backend(edges, 4, device="tpu",
                          budget_bytes=1 << 40) == "pallas"
    assert select_backend(edges, 100_000, device="tpu",
                          budget_bytes=1 << 40) == "sparse_jax"
    assert estimate_working_set_bytes(edges, 4) > 0


def test_select_backend_streams_across_devices_over_budget():
    edges = _random_edges()
    # over budget + >1 device: split every window across the mesh
    assert select_backend(edges, 4, budget_bytes=16,
                          num_devices=4) == "streamed_sharded"
    # a single device still streams through the chunked fold
    assert select_backend(edges, 4, budget_bytes=16,
                          num_devices=1) == "chunked"


def test_pallas_estimate_sees_ell_padding_blowup():
    """Regression (cost model): on a skewed degree distribution the
    bucketed ELL packing costs far more than the raw edge count -- the
    flat estimate used to route hub graphs to ``pallas`` that could not
    fit after packing."""
    n = 2000                               # star: hub 0 <-> every other node
    hub = np.zeros(n - 1, np.int64)
    spokes = np.arange(1, n, dtype=np.int64)
    edges = edge_list_from_numpy(np.concatenate([hub, spokes]),
                                 np.concatenate([spokes, hub]), None, n)
    flat = estimate_working_set_bytes(edges, 4)
    packed = estimate_working_set_bytes(edges, 4, backend="pallas")
    # hub row pads to pow2(~n) slots; the tail pads to the 8-wide bucket
    assert packed > 1.5 * flat
    # budget between the two: the kernel must NOT be selected on TPU...
    budget = (flat + packed) // 2
    assert flat < budget < packed
    assert select_backend(edges, 4, device="tpu",
                          budget_bytes=budget) == "sparse_jax"
    # ...but a budget that covers the packed set still picks it
    assert select_backend(edges, 4, device="tpu",
                          budget_bytes=1 << 40) == "pallas"
    # PreparedGraph memoizes the O(E) slot count under ("ell_slots",)
    prep = PreparedGraph.wrap(edges)
    assert estimate_working_set_bytes(prep, 4, backend="pallas") \
        == estimate_working_set_bytes(prep, 4, backend="pallas")
    assert prep.is_cached(("ell_slots",))


def test_auto_routes_to_chunked_by_budget(monkeypatch):
    from repro.core.plan import ENV_MEMORY_BUDGET

    monkeypatch.setenv(ENV_MEMORY_BUDGET, "64")
    edges = _random_edges()
    plan = GEEPlan.build(edges, 4, OPTS_ALL, backend="auto")
    assert plan.backend == "chunked"
    z = np.asarray(plan.execute(_random_labels()))
    ref = np.asarray(gee(edges, _random_labels(), 4, OPTS_ALL,
                         backend="sparse_jax"))
    np.testing.assert_allclose(z, ref, atol=1e-5)


# ---------------------------------------------------------------------------
# satellite: gee(backend="chunked") reuses the cached chunk manifest
# ---------------------------------------------------------------------------

def test_chunked_backend_no_rebuild(monkeypatch):
    from repro.graph import io as gio

    calls = {"n": 0}
    real = gio.ChunkedEdgeList.from_edge_list    # staticmethod -> function

    def counting(edges, chunk_edges=gio.DEFAULT_CHUNK_EDGES):
        calls["n"] += 1
        return real(edges, chunk_edges)

    monkeypatch.setattr(gio.ChunkedEdgeList, "from_edge_list",
                        staticmethod(counting))
    edges = _random_edges()
    labels = _random_labels()
    prep = PreparedGraph.wrap(edges)
    z1 = gee(prep, labels, 4, OPTS_ALL, backend="chunked")
    z2 = gee(prep, labels, 4, GEEOptions(), backend="chunked")
    assert calls["n"] == 1, "second chunked fit rebuilt the manifest"
    assert prep.is_cached(("chunked", gio.DEFAULT_CHUNK_EDGES))
    del z1, z2


def test_embedder_chunked_backend_no_rebuild(monkeypatch):
    from repro.core.api import GEEEmbedder
    from repro.graph import io as gio

    calls = {"n": 0}
    real = gio.ChunkedEdgeList.from_edge_list    # staticmethod -> function

    def counting(edges, chunk_edges=gio.DEFAULT_CHUNK_EDGES):
        calls["n"] += 1
        return real(edges, chunk_edges)

    monkeypatch.setattr(gio.ChunkedEdgeList, "from_edge_list",
                        staticmethod(counting))
    edges = _random_edges()
    labels = _random_labels()
    emb = GEEEmbedder(num_classes=4, backend="chunked", chunk_edges=64)
    emb.fit(edges, labels)
    emb.transform()
    emb._z = None                  # force a recompute on the same fit
    emb.transform()
    assert calls["n"] == 1, "recompute rebuilt the chunk manifest"


# ---------------------------------------------------------------------------
# sweep_options: the 8-setting fast path is exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["sparse_jax", "chunked"])
def test_sweep_options_matches_per_call(backend):
    edges = _random_edges(n=50, e=200, seed=9)
    labels = _random_labels(n=50, seed=9)
    zs = sweep_options(edges, labels, 4, backend=backend)
    assert len(zs) == len(ALL_OPTION_SETTINGS)
    for opts, z in zs.items():
        ref = np.asarray(gee(edges, labels, 4, opts, backend="sparse_jax"))
        assert np.abs(np.asarray(z) - ref).max() <= 1e-5, opts.tag()


def test_embedder_consumes_prepared():
    from repro.core.api import GEEEmbedder

    edges = _random_edges()
    labels = _random_labels()
    emb1 = GEEEmbedder(num_classes=4).fit(edges, labels)
    z1 = np.asarray(emb1.transform())
    # a second embedder reuses the first one's prep artifacts
    emb2 = GEEEmbedder(num_classes=4,
                       options=GEEOptions(laplacian=True)).fit(
        emb1.prepared, labels)
    assert emb2.prepared is emb1.prepared
    z2 = np.asarray(emb2.transform())
    ref = np.asarray(gee(edges, labels, 4, GEEOptions(laplacian=True)))
    np.testing.assert_allclose(z2, ref, atol=1e-6)
    assert z1.shape == z2.shape


def test_embedder_exposes_resolved_plan():
    from repro.core.api import GEEEmbedder

    edges = _random_edges()
    labels = _random_labels()
    emb = GEEEmbedder(num_classes=4, options=OPTS_ALL, backend="auto")
    assert emb.fit(edges, labels).plan is None        # nothing ran yet
    z = np.asarray(emb.transform())
    plan = emb.plan
    assert plan.backend != "auto"
    assert plan.backend == GEEPlan.build(emb.prepared, 4, OPTS_ALL,
                                         backend="auto").backend
    ref = np.asarray(gee(edges, labels, 4, OPTS_ALL))
    np.testing.assert_allclose(z, ref, atol=1e-5)
    emb.fit(edges, labels)                            # a refit forgets it
    assert emb.plan is None


# ---------------------------------------------------------------------------
# shared epilogue numerics
# ---------------------------------------------------------------------------

def test_epilogue_impls_agree():
    rng = np.random.default_rng(0)
    z = rng.normal(size=(37, 5)).astype(np.float32)
    z[5] = 0.0                                   # zero row stays zero
    a = np.asarray(epilogue.row_l2_normalize(jnp.asarray(z), impl="jnp"))
    b = np.asarray(epilogue.row_l2_normalize(jnp.asarray(z), impl="pallas",
                                             interpret=True))
    c = epilogue.row_l2_normalize_np(z)
    np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(a, c.astype(np.float32), atol=1e-6)
    np.testing.assert_array_equal(a[5], np.zeros(5, np.float32))
    np.testing.assert_allclose(np.linalg.norm(a[0]), 1.0, atol=1e-6)
    with pytest.raises(ValueError, match="unknown impl"):
        epilogue.row_l2_normalize(jnp.asarray(z), impl="bogus")


def test_epilogue_degree_inversion_twins():
    deg = np.array([0.0, 1.0, 4.0, 1e-35], np.float64)
    a = np.asarray(epilogue.inv_sqrt_degrees(jnp.asarray(deg,
                                                         jnp.float32)))
    b = epilogue.inv_sqrt_degrees_np(deg)
    np.testing.assert_allclose(a[:3], b[:3].astype(np.float32), rtol=1e-6)
    assert a[0] == 0.0 and b[0] == 0.0


# ---------------------------------------------------------------------------
# unified autotune registry
# ---------------------------------------------------------------------------

def test_autotune_helpers():
    assert ceil_to(1, 8) == 8 and ceil_to(8, 8) == 8 and ceil_to(9, 8) == 16
    assert pow2_at_least(0) == 1 and pow2_at_least(5) == 8
    assert pow2_bucket(3, 100, 1) == (4, 128, 1)


def test_registry_resolution_order_and_roundtrip(tmp_path):
    reg = AutotuneRegistry()
    reg.register("k", table={(8, 8): (1, 1)},
                 fallback=lambda key: (key[0], key[1]))
    assert reg.lookup("k", (8, 8)) == (1, 1)        # table
    assert reg.lookup("k", (16, 8)) == (16, 8)      # formula
    reg.record("k", (16, 8), (2, 2))                # measurement wins
    assert reg.lookup("k", (16, 8)) == (2, 2)
    path = str(tmp_path / "tune.json")
    assert reg.save(path) == path

    reg2 = AutotuneRegistry()
    reg2.register("k", fallback=lambda key: (0, 0))
    assert reg2.load(path) == 1
    assert reg2.lookup("k", (16, 8)) == (2, 2)      # persisted entry
    assert reg2.load(str(tmp_path / "absent.json")) == 0
    reg2.clear("k")
    assert reg2.lookup("k", (16, 8)) == (0, 0)
    with pytest.raises(KeyError):
        reg.lookup("unregistered", (1,))


def test_registry_env_persistence(tmp_path, monkeypatch):
    from repro.kernels.autotune import ENV_CACHE_PATH

    path = str(tmp_path / "env_tune.json")
    monkeypatch.setenv(ENV_CACHE_PATH, path)
    reg = AutotuneRegistry()
    reg.register("k", fallback=lambda key: (3,))
    reg.record("k", (4,), (9,))
    assert reg.save() == path                       # env default path
    reg2 = AutotuneRegistry()
    reg2.register("k", fallback=lambda key: (3,))
    assert reg2.lookup("k", (4,)) == (9,)           # lazy env load


def test_shared_registry_serves_kernels():
    """The real kernels resolve through the one shared REGISTRY."""
    from repro.kernels.gee_spmm import choose_block_sizes
    from repro.kernels.topk_score import (choose_gathered_blocks,
                                          choose_pairwise_blocks)

    assert {"gee_spmm", "topk_pairwise",
            "topk_gathered"} <= set(REGISTRY.kernels())
    br, bd, ds = choose_block_sizes(1000, 100, 4)
    assert br % 8 == 0 and bd >= 8 and 1 <= ds <= bd
    bq, bm = choose_pairwise_blocks(100, 1000, 4)
    assert bq >= 8 and bm >= 8
    bq, bm = choose_gathered_blocks(100, 500, 4)
    assert bq >= 8 and bm >= 8


def test_deprecated_helper_aliases_still_importable():
    from repro.core.gee import select_backend as old_select
    from repro.kernels.gee_spmm import (_ceil_to as c1,
                                        _pow2_at_least as p1)
    from repro.kernels.row_norm import _ceil_to as c2
    from repro.kernels.topk_score import (_ceil_to as c3,
                                          _pow2_at_least as p2)

    assert c1(9, 8) == c2(9, 8) == c3(9, 8) == 16
    assert p1(5) == p2(5) == 8
    assert old_select(_random_edges(), 4) in ("sparse_jax", "pallas",
                                              "chunked")
