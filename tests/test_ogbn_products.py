"""The ogbn-products deployment: the OGB registry entry, its partly
labelled stand-in, and every in-memory path the deployment runs on a small
graph of its shape (K=47, ~8% labelled, mean degree ~50, skewed degrees),
held against the float64 SciPy reference under all 8 option settings."""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import GEEEmbedder
from repro.core.gee import ALL_OPTION_SETTINGS, gee_scipy
from repro.core.plan import PreparedGraph
from repro.graph import datasets
from repro.graph.datasets import OGB, REGISTRY, TABLE2, DatasetSpec, load

OPT_IDS = [o.tag() for o in ALL_OPTION_SETTINGS]

# ogbn-products' shape at 2,000 vertices: 50.5 edges a vertex, 47 classes,
# 8.03% of the vertices labelled
MINI = DatasetSpec("ogbn-products-mini", 2_000, 50_500, 47, labelled=161)

# sha256 of (src, dst, labels) as int32 of synth_like's draws before the
# labelled count existed; a Table 2 draw must not move
TABLE2_DIGESTS = {
    ("citeseer", 0):
        "3b7ff2b927c02b5edc582503fcf7324fb56ec6dc144eb92111b881578b35e358",
    ("cora", 5):
        "9be624055fd9f258cd2427c58f914770fb655c0f04785bca30abace2b99a96e2",
}


def _digest(ds) -> str:
    e = ds.edges.num_edges
    h = hashlib.sha256()
    for a in (np.asarray(ds.edges.src)[:e], np.asarray(ds.edges.dst)[:e],
              np.asarray(ds.labels)):
        h.update(np.ascontiguousarray(a, np.int32).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

def test_ogb_spec_is_the_published_split():
    spec = OGB["ogbn-products"]
    assert (spec.num_nodes, spec.num_edges, spec.num_classes) == (
        2_449_029, 61_859_140, 47)
    assert spec.num_labelled == 196_615
    assert REGISTRY["ogbn-products"] is spec
    # Table 2 specs stay fully labelled
    assert all(s.labelled is None and s.num_labelled == s.num_nodes
               for s in TABLE2.values())


def test_load_resolves_ogbn_products_with_its_known_labels(monkeypatch):
    """``load`` reaches the OGB registry and marks all but the train
    split's 196,615 vertices unknown.  The edge draw is cut to 1,000 pairs
    so the check runs at the full vertex count in a moment."""
    def few_pairs(rng, n, count, p):
        src = np.arange(1_000, dtype=np.int32)
        return src, src + 1

    monkeypatch.setattr(datasets, "_sample_loop_free_pairs", few_pairs)
    ds = load("ogbn-products", seed=7)
    assert ds.spec is OGB["ogbn-products"]
    y = np.asarray(ds.labels)
    assert y.shape == (2_449_029,)
    assert np.count_nonzero(y >= 0) == 196_615
    assert set(np.unique(y)) <= set(range(-1, 47))
    assert np.array_equal(np.asarray(load("OGBN-Products", seed=7).labels), y)


@pytest.mark.parametrize("name,seed", sorted(TABLE2_DIGESTS))
def test_table2_draw_is_unchanged(name, seed):
    ds = datasets.synth_like(TABLE2[name], seed=seed)
    assert _digest(ds) == TABLE2_DIGESTS[(name, seed)]
    # the labels are still the stream's first draw, all of them known
    spec = TABLE2[name]
    first = np.random.default_rng(seed).integers(0, spec.num_classes,
                                                 size=spec.num_nodes)
    assert np.array_equal(np.asarray(ds.labels), first)


def test_labelled_subset_is_drawn_after_the_edges():
    """A partly labelled spec draws the same edges and the same classes as
    its fully labelled twin, and only hides labels outside its subset."""
    full = datasets.synth_like(
        DatasetSpec("t", MINI.num_nodes, MINI.num_edges, 47), seed=3)
    part = datasets.synth_like(MINI, seed=3)
    e = full.edges.num_edges
    for a, b in ((full.edges.src, part.edges.src),
                 (full.edges.dst, part.edges.dst)):
        assert np.array_equal(np.asarray(a)[:e], np.asarray(b)[:e])
    y_full, y = np.asarray(full.labels), np.asarray(part.labels)
    known = y >= 0
    assert known.sum() == MINI.num_labelled
    assert np.array_equal(y[known], y_full[known])
    other = np.asarray(datasets.synth_like(MINI, seed=4).labels) >= 0
    assert not np.array_equal(known, other)


def test_synth_to_disk_keeps_the_labelled_subset(tmp_path):
    from repro.graph.io import load_labels

    path = datasets.synth_to_disk(MINI, str(tmp_path / "g.geeb"), seed=2)
    y = load_labels(path)
    assert np.count_nonzero(y >= 0) == MINI.num_labelled
    ds = load(path)
    assert ds.spec.num_labelled == MINI.num_labelled


def test_gee_run_names_ogbn_products(monkeypatch, capsys):
    """``gee_run --dataset ogbn-products`` loads the registry entry and
    fits it through the plan; the entry is swapped for its small twin so
    the run fits a CPU test."""
    from repro.launch import gee_run

    small = DatasetSpec("ogbn-products", 500, 12_625, 47, labelled=40)
    monkeypatch.setitem(REGISTRY, "ogbn-products", small)
    gee_run.main(["--dataset", "ogbn-products", "--lap", "--diag", "--cor"])
    out = capsys.readouterr().out
    assert "ogbn-products: N=500 E=12625 K=47 known=40" in out


# ---------------------------------------------------------------------------
# every in-memory path against the reference, K=47, 8% labelled
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mini():
    ds = datasets.synth_like(MINI, seed=0)
    src, dst, w = ds.edges.valid_arrays()
    deg = np.bincount(src, minlength=MINI.num_nodes)
    assert 45 <= deg.mean() <= 56 and deg.max() > 8 * deg.mean()   # skewed
    return ds, PreparedGraph.wrap(ds.edges), (src, dst, w)


PATHS = [("pallas", "host"), ("pallas", "device"), ("sparse_jax", "host")]


# Tolerance 1e-5 max-abs, the repository's cross-backend gate: every path
# accumulates in float32 (the Pallas contraction at HIGHEST), the
# reference in float64; with correlation on, Z is row-normalised to <= 1,
# and without it the entries are sums of at most a few hundred terms of
# size <= 1/n_k, so float32 rounding stays near 1e-7 (every path read
# 1.2e-7 at most over the 8 settings on this graph).
@pytest.mark.pallas_interpret
@pytest.mark.parametrize("opts", ALL_OPTION_SETTINGS, ids=OPT_IDS)
@pytest.mark.parametrize("backend,labels_on", PATHS,
                         ids=["pallas-fused", "pallas-device-labels",
                              "sparse_jax"])
def test_paths_match_reference_k47_partly_labelled(mini, backend, labels_on,
                                                   opts):
    """Labels already on the device give the Pallas fit the same Z, bit
    for bit, as the host labels they were uploaded from."""
    ds, prep, (src, dst, w) = mini
    labels = np.asarray(ds.labels)
    emb = GEEEmbedder(num_classes=47, options=opts, backend=backend)
    z = np.asarray(emb.fit(prep, jnp.asarray(labels) if labels_on == "device"
                           else labels).transform())
    assert emb.plan.backend == backend
    assert emb.plan.fused is (backend == "pallas")
    if labels_on == "device":
        z_host = GEEEmbedder(num_classes=47, options=opts, backend=backend)
        assert np.array_equal(
            z, np.asarray(z_host.fit(prep, labels).transform()))
    ref = gee_scipy(src, dst, w, labels, 47, opts,
                    num_nodes=MINI.num_nodes)
    assert z.shape == (MINI.num_nodes, 47)
    np.testing.assert_allclose(z, ref, rtol=0, atol=1e-5)
