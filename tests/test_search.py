"""Vertex similarity retrieval: kernels, index, service, embedder wiring.

The two acceptance properties this file pins down:

* ``nprobe = num_cells`` is *exact*: recall@10 == 1.0 against brute force
  (every vertex lives in exactly one bucket, so probing all cells scans
  everything), and the default ``nprobe`` stays >= 0.9 on the paper's SBM.
* After ``partial_fit`` deltas, queries reflect the updated embedding via
  incremental bucket repair -- equivalent to a freshly built index on the
  mutated graph to 1e-5, with no index rebuild.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.api import GEEEmbedder
from repro.core.gee import GEEOptions, gee_sparse_jax
from repro.core.incremental import IncrementalGEE
from repro.graph.containers import edge_list_from_numpy, symmetrize
from repro.graph.delta import (edge_delta_from_numpy, label_delta_from_numpy,
                               symmetrize_delta)
from repro.graph.sbm import sample_sbm
from repro.kernels.topk_score import (NEG_INF, gathered_scores, masked_topk,
                                      pairwise_scores)
from repro.launch.gee_search import recall_at_k
from repro.search.index import ClassPartitionedIndex, default_nprobe
from repro.search.service import GEEDeltaServer, GEEQueryService

OPTS = GEEOptions(laplacian=True, diag_aug=True, correlation=True)


def _embed(sample, opts=OPTS):
    return np.asarray(gee_sparse_jax(sample.edges, jnp.asarray(sample.labels),
                                     sample.num_classes, opts))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_pairwise_scores_pallas_matches_jax_and_numpy(metric):
    rng = np.random.default_rng(0)
    q = rng.normal(size=(7, 5)).astype(np.float32)
    x = rng.normal(size=(23, 5)).astype(np.float32)
    x[3] = 0.0                                    # zero row: cosine -> 0
    valid = (rng.random(23) > 0.25).astype(np.float32)
    sj = np.asarray(pairwise_scores(q, x, valid, metric=metric, impl="jax"))
    sp = np.asarray(pairwise_scores(q, x, valid, metric=metric,
                                    impl="pallas", interpret=True))
    if metric == "l2":
        ref = -((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    else:
        qn = np.linalg.norm(q, axis=1, keepdims=True)
        xn = np.linalg.norm(x, axis=1)[None, :]
        ref = np.divide(q @ x.T, qn * xn, out=np.zeros((7, 23), np.float32),
                        where=qn * xn > 0)
    ref = np.where(valid[None, :] > 0, ref, NEG_INF)
    np.testing.assert_allclose(sj, ref, atol=1e-5)
    np.testing.assert_allclose(sp, sj, atol=1e-6)


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_gathered_scores_pallas_matches_jax(metric):
    rng = np.random.default_rng(1)
    q = rng.normal(size=(6, 4)).astype(np.float32)
    cand = rng.normal(size=(6, 17, 4)).astype(np.float32)
    mask = (rng.random((6, 17)) > 0.3).astype(np.float32)
    gj = np.asarray(gathered_scores(q, cand, mask, metric=metric,
                                    impl="jax"))
    gp = np.asarray(gathered_scores(q, cand, mask, metric=metric,
                                    impl="pallas", interpret=True))
    np.testing.assert_allclose(gp, gj, atol=1e-6)
    assert (gj[mask == 0] == NEG_INF).all()


def test_masked_topk_fills_unreachable_with_minus_one():
    scores = np.full((2, 4), NEG_INF, np.float32)
    scores[0, 2] = 1.0
    ids, sc = masked_topk(jnp.asarray(scores), None, 3)
    ids = np.asarray(ids)
    assert ids[0, 0] == 2 and (ids[0, 1:] == -1).all()
    assert (ids[1] == -1).all()
    # k beyond the candidate count pads with -1 / NEG_INF
    ids6, sc6 = masked_topk(jnp.asarray(scores), None, 6)
    assert np.asarray(ids6).shape == (2, 6)
    assert (np.asarray(ids6)[:, 4:] == -1).all()
    assert (np.asarray(sc6)[:, 4:] == NEG_INF).all()


# ---------------------------------------------------------------------------
# index: exactness + recall
# ---------------------------------------------------------------------------

def test_full_probe_is_exact_recall_one(sbm_small):
    z = _embed(sbm_small)
    idx = ClassPartitionedIndex.build(z, sbm_small.labels,
                                      sbm_small.num_classes, pad_multiple=64)
    rng = np.random.default_rng(2)
    q = z[rng.integers(0, z.shape[0], 64)]
    ids_f, sc_f = (np.asarray(a) for a in
                   idx.search(q, 10, nprobe=idx.num_cells))
    ids_b, sc_b = (np.asarray(a) for a in idx.search(q, 10, brute_force=True))
    assert recall_at_k(ids_f, sc_f, ids_b, sc_b) == 1.0
    np.testing.assert_allclose(sc_f, sc_b, atol=1e-6)


def test_default_nprobe_recall_on_sbm(sbm_medium):
    z = _embed(sbm_medium)
    idx = ClassPartitionedIndex.build(z, sbm_medium.labels,
                                      sbm_medium.num_classes)
    assert idx.nprobe == default_nprobe(idx.num_cells)
    rng = np.random.default_rng(3)
    q = z[rng.integers(0, z.shape[0], 128)]
    ids_d, sc_d = (np.asarray(a) for a in idx.search(q, 10))
    ids_b, sc_b = (np.asarray(a) for a in idx.search(q, 10, brute_force=True))
    assert recall_at_k(ids_d, sc_d, ids_b, sc_b) >= 0.9


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_metrics_and_search_rows_self_hit(sbm_small, metric):
    z = _embed(sbm_small)
    idx = ClassPartitionedIndex.build(z, sbm_small.labels,
                                      sbm_small.num_classes, metric=metric)
    rows = np.array([0, 7, 123])
    ids, sc = idx.search_rows(rows, 5, nprobe=idx.num_cells)
    assert (np.asarray(ids)[:, 0] == rows).all()   # self is the best hit
    i1, s1 = idx.search(z[7], 3)                   # single-vector query
    assert i1.shape == (3,) and int(np.asarray(i1)[0]) == 7


def test_unknown_labels_are_still_indexed(sbm_small):
    z = _embed(sbm_small)
    y = sbm_small.labels.copy()
    y[::5] = -1                                    # 20% unknown
    idx = ClassPartitionedIndex.build(z, y, sbm_small.num_classes)
    # every vertex is in exactly one bucket
    assert int(idx._cell_len.sum()) == z.shape[0]
    ids, _ = idx.search(z[5], 1, nprobe=idx.num_cells)   # unknown-label row
    assert int(np.asarray(ids)[0]) == 5


def test_all_unknown_degenerates_to_single_cell(sbm_small):
    z = _embed(sbm_small)
    idx = ClassPartitionedIndex.build(z, np.full(z.shape[0], -1, np.int32),
                                      sbm_small.num_classes)
    assert idx.num_cells == 1
    q = z[:16]
    ids_f, sc_f = idx.search(q, 10)                # single cell = exact
    ids_b, sc_b = idx.search(q, 10, brute_force=True)
    np.testing.assert_allclose(np.asarray(sc_f), np.asarray(sc_b), atol=1e-6)


# ---------------------------------------------------------------------------
# index: incremental repair
# ---------------------------------------------------------------------------

def test_update_rows_moves_buckets_and_stays_exact(sbm_small):
    z = _embed(sbm_small)
    idx = ClassPartitionedIndex.build(z, sbm_small.labels,
                                      sbm_small.num_classes, pad_multiple=64)
    rng = np.random.default_rng(4)
    rows = rng.choice(z.shape[0], 25, replace=False)
    z2 = z.copy()
    z2[rows] = rng.normal(size=(25, z.shape[1])).astype(np.float32)
    idx.update_rows(rows, z2[rows])
    assert idx.stats["repaired_rows"] == 25
    assert int(idx._cell_len.sum()) == z.shape[0]  # membership conserved
    fresh = ClassPartitionedIndex.build(z2, sbm_small.labels,
                                        sbm_small.num_classes)
    q = z2[rng.integers(0, z.shape[0], 32)]
    _, sc_a = idx.search(q, 10, nprobe=idx.num_cells)
    _, sc_b = fresh.search(q, 10, nprobe=fresh.num_cells)
    np.testing.assert_allclose(np.asarray(sc_a), np.asarray(sc_b), atol=1e-5)
    assert idx.stats["builds"] == 1                # repaired, not rebuilt


def test_update_rows_grows_full_bucket():
    # 2 tight clusters, tiny pad_multiple so moving everything into one
    # bucket must overflow its capacity
    rng = np.random.default_rng(5)
    n = 40
    z = np.concatenate([rng.normal(0, 0.05, (20, 2)),
                        rng.normal(5, 0.05, (20, 2))]).astype(np.float32)
    y = np.repeat([0, 1], 20).astype(np.int32)
    idx = ClassPartitionedIndex.build(z, y, 2, pad_multiple=8)
    cap0 = idx.bucket_capacity
    rows = np.arange(20, 40)
    z2 = z.copy()
    z2[rows] = rng.normal(0, 0.05, (20, 2)).astype(np.float32)  # all -> cell 0
    moved = idx.update_rows(rows, z2[rows])
    assert moved == 20
    assert idx.stats["table_grows"] >= 1 and idx.bucket_capacity > cap0
    assert int(idx._cell_len.sum()) == n
    _, sc_a = idx.search(z2[:8], 5, nprobe=idx.num_cells)
    fresh = ClassPartitionedIndex.build(z2, y, 2, pad_multiple=8)
    _, sc_b = fresh.search(z2[:8], 5, nprobe=fresh.num_cells)
    np.testing.assert_allclose(np.asarray(sc_a), np.asarray(sc_b), atol=1e-5)


# ---------------------------------------------------------------------------
# embedder wiring: neighbors + partial_fit repair (the acceptance test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opts", [
    GEEOptions(laplacian=True, diag_aug=True, correlation=True),
    GEEOptions(laplacian=False, diag_aug=True, correlation=False),
])
def test_partial_fit_repairs_index_no_rebuild(opts):
    s = sample_sbm(400, seed=21)
    emb = GEEEmbedder(num_classes=s.num_classes, options=opts).fit(
        s.edges, s.labels)
    emb.neighbors(np.arange(4), k=5)               # builds the index
    assert emb.index is not None and emb.index.stats["builds"] == 1

    rng = np.random.default_rng(6)
    src = rng.integers(0, 400, 30)
    dst = (src + 1 + rng.integers(0, 399, 30)) % 400
    emb.partial_fit(symmetrize_delta(edge_delta_from_numpy(
        src, dst, np.ones(30, np.float32))))
    emb.partial_fit(label_delta_from_numpy(
        np.array([3]), np.array([(int(s.labels[3]) + 1) % s.num_classes],
                                np.int32)))

    q = np.arange(32)
    ids_a, sc_a = emb.neighbors(q, k=10, nprobe=emb.index.num_cells)
    assert emb.index.stats["builds"] == 1          # repaired in place

    # oracle: a fresh embedder + index on the mutated graph
    y = np.asarray(emb.incremental.labels)
    fresh = GEEEmbedder(num_classes=s.num_classes, options=opts).fit(
        emb.current_edges(), y)
    ids_b, sc_b = fresh.neighbors(q, k=10, brute_force=True)
    np.testing.assert_allclose(np.asarray(sc_a), np.asarray(sc_b), atol=1e-5)
    assert recall_at_k(np.asarray(ids_a), np.asarray(sc_a),
                       np.asarray(ids_b), np.asarray(sc_b)) == 1.0


def test_neighbors_explicit_queries_and_refit_resets(sbm_small):
    emb = GEEEmbedder(num_classes=sbm_small.num_classes).fit(
        sbm_small.edges, sbm_small.labels)
    z = np.asarray(emb.transform())
    ids, sc = emb.neighbors(queries=z[:3], k=4)
    assert np.asarray(ids).shape == (3, 4)
    with pytest.raises(ValueError):
        emb.neighbors()                            # no rows, no queries
    emb.fit(sbm_small.edges, sbm_small.labels)     # refit drops the index
    assert emb.index is None


# ---------------------------------------------------------------------------
# query service
# ---------------------------------------------------------------------------

def _inc_and_index(sample, opts=OPTS, pad_multiple=64):
    inc = IncrementalGEE.from_graph(sample.edges, sample.labels,
                                    sample.num_classes, opts)
    idx = ClassPartitionedIndex.build(inc.embedding(), sample.labels,
                                      sample.num_classes,
                                      pad_multiple=pad_multiple)
    return inc, idx


def test_service_batches_and_pads(sbm_small):
    inc, idx = _inc_and_index(sbm_small)
    svc = GEEQueryService(idx, inc, flush_every=8, pad_multiple=8,
                          default_k=5)
    tickets = [svc.submit_rows(np.array([i])) for i in range(3)]
    assert not any(t.done for t in tickets)        # below flush threshold
    svc.flush()
    assert all(t.done for t in tickets)
    assert all(int(t.ids[0, 0]) == i for i, t in enumerate(tickets))
    assert svc.stats["flushes"] == 1
    assert svc.stats["pad_queries"] == 5           # 3 queries padded to 8
    # auto-flush once the backlog reaches flush_every
    t8 = [svc.submit_rows(np.array([i])) for i in range(8)]
    assert all(t.done for t in t8)


def test_service_repairs_on_delta(sbm_small):
    inc, idx = _inc_and_index(sbm_small)
    svc = GEEQueryService(idx, inc, flush_every=1 << 30)
    inc.apply_edges(symmetrize_delta(edge_delta_from_numpy(
        np.array([0]), np.array([200]), np.array([1.0]))))
    assert svc.stale_rows > 0
    ids, sc = svc.search(np.asarray(inc.embedding())[:8], k=10)
    assert svc.stale_rows == 0
    assert svc.stats["repaired_rows"] > 0
    assert idx.stats["builds"] == 1
    # equivalence against a fresh index on the mutated state
    fresh = ClassPartitionedIndex.build(
        inc.embedding(), np.asarray(inc.labels), sbm_small.num_classes)
    _, sc_b = fresh.search(np.asarray(inc.embedding())[:8], 10,
                           nprobe=fresh.num_cells)
    _, sc_a = idx.search(np.asarray(inc.embedding())[:8], 10,
                         nprobe=idx.num_cells)
    np.testing.assert_allclose(np.asarray(sc_a), np.asarray(sc_b), atol=1e-5)


def test_service_full_refresh_on_label_flip(sbm_small):
    inc, idx = _inc_and_index(sbm_small)
    svc = GEEQueryService(idx, inc)
    node = 11
    new = (int(sbm_small.labels[node]) + 1) % sbm_small.num_classes
    inc.apply_labels(label_delta_from_numpy(np.array([node]),
                                            np.array([new], np.int32)))
    assert svc.stale_rows == inc.n                 # 1/n_k moved: all stale
    svc.flush()
    assert svc.stats["full_refreshes"] == 1
    assert svc.stale_rows == 0


def test_service_composes_with_delta_server(sbm_small):
    inc, idx = _inc_and_index(sbm_small)
    svc = GEEQueryService(idx, inc)
    srv = GEEDeltaServer(inc, flush_every=1 << 30)
    srv.submit(symmetrize_delta(edge_delta_from_numpy(
        np.array([1]), np.array([300]), np.array([1.0]))))
    assert svc.stale_rows == 0                     # queued, not yet applied
    srv.flush()
    assert svc.stale_rows > 0                      # applied -> invalidated


def test_delta_server_import_from_old_location():
    from repro.search.service import GEEDeltaServer as new_loc
    from repro.serve.batching import GEEDeltaServer as old_loc

    assert old_loc is new_loc


# ---------------------------------------------------------------------------
# file-backed path: index over fit_transform_file output
# ---------------------------------------------------------------------------

def test_index_over_file_backed_fit(tmp_path):
    from repro.graph.datasets import DatasetSpec, synth_to_disk

    path = str(tmp_path / "g.geeb")
    synth_to_disk(DatasetSpec("g", 300, 1500, 3), path, seed=0)
    emb = GEEEmbedder(num_classes=3, chunk_edges=512)
    emb.fit_file(path)
    index = emb.build_index()
    ids, sc = emb.neighbors(np.arange(8), k=5, nprobe=index.num_cells)
    ids_b, sc_b = emb.neighbors(np.arange(8), k=5, brute_force=True)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_b), atol=1e-6)


# ---------------------------------------------------------------------------
# IncrementalGEE dirty listener contract
# ---------------------------------------------------------------------------

def test_service_close_unsubscribes(sbm_small):
    inc, idx = _inc_and_index(sbm_small)
    svc = GEEQueryService(idx, inc)
    inc.apply_edges(symmetrize_delta(edge_delta_from_numpy(
        np.array([0]), np.array([100]), np.array([1.0]))))
    assert svc.stale_rows > 0
    svc.flush()
    svc.close()
    svc.close()                                    # idempotent
    inc.apply_edges(symmetrize_delta(edge_delta_from_numpy(
        np.array([1]), np.array([200]), np.array([1.0]))))
    assert svc.stale_rows == 0                     # no longer subscribed


def test_dirty_listener_rows_and_full_flag():
    src = np.array([0, 1, 2]); dst = np.array([1, 2, 3])
    edges = symmetrize(edge_list_from_numpy(src, dst, None, 5))
    y = np.array([0, 0, 1, 1, -1], np.int32)
    inc = IncrementalGEE.from_graph(edges, y, 2, GEEOptions())
    events = []
    inc.add_dirty_listener(lambda rows, full: events.append(
        (sorted(int(r) for r in rows), full)))
    inc.apply_edges(edge_delta_from_numpy(np.array([0]), np.array([3]),
                                          np.array([1.0])))
    assert events[-1] == ([0], False)              # plain mode: row 0 only
    inc.apply_labels(label_delta_from_numpy(np.array([3]), np.array([0])))
    assert events[-1][1] is True                   # label flip: full
    n_events = len(events)
    inc.apply_labels(label_delta_from_numpy(np.array([3]), np.array([0])))
    assert len(events) == n_events                 # no-op flip: no event


def test_ivf_search_batches_match_one_batch(monkeypatch):
    """Query sets larger than the gather budget are split into equal
    batches (tail padded); results match one unsplit search exactly."""
    import repro.search.index as index_mod

    s = sample_sbm(600, seed=5)
    idx = ClassPartitionedIndex.build(_embed(s), s.labels, s.num_classes)
    qs = _embed(s)[:37]
    ids1, sc1 = idx.search(qs, 5)
    per_query = idx.nprobe * idx.bucket_capacity * 128 * 4
    monkeypatch.setattr(index_mod, "GATHER_BUDGET_BYTES", 8 * per_query)
    ids8, sc8 = idx.search(qs, 5)                  # 5 batches of 8 rows
    np.testing.assert_array_equal(np.asarray(ids8), np.asarray(ids1))
    np.testing.assert_array_equal(np.asarray(sc8), np.asarray(sc1))
