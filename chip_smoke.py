"""Smoke test of the GEE main paths on a TPU, at the paper's largest Table-2 size.

Runs, in one process, through the public entry points and on a graph
generated from ``--seed`` (the ``cl-100k-1d8-l5`` stand-in: 92,482 nodes,
10,000,000 undirected edges, K=5; Laplacian, diagonal augmentation and
correlation all on):

  (a) in-memory fit: ``GEEEmbedder(backend="auto")`` (on a TPU the router
      picks the Pallas backend with the fused epilogue) and
      ``backend="sparse_jax"``, each checked against a float64 numpy
      bincount reference that uses no kernel;
  (b) streamed fit: the same graph written with ``synth_to_disk`` and fitted
      with ``GEEEmbedder.fit_transform_file`` (the chunked ``stream_fold``),
      checked against (a);
  (c) retrieval: ``build_index`` + ``neighbors`` for 1,024 vertex queries at
      the default ``nprobe``, recall@10 against ``brute_force=True``, and the
      brute-force hits against a numpy exact top-10.

With ``--four-chips`` it runs only the ``streamed_sharded`` fit of the (b)
file over every local device and a one-device streamed fit of the same file
to compare against.

Each phase prints one JSON line (seconds, max errors, resolved backend).
The last line is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits nonzero before any phase runs.

  python chip_smoke.py [--four-chips] [--seed 0] [--out DIR]
"""

from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DATASET = "cl-100k-1d8-l5"
TOL = 1e-5
NUM_QUERIES = 1024
TOP_K = 10
MIN_RECALL = 0.99


def _emit(**row) -> None:
    print(json.dumps(row), flush=True)


def _max_err(a, b) -> float:
    return float(np.abs(np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)).max())


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def numpy_gee(src, dst, w, labels, k: int) -> np.ndarray:
    """Reference GEE (Laplacian, diag-aug, correlation) in float64 numpy:
    a bincount over the directed edge list, no kernel involved."""
    n = labels.shape[0]
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    w = w.astype(np.float64)
    deg = np.bincount(src, weights=w, minlength=n) + 1.0      # + self loop
    dinv = 1.0 / np.sqrt(deg)
    known = labels >= 0
    counts = np.bincount(labels[known], minlength=k).astype(np.float64)
    winv = np.where(counts > 0, 1.0 / np.maximum(counts, 1.0), 0.0)
    yd = labels[dst]
    keep = yd >= 0
    contrib = (w * dinv[src] * dinv[dst])[keep] * winv[yd[keep]]
    z = np.bincount(src[keep] * k + yd[keep], weights=contrib,
                    minlength=n * k).reshape(n, k)
    rows = np.flatnonzero(known)
    z[rows, labels[rows]] += dinv[rows] ** 2 * winv[labels[rows]]
    norm = np.linalg.norm(z, axis=1, keepdims=True)
    return np.divide(z, norm, out=np.zeros_like(z), where=norm > 0)


def numpy_topk_l2(z: np.ndarray, rows: np.ndarray, k: int):
    """Exact top-k by -||q - x||^2 in float64: (ids, scores), best first."""
    ids, scores = [], []
    for lo in range(0, rows.size, 128):
        q = z[rows[lo:lo + 128]]
        s = -((q * q).sum(1)[:, None] - 2.0 * q @ z.T + (z * z).sum(1)[None])
        top = np.argpartition(-s, k - 1, axis=1)[:, :k]
        top = np.take_along_axis(
            top, np.argsort(-np.take_along_axis(s, top, axis=1), axis=1), 1)
        ids.append(top)
        scores.append(np.take_along_axis(s, top, axis=1))
    return np.concatenate(ids), np.concatenate(scores)


def _assert_mosaic(fn, *shapes) -> None:
    """Compile ``fn`` for the attached chip at these shapes and require a
    Mosaic kernel in the program (compiled, not interpreted)."""
    import jax

    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError(f"no Mosaic kernel in {fn}")


def _write_file(out: Path, seed: int):
    from repro.graph.datasets import TABLE2, synth_to_disk

    spec = TABLE2[DATASET]
    path = str(out / f"{DATASET}.geeb")
    # one chunk as large as the graph draws exactly synth_like's sample
    _, dt = _timed(lambda: synth_to_disk(spec, path, seed=seed,
                                         chunk_edges=spec.num_edges))
    return path, dt


def phase_fit(ds, opts):
    """(a) in-memory fit through GEEEmbedder: auto and sparse_jax."""
    import jax.numpy as jnp

    from repro.core.api import GEEEmbedder
    from repro.core.plan import PreparedGraph
    from repro.kernels.gee_fused import gee_spmm_fused
    from repro.kernels.platform import interpret_mode

    k = ds.spec.num_classes
    labels = np.asarray(ds.labels)
    t0 = time.perf_counter()
    src, dst, w = ds.edges.valid_arrays()
    z_ref = numpy_gee(src, dst, w, labels, k)
    ref_s = time.perf_counter() - t0

    prep = PreparedGraph.wrap(ds.edges)
    bell, pack_s = _timed(lambda: prep.bucketed_ell(False))
    widest = max(bell.buckets, key=lambda b: b.cols.size)
    rows, width = widest.cols.shape
    _assert_mosaic(functools.partial(gee_spmm_fused, num_classes=k),
                   ((rows, width), jnp.int32), ((rows, width), jnp.float32),
                   ((rows,), jnp.int32), ((rows,), jnp.float32))

    results = {}
    for backend in ("auto", "sparse_jax"):
        emb = GEEEmbedder(num_classes=k, options=opts, backend=backend)

        def fit():
            return emb.fit(prep, labels).transform()
        z, cold_s = _timed(fit)
        z, warm_s = _timed(fit)
        plan = emb.plan                # what the timed fit actually ran
        if backend == "auto" and ((plan.backend, plan.fused)
                                  != ("pallas", True) or interpret_mode()):
            raise AssertionError(f"auto resolved to backend={plan.backend} "
                                 f"fused={plan.fused} on this TPU")
        err = _max_err(z, z_ref)
        _emit(phase="a_fit", backend=backend, resolved=plan.backend,
              fused=plan.fused,
              buckets=len(bell.buckets) if backend == "auto" else None,
              pack_s=pack_s if backend == "auto" else None,
              cold_s=cold_s, warm_s=warm_s, compile_s_est=cold_s - warm_s,
              numpy_ref_s=ref_s, max_err_vs_numpy=err)
        if not err <= TOL:
            raise AssertionError(f"{backend} fit max err {err} > {TOL}")
        results[backend] = z
    return prep, results["auto"]


def phase_stream(path: str, write_s: float, z_a, opts, k: int):
    """(b) streamed fit of the on-disk copy, compared with (a)."""
    from repro.core.api import GEEEmbedder

    def fit():
        return GEEEmbedder(num_classes=k, options=opts).fit_transform_file(
            path)
    z, cold_s = _timed(fit)
    z, warm_s = _timed(fit)
    err = _max_err(z, z_a)
    _emit(phase="b_stream", backend="chunked stream_fold", write_s=write_s,
          cold_s=cold_s, warm_s=warm_s, compile_s_est=cold_s - warm_s,
          max_err_vs_a=err)
    if not err <= TOL:
        raise AssertionError(f"streamed fit max err {err} > {TOL}")


def phase_search(ds, prep, z_a, opts, seed: int):
    """(c) index + neighbors at the default nprobe vs brute force."""
    import jax.numpy as jnp

    from repro.core.api import GEEEmbedder
    from repro.launch.gee_search import recall_at_k
    from repro.kernels.topk_score import fused_topk_enabled, scored_topk

    k = ds.spec.num_classes
    n = ds.spec.num_nodes
    if not fused_topk_enabled():
        raise AssertionError("fused top-k route is off on this TPU")
    _assert_mosaic(functools.partial(scored_topk, valid=None, k=TOP_K,
                                     fused=True),
                   ((NUM_QUERIES, k), jnp.float32), ((n, k), jnp.float32))
    emb = GEEEmbedder(num_classes=k, options=opts, backend="auto")
    emb.fit(prep, ds.labels)
    index, build_s = _timed(emb.build_index)
    rows = np.random.default_rng(seed).choice(n, NUM_QUERIES, replace=False)

    (ids, sc), cold_s = _timed(lambda: emb.neighbors(query_rows=rows,
                                                     k=TOP_K))
    (ids, sc), warm_s = _timed(lambda: emb.neighbors(query_rows=rows,
                                                     k=TOP_K))
    (bf_ids, bf_sc), bf_s = _timed(lambda: emb.neighbors(
        query_rows=rows, k=TOP_K, brute_force=True))
    recall = recall_at_k(np.asarray(ids), np.asarray(sc),
                         np.asarray(bf_ids), np.asarray(bf_sc))
    np_ids, np_sc = numpy_topk_l2(np.asarray(z_a, np.float64), rows, TOP_K)
    bf_recall = recall_at_k(np.asarray(bf_ids), np.asarray(bf_sc),
                            np_ids, np_sc)
    _emit(phase="c_search", cells=index.num_cells, nprobe=index.nprobe,
          bucket_capacity=index.bucket_capacity, queries=NUM_QUERIES,
          k=TOP_K, fused_topk=True, build_s=build_s, cold_s=cold_s,
          warm_s=warm_s, brute_force_s=bf_s, recall_vs_brute_force=recall,
          brute_force_recall_vs_numpy=bf_recall)
    if not (recall >= MIN_RECALL and bf_recall >= MIN_RECALL):
        raise AssertionError(f"recall {recall} / brute force vs numpy "
                             f"{bf_recall} below {MIN_RECALL}")


def phase_four_chips(path: str, write_s: float, opts, k: int, z_ref):
    """streamed_sharded over every local device vs a one-device fit."""
    import jax

    from repro.core.api import GEEEmbedder

    def fit(backend):
        return lambda: GEEEmbedder(num_classes=k, options=opts,
                                   backend=backend).fit_transform_file(path)
    z4, cold4 = _timed(fit("streamed_sharded"))
    z4, warm4 = _timed(fit("streamed_sharded"))
    z1, cold1 = _timed(fit("chunked"))
    z1, warm1 = _timed(fit("chunked"))
    shards = len(z4.sharding.device_set)
    err = _max_err(z4, z1)
    err_ref = _max_err(z4, z_ref)
    _emit(phase="four_chip_stream", devices=jax.device_count(),
          z_shards=shards, write_s=write_s, sharded_cold_s=cold4,
          sharded_warm_s=warm4, one_device_cold_s=cold1,
          one_device_warm_s=warm1, max_err_vs_one_device=err,
          max_err_vs_numpy=err_ref)
    if shards != jax.device_count() or not (err <= TOL and err_ref <= TOL):
        raise AssertionError(f"sharded fit: {shards} shards, err {err}, "
                             f"vs numpy {err_ref}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device streamed_sharded fit and "
                         "its one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / ".chip_smoke"),
                    help="directory for the on-disk graph (a subdirectory "
                         "is made and removed)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s), found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2

    from repro.core.gee import GEEOptions
    from repro.graph.datasets import load

    opts = GEEOptions(laplacian=True, diag_aug=True, correlation=True)
    out = Path(args.out) / f"{DATASET}-seed{args.seed}"
    out.mkdir(parents=True, exist_ok=True)
    try:
        ds, gen_s = _timed(lambda: load(DATASET, seed=args.seed))
        _emit(phase="setup", dataset=DATASET, nodes=ds.spec.num_nodes,
              edges=ds.spec.num_edges, classes=ds.spec.num_classes,
              seed=args.seed, generate_s=gen_s,
              device_kind=devices[0].device_kind)
        k = ds.spec.num_classes
        path, write_s = _write_file(out, args.seed)
        if args.four_chips:
            src, dst, w = ds.edges.valid_arrays()
            z_ref = numpy_gee(src, dst, w, np.asarray(ds.labels), k)
            phase_four_chips(path, write_s, opts, k, z_ref)
        else:
            prep, z_a = phase_fit(ds, opts)
            phase_stream(path, write_s, z_a, opts, k)
            phase_search(ds, prep, z_a, opts, args.seed)
    finally:
        shutil.rmtree(out, ignore_errors=True)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
