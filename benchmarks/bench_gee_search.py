"""Vertex-similarity retrieval: recall@k vs exact brute force, QPS vs size.

For SBM graphs across >= 3 node counts, embeds with the production backend,
builds the class-partitioned index, and measures

  * recall@k against exact brute force at the default ``nprobe`` and at
    ``nprobe = num_cells`` (the latter is *asserted* == 1.0: probing every
    cell covers every vertex, so the IVF path must reproduce brute force),
  * batched query throughput (QPS) for the IVF path and the brute-force
    path (min-of-N warm repeats, jit warmup excluded),
  * index build time and table padding overhead.

Each run writes BENCH_search.json; CI uploads it as a per-commit artifact
alongside the other benchmark JSONs.

  PYTHONPATH=src python benchmarks/bench_gee_search.py \
      [--nodes 2000,6000,20000] [--queries 256] [--k 10] [--repeats 3]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax

from repro.core.api import GEEEmbedder
from repro.core.gee import GEEOptions
from repro.graph.sbm import sample_sbm
from repro.launch.gee_search import recall_at_k

NODES = (2_000, 6_000, 20_000)
OPTS = GEEOptions(laplacian=True, diag_aug=True, correlation=True)


def _time_call(fn, repeats):
    jax.block_until_ready(fn()[1])            # compile/warm outside timing
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn()[1])
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _time_search(index, queries, k, repeats, **kw):
    return _time_call(lambda: index.search(queries, k, **kw), repeats)


def _fused_query_cell(z, queries, k, repeats, seed):
    """Fused score-and-top-k vs staged scores+masked_topk on the pallas
    query path: the index's brute-force search (``scored_topk`` over the
    whole Z) with ``fused=`` set each way.  Off-TPU the kernels run in
    interpret mode, so this is parity documentation; the headline gate
    lives in the TPU-capable runs."""
    import jax.numpy as jnp

    from repro.kernels.topk_score import scored_topk

    n = z.shape[0]
    zj = jnp.asarray(z)
    q = zj[np.random.default_rng(seed).integers(0, n, queries)]
    searches = {
        fused: jax.jit(lambda q, fused=fused: scored_topk(
            q, zj, None, k, metric="l2", impl="pallas", fused=fused))
        for fused in (False, True)}
    ids_s, sc_s = (np.asarray(a) for a in searches[False](q))
    ids_f, sc_f = (np.asarray(a) for a in searches[True](q))
    t_staged = _time_call(lambda: searches[False](q), repeats)
    t_fused = _time_call(lambda: searches[True](q), repeats)
    assert np.array_equal(ids_s, ids_f), \
        "fused top-k returned different neighbor ids than staged"
    np.testing.assert_allclose(sc_f, sc_s, atol=1e-5)
    return {"nodes": int(n), "queries": int(queries), "k": int(k),
            "device": jax.default_backend(),
            "staged_s": t_staged, "fused_s": t_fused,
            "fused_query_speedup": t_staged / t_fused}


def run(nodes=NODES, queries=256, k=10, repeats=3, seed=0):
    rows = []
    fused_cell = None
    # interpret mode makes the pallas query path slow off-TPU: run the
    # fused-vs-staged cell on the smallest graph there, largest on TPU
    on_tpu = jax.default_backend() == "tpu"
    fused_n = max(nodes) if on_tpu else min(nodes)
    for n in nodes:
        s = sample_sbm(n, seed=seed)
        emb = GEEEmbedder(num_classes=s.num_classes,
                          options=OPTS).fit(s.edges, s.labels)
        z = np.asarray(emb.transform())

        t0 = time.perf_counter()
        index = emb.build_index()
        t_build = time.perf_counter() - t0

        rng = np.random.default_rng(seed)
        q = z[rng.integers(0, n, queries)]

        t_ivf = _time_search(index, q, k, repeats)
        t_bf = _time_search(index, q, k, repeats, brute_force=True)

        ids_d, sc_d = (np.asarray(a) for a in index.search(q, k))
        ids_f, sc_f = (np.asarray(a) for a in
                       index.search(q, k, nprobe=index.num_cells))
        ids_b, sc_b = (np.asarray(a) for a in
                       index.search(q, k, brute_force=True))
        rec_default = recall_at_k(ids_d, sc_d, ids_b, sc_b)
        rec_full = recall_at_k(ids_f, sc_f, ids_b, sc_b)
        assert rec_full == 1.0, \
            f"nprobe=num_cells must be exact, got recall {rec_full}"

        row = {
            "nodes": n,
            "edges": s.edges.num_edges,
            "num_cells": index.num_cells,
            "nprobe_default": index.nprobe,
            "bucket_capacity": index.bucket_capacity,
            "padding_fraction": index.padding_fraction(),
            "t_build": t_build,
            "queries": queries,
            "k": k,
            "qps_ivf": queries / t_ivf,
            "qps_brute_force": queries / t_bf,
            "recall_at_k_default": rec_default,
            "recall_at_k_full_probe": rec_full,
        }
        rows.append(row)
        print(f"N={n:7d} E={row['edges']:9d} C={row['num_cells']} "
              f"nprobe={row['nprobe_default']}  "
              f"build={t_build*1e3:7.1f}ms  "
              f"ivf={row['qps_ivf']:10,.0f} QPS  "
              f"bf={row['qps_brute_force']:10,.0f} QPS  "
              f"recall@{k}={rec_default:.4f} (full-probe {rec_full:.1f})")

        if n == fused_n:
            fq = queries if on_tpu else min(queries, 64)
            fused_cell = _fused_query_cell(z, fq, k, repeats, seed)
            print(f"  fused query path (N={n}, {fused_cell['device']}): "
                  f"staged={fused_cell['staged_s']*1e3:7.1f}ms  "
                  f"fused={fused_cell['fused_s']*1e3:7.1f}ms  "
                  f"{fused_cell['fused_query_speedup']:5.2f}x"
                  + ("" if on_tpu
                     else "  [interpret mode: parity only]"))
    return rows, fused_cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=str, default=",".join(map(str, NODES)),
                    help="comma-separated SBM node counts")
    ap.add_argument("--queries", type=int, default=256,
                    help="query batch size per measurement")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", type=str, default="BENCH_search.json",
                    help="output JSON path ('' disables)")
    ap.add_argument("--min-recall", type=float, default=0.9,
                    help="fail if default-nprobe recall@k drops below this "
                         "on any graph (0 disables)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics-registry snapshot JSON here")
    args = ap.parse_args(argv)
    nodes = tuple(int(x) for x in args.nodes.split(",") if x)
    rows, fused_cell = run(nodes, args.queries, args.k, args.repeats,
                           args.seed)
    if args.json:
        payload = {"benchmark": "gee_search",
                   "backend": jax.default_backend(),
                   "opts": OPTS.tag(), "rows": rows,
                   "fused_cell": fused_cell,
                   "fused_query_speedup":
                       fused_cell["fused_query_speedup"]
                       if fused_cell else None}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    if args.metrics_out:
        from repro.obs.metrics import get_registry

        get_registry().write_json(args.metrics_out)
        print(f"wrote {args.metrics_out}")
    if args.min_recall:
        worst = min(r["recall_at_k_default"] for r in rows)
        if worst < args.min_recall:
            raise SystemExit(
                f"recall@{args.k} {worst:.4f} at default nprobe is below "
                f"--min-recall {args.min_recall}")
    return rows


if __name__ == "__main__":
    main()
