"""Pallas ELL backend vs the segment-sum production path.

Reports, per graph size:

  * ELL padding overhead (stored slots / real edges) for the flat and the
    degree-bucketed packing -- the quantity the bucketing layer exists to
    bound on power-law graphs;
  * runtime of gee(..., backend="pallas") and gee_sparse_jax.

On CPU the kernel runs in interpret mode, so the runtime columns measure
pipeline overhead, not MXU throughput; on TPU the same script times the
compiled Mosaic kernel.  Each run writes BENCH_gee_pallas.json; CI uploads
it as a per-commit artifact, which is how the perf trajectory accumulates.

  PYTHONPATH=src python benchmarks/bench_gee_pallas.py [--sizes 300,600,1200]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.gee import GEEOptions, gee, gee_sparse_jax
from repro.graph.ell import ell_stats
from repro.graph.sbm import sample_sbm

import jax.numpy as jnp

SIZES = (300, 600, 1200)
OPTS = GEEOptions(laplacian=True, diag_aug=True, correlation=True)


def _time(fn, repeats=2) -> float:
    out = fn()
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        if hasattr(out, "block_until_ready"):
            out.block_until_ready()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run(sizes=SIZES, repeats=2):
    rows = []
    for n in sizes:
        s = sample_sbm(n, seed=0)
        stats = ell_stats(s.edges)
        labels = jnp.asarray(s.labels)

        t_sparse = _time(lambda: gee_sparse_jax(s.edges, labels,
                                                s.num_classes, OPTS), repeats)
        t_bucketed = _time(lambda: gee(s.edges, s.labels, s.num_classes,
                                       OPTS, backend="pallas"), repeats)

        # equivalence gate: the benchmark is invalid if the backends diverge
        zp = np.asarray(gee(s.edges, s.labels, s.num_classes, OPTS,
                            backend="pallas"))
        zr = np.asarray(gee_sparse_jax(s.edges, labels, s.num_classes, OPTS))
        max_err = float(np.abs(zp - zr).max())
        assert max_err <= 1e-5, f"pallas diverged from sparse_jax: {max_err}"

        row = {
            "nodes": n,
            "edges": stats["num_edges"],
            "max_degree": stats["max_degree"],
            "flat_overhead": round(stats["flat_overhead"], 3),
            "bucketed_overhead": round(stats["bucketed_overhead"], 3),
            "num_buckets": stats["num_buckets"],
            "t_sparse_jax": t_sparse,
            "t_pallas_bucketed": t_bucketed,
            "max_abs_err": max_err,
        }
        rows.append(row)
        print(f"N={n:6d} E={row['edges']:8d} dmax={row['max_degree']:4d}  "
              f"pad flat={row['flat_overhead']:5.2f}x "
              f"bucketed={row['bucketed_overhead']:5.2f}x  "
              f"sparse_jax={t_sparse*1e3:8.1f}ms "
              f"pallas={t_bucketed*1e3:8.1f}ms  err={max_err:.1e}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=str, default=",".join(map(str, SIZES)),
                    help="comma-separated SBM node counts (>= 3 sizes)")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--json", type=str, default="BENCH_gee_pallas.json",
                    help="output JSON path ('' disables)")
    args = ap.parse_args(argv)
    sizes = tuple(int(x) for x in args.sizes.split(",") if x)
    rows = run(sizes, args.repeats)
    if args.json:
        import jax
        payload = {"benchmark": "gee_pallas", "backend": jax.default_backend(),
                   "interpret": jax.default_backend() != "tpu", "rows": rows}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    return rows


if __name__ == "__main__":
    main()
