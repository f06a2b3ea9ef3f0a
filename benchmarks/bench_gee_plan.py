"""Prep-reuse benchmark: the 8-option sweep through the plan/executor layer.

The workload is the paper's own evaluation protocol -- embed one graph
under every (Laplacian, diag-aug, correlation) setting -- executed two
ways:

  cold   what a naive per-call sweep does: every setting re-prepares the
         graph from raw host arrays (symmetrize + device upload +
         self-loop augmentation + Laplacian fold) before its scatter.
  warm   one ``PreparedGraph`` + ``sweep_options``: prep artifacts are
         derived once and shared, and settings that differ only in the
         correlation flag share their scatter pass (8 settings -> 4
         scatters + 4 row normalizations).

Both paths produce identical embeddings (asserted <= 1e-5 against the
fused single-jit reference).  CI runs this as the bench-smoke cell
publishing ``BENCH_plan.json`` and gates on ``--min-speedup`` (default
1.5x).  The JSON also records the autotune-registry persistence
round-trip smoke (save -> fresh registry -> load -> identical entries).
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from repro.core.gee import ALL_OPTION_SETTINGS, gee
from repro.core.plan import GEEPlan, PreparedGraph, sweep_options
from repro.graph.sbm import sample_sbm

NODE_GRID = (1_000, 3_000, 10_000)


def _block(z):
    if hasattr(z, "block_until_ready"):
        z.block_until_ready()
    return z


def _raw_half_edges(edges):
    """One-entry-per-undirected-edge host arrays (what an ingesting client
    holds before symmetrization)."""
    e = edges.num_edges
    src = np.asarray(edges.src)[:e]
    dst = np.asarray(edges.dst)[:e]
    w = np.asarray(edges.weight)[:e]
    keep = src <= dst                     # sampler graphs are loop-free
    return src[keep], dst[keep], w[keep]


def _cold_sweep(src, dst, w, n, labels, k, backend):
    """Per-setting prep from raw arrays: fresh PreparedGraph every call."""
    out = []
    for opts in ALL_OPTION_SETTINGS:
        prep = PreparedGraph.from_arrays(src, dst, w, num_nodes=n)
        out.append(_block(GEEPlan.build(prep, k, opts,
                                        backend=backend).execute(labels)))
    return out


def _warm_sweep(src, dst, w, n, labels, k, backend):
    """Shared prep: one PreparedGraph, correlation pairs share scatters."""
    prep = PreparedGraph.from_arrays(src, dst, w, num_nodes=n)
    zs = sweep_options(prep, labels, k, backend=backend)
    return [_block(zs[opts]) for opts in ALL_OPTION_SETTINGS]


def _time(fn, repeats: int) -> float:
    fn()                                   # warmup: jit traces + caches
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _tracer_overhead_cell(n: int, repeats: int) -> dict:
    """The observability gate: disabled span instrumentation must cost
    <= 2% of a full-option ``gee()`` fit.  Uses the deterministic
    decomposition in ``repro.obs.trace.tracer_overhead_pct`` (span count
    x measured null-span cost / fit time) rather than an A/B wall-clock
    diff that CI scheduler jitter would drown."""
    from repro.core.gee import GEEOptions
    from repro.obs.trace import tracer_overhead_pct

    s = sample_sbm(n, seed=0)
    prep = PreparedGraph.wrap(s.edges)
    labels, k = s.labels, s.num_classes
    opts = GEEOptions(laplacian=True, diag_aug=True, correlation=True)
    r = tracer_overhead_pct(lambda: _block(gee(prep, labels, k, opts)),
                            repeats=repeats)
    r["nodes"] = int(n)
    return r


def _autotune_roundtrip_smoke() -> bool:
    """Persistence smoke: recorded entries survive save -> fresh load.

    Runs on scratch registries only -- the process-global REGISTRY must
    never pick up a fabricated measurement from a benchmark."""
    from repro.kernels.autotune import AutotuneRegistry

    key, value = (1 << 20, 1 << 9, 8), (512, 128, 16)
    scratch = AutotuneRegistry()
    scratch.record("gee_spmm", key, value)
    fd, path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        scratch.save(path)
        fresh = AutotuneRegistry()
        fresh.load(path)
        return fresh.recorded("gee_spmm").get(key) == value
    finally:
        os.unlink(path)


def run(nodes=NODE_GRID, repeats: int = 3, backend: str = "sparse_jax",
        min_speedup: float = 1.5, json_path: str | None = None,
        max_tracer_overhead: float = 2.0,
        metrics_path: str | None = None):
    cells = []
    for n in nodes:
        s = sample_sbm(n, seed=0)
        src, dst, w = _raw_half_edges(s.edges)
        labels, k = s.labels, s.num_classes

        # correctness first: both sweeps match the fused per-call reference
        cold_z = _cold_sweep(src, dst, w, n, labels, k, backend)
        warm_z = _warm_sweep(src, dst, w, n, labels, k, backend)
        for opts, zc, zw in zip(ALL_OPTION_SETTINGS, cold_z, warm_z):
            ref = np.asarray(gee(s.edges, labels, k, opts))
            err_c = np.abs(np.asarray(zc) - ref).max()
            err_w = np.abs(np.asarray(zw) - ref).max()
            assert max(err_c, err_w) <= 1e-5, (opts.tag(), err_c, err_w)

        t_cold = _time(lambda: _cold_sweep(src, dst, w, n, labels, k,
                                           backend), repeats)
        t_warm = _time(lambda: _warm_sweep(src, dst, w, n, labels, k,
                                           backend), repeats)
        cell = {"nodes": int(n), "edges": int(s.edges.num_edges),
                "settings": len(ALL_OPTION_SETTINGS),
                "cold_s": t_cold, "warm_s": t_warm,
                "speedup": t_cold / t_warm}
        cells.append(cell)
        print(f"N={n:7d} E={cell['edges']:8d}  "
              f"cold={t_cold*1e3:8.1f} ms  warm={t_warm*1e3:8.1f} ms  "
              f"prep-reuse speedup {cell['speedup']:5.2f}x")

    overhead = _tracer_overhead_cell(min(max(nodes), 3_000), repeats)
    print(f"disabled-tracer overhead (N={overhead['nodes']}): "
          f"{overhead['span_count']} spans x "
          f"{overhead['disabled_span_ns']:.0f} ns / "
          f"{overhead['fn_s']*1e3:.1f} ms fit = "
          f"{overhead['overhead_pct']:.4f}%  (gate <= "
          f"{max_tracer_overhead}%)")

    roundtrip_ok = _autotune_roundtrip_smoke()
    print(f"autotune persistence round-trip: "
          f"{'ok' if roundtrip_ok else 'FAILED'}")
    worst = min(c["speedup"] for c in cells)
    result = {"backend": backend, "repeats": repeats, "cells": cells,
              "worst_speedup": worst, "min_speedup": min_speedup,
              "tracer_overhead": overhead,
              "tracer_overhead_pct": overhead["overhead_pct"],
              "max_tracer_overhead": max_tracer_overhead,
              "autotune_roundtrip": roundtrip_ok}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {json_path}")
    if metrics_path:
        from repro.obs.metrics import get_registry

        get_registry().write_json(metrics_path)
        print(f"wrote {metrics_path}")
    assert roundtrip_ok, "autotune registry persistence round-trip failed"
    assert worst >= min_speedup, (
        f"prep reuse speedup {worst:.2f}x below the {min_speedup}x gate")
    assert overhead["overhead_pct"] <= max_tracer_overhead, (
        f"disabled tracer overhead {overhead['overhead_pct']:.3f}% above "
        f"the {max_tracer_overhead}% gate")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", default=",".join(map(str, NODE_GRID)),
                    help="comma-separated SBM node counts")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--backend", default="sparse_jax")
    ap.add_argument("--min-speedup", type=float, default=1.5)
    ap.add_argument("--max-tracer-overhead", type=float, default=2.0,
                    help="disabled-instrumentation overhead gate, percent")
    ap.add_argument("--json", default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics-registry snapshot JSON here")
    args = ap.parse_args(argv)
    return run(tuple(int(x) for x in args.nodes.split(",")),
               args.repeats, args.backend, args.min_speedup, args.json,
               args.max_tracer_overhead,
               args.metrics_out)


if __name__ == "__main__":
    main()
