"""GEE driver: the paper's pipeline as a CLI.

  PYTHONPATH=src python -m repro.launch.gee_run --sbm 10000 --backend sparse_jax \
      --lap --diag --cor
  PYTHONPATH=src python -m repro.launch.gee_run --dataset citeseer --compare
  PYTHONPATH=src python -m repro.launch.gee_run --edge-file graph.geeb \
      --chunk-edges 1048576 --lap --diag --cor   # out-of-core streaming
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.gee import GEEOptions, gee
from repro.core.plan import GEEPlan, PreparedGraph
from repro.graph.datasets import TABLE2, load
from repro.graph.sbm import sample_sbm
from repro.kernels.platform import interpret_mode
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import cli as obs_cli


def _time(fn, repeats=3):
    # Block on the warmup too: without it, the async compile+execute of the
    # first call bleeds into the first timed repeat and inflates it.
    jax.block_until_ready(fn())           # warmup / compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())       # no-op on host (numpy) outputs
        ts.append(time.perf_counter() - t0)
    return min(ts)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sbm", type=int, default=None,
                    help="SBM node count (paper's simulation)")
    ap.add_argument("--dataset", default=None,
                    help=f"one of {sorted(TABLE2)}, or a path to an edge "
                         f"file (.geeb/.npz/.txt)")
    ap.add_argument("--edge-file", default=None,
                    help="embed an on-disk edge list out-of-core (any "
                         "repro.graph.io format); labels come from the "
                         "<file>.labels.npy sidecar or --classes random")
    ap.add_argument("--chunk-edges", type=int, default=None,
                    help="streaming window for --edge-file / chunked "
                         "backend (default 1M edges = 12 MB/chunk)")
    ap.add_argument("--prefetch-windows", type=int, default=None,
                    help="windows staged ahead by background threads for "
                         "the streaming backends (default: "
                         "REPRO_GEE_PREFETCH_WINDOWS or 2; 0 = "
                         "synchronous reads)")
    ap.add_argument("--classes", type=int, default=5,
                    help="synthetic label count when --edge-file has no "
                         "labels sidecar")
    ap.add_argument("--backend", default="sparse_jax",
                    choices=("sparse_jax", "dense_jax", "scipy",
                             "python_loop", "pallas", "chunked",
                             "streamed_sharded", "auto"))
    ap.add_argument("--lap", action="store_true")
    ap.add_argument("--diag", action="store_true")
    ap.add_argument("--cor", action="store_true")
    ap.add_argument("--compare", action="store_true",
                    help="time all backends (prep shared via PreparedGraph)")
    ap.add_argument("--plan", action="store_true",
                    help="print the resolved GEEPlan stages per backend")
    ap.add_argument("--seed", type=int, default=0)
    obs_cli.add_flags(ap)
    args = ap.parse_args(argv)
    obs_cli.setup(args)
    enable_compile_cache()

    opts = GEEOptions(laplacian=args.lap, diag_aug=args.diag,
                      correlation=args.cor)

    if args.edge_file:
        # Out-of-core path: the edge list stays on disk, windows stream
        # through the shared fold (repro.core.fold).  'streamed_sharded'
        # splits every window across all visible devices; everything else
        # runs the single-device chunked fold.
        from repro.core.chunked import gee_chunked
        from repro.core.fold import gee_streamed_sharded
        from repro.graph.io import (DEFAULT_CHUNK_EDGES, load_labels,
                                    open_edge_list, open_window_parallel)

        if args.compare:
            print("  (--compare with --edge-file: timing the on-disk "
                  "streaming backends)")
        chunk = args.chunk_edges or DEFAULT_CHUNK_EDGES
        streamed = args.backend == "streamed_sharded" or args.compare
        if streamed:
            chunked = open_window_parallel(args.edge_file,
                                           jax.device_count(),
                                           chunk_edges=chunk)
        else:
            chunked = open_edge_list(args.edge_file, chunk_edges=chunk)
        labels = load_labels(args.edge_file)
        if labels is None:
            labels = np.random.default_rng(args.seed).integers(
                0, args.classes, chunked.num_nodes).astype(np.int32)
            print(f"  (no labels sidecar; random K={args.classes} labels)")
            k = args.classes
        else:
            # all-unknown (-1) sidecars still get K=1 (a zero embedding),
            # not a zero-width Z
            k = max(int(labels.max()) + 1, 1)
        print(f"{args.edge_file}: N={chunked.num_nodes} "
              f"E={chunked.num_edges}"
              f"{' (undirected storage)' if chunked.undirected else ''} "
              f"K={k} windows={chunked.num_windows}"
              f"x{chunked.window_edges} "
              f"[{opts.tag()}]")
        pf = args.prefetch_windows
        cells = []
        if args.backend != "streamed_sharded" or args.compare:
            cells.append(("chunked",
                          lambda: gee_chunked(chunked, labels, k, opts,
                                              prefetch_windows=pf)))
        if streamed:
            cells.append((f"streamed x{jax.device_count()}",
                          lambda: gee_streamed_sharded(chunked, labels, k,
                                                       opts,
                                                       prefetch_windows=pf)))
        for name, fn in cells:
            dt = _time(fn)
            z = np.asarray(fn())
            eps = (2 if chunked.undirected else 1) * chunked.num_edges / dt
            print(f"  {name:12s}: {dt*1e3:9.1f} ms   "
                  f"{eps/1e6:8.2f} M edges/s"
                  f"   Z[{z.shape[0]}x{z.shape[1]}] "
                  f"norm {np.linalg.norm(z):.4f}")
        obs_cli.finish(args)
        return

    if args.sbm:
        s = sample_sbm(args.sbm, seed=args.seed)
        edges, labels, k = s.edges, s.labels, s.num_classes
        name = f"sbm-{args.sbm}"
    else:
        ds = load(args.dataset or "citeseer", seed=args.seed)
        edges, labels, k = ds.edges, ds.labels, ds.spec.num_classes
        name = ds.spec.name
    print(f"{name}: N={edges.num_nodes} E={edges.num_edges//2} K={k} "
          f"[{opts.tag()}]")

    backends = (("sparse_jax", "chunked", "streamed_sharded", "pallas",
                 "auto", "dense_jax", "scipy", "python_loop")
                if args.compare else (args.backend,))
    # One PreparedGraph for every cell: symmetrized upload, self-loop
    # augmentation, laplacian fold, ELL packing and the chunk manifest are
    # derived once and shared across the whole comparison.
    prep = PreparedGraph.wrap(edges)
    for b in backends:
        if b == "python_loop" and edges.num_edges > 3_000_000:
            print(f"  {b:12s}: skipped (too slow at this size)")
            continue
        if b == "pallas" and args.compare and interpret_mode():
            print(f"  {b:12s}: skipped (interpret mode off-TPU; "
                  f"run with --backend pallas to force)")
            continue
        plan = None
        if args.plan:
            plan = GEEPlan.build(prep, k, opts, backend=b,
                                 chunk_edges=args.chunk_edges,
                                 prefetch_windows=args.prefetch_windows)
            if not args.trace:
                print("\n".join("  " + ln for ln in
                                plan.describe().splitlines()))
        if b == "chunked" and args.chunk_edges:
            from repro.core.chunked import gee_chunked
            fn = lambda: gee_chunked(prep.chunked(args.chunk_edges),
                                     labels, k, opts,
                                     prefetch_windows=args.prefetch_windows)
        elif plan is not None:
            # Execute through the printed plan so --trace populates its
            # per-stage timings (describe(timings=True) below).
            fn = lambda: plan.execute(labels)
        else:
            fn = lambda: gee(prep, labels, k, opts, backend=b)
        dt = _time(fn)
        z = np.asarray(fn())
        print(f"  {b:12s}: {dt*1e3:9.1f} ms   Z[{z.shape[0]}x{z.shape[1]}] "
              f"norm {np.linalg.norm(z):.4f}")
        if plan is not None and args.trace:
            print("\n".join("  " + ln for ln in
                            plan.describe(timings=True).splitlines()))
    obs_cli.finish(args)


if __name__ == "__main__":
    main()
