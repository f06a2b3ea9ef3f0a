"""Edge-stream replay driver: incremental GEE vs from-scratch recompute.

Holds out a fraction of a graph's undirected edges, fits ``IncrementalGEE``
on the rest, then replays the held-out edges (plus optional label churn)
through the delta-coalescing ``GEEDeltaServer`` in fixed-size batches,
timing every update.  Periodically verifies the streamed state against a
from-scratch ``gee_sparse_jax`` on the mutated graph and times that full
recompute, so the output directly reports the update-vs-recompute latency
gap the incremental subsystem exists for.

  PYTHONPATH=src python -m repro.launch.gee_stream --sbm 2000 \
      --stream-frac 0.2 --batch 64 --lap --diag --cor
  PYTHONPATH=src python -m repro.launch.gee_stream --dataset citeseer

Crash safety: with ``--snapshot-dir`` the stream runs through the full
durability stack (``repro.serve.snapshot``) -- every batch commits as one
atomic WAL record before applying, a consistent snapshot (state + vertex
index + watermark) is taken every ``--snapshot-every`` batches, and
``--recover`` resumes a killed run from the newest snapshot + WAL replay,
re-deriving the RNG position so the resumed stream is byte-identical to an
uninterrupted one.  ``benchmarks/bench_gee_recovery`` SIGKILLs this driver
mid-stream and asserts exactly that.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gee import GEEOptions, gee_sparse_jax
from repro.core.incremental import IncrementalGEE
from repro.graph.containers import edge_list_from_numpy, symmetrize
from repro.graph.datasets import TABLE2, load
from repro.graph.delta import (edge_delta_from_numpy, label_delta_from_numpy,
                               symmetrize_delta)
from repro.graph.sbm import sample_sbm
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import cli as obs_cli
from repro.search.service import GEEDeltaServer


def _undirected_pairs(edges):
    """Valid directed entries -> one row per undirected edge (src <= dst)."""
    e = edges.num_edges
    src = np.asarray(edges.src)[:e]
    dst = np.asarray(edges.dst)[:e]
    w = np.asarray(edges.weight)[:e]
    keep = src <= dst
    return src[keep], dst[keep], w[keep]


def prepare_stream(args):
    """Deterministic stream setup shared by fresh runs, recovered runs and
    the recovery benchmark's reference rebuild: load the graph, permute the
    undirected edges with the seeded RNG, split base vs stream.  Returns a
    dict; ``rng`` is positioned right after the permutation draw, so
    per-batch label draws replay identically across runs."""
    if args.sbm:
        s = sample_sbm(args.sbm, seed=args.seed)
        edges, labels, k = s.edges, s.labels, s.num_classes
        name = f"sbm-{args.sbm}"
    else:
        ds = load(args.dataset or "citeseer", seed=args.seed)
        edges, labels, k = ds.edges, ds.labels, ds.spec.num_classes
        name = ds.spec.name
    opts = GEEOptions(laplacian=args.lap, diag_aug=args.diag,
                      correlation=args.cor)
    rng = np.random.default_rng(args.seed)
    su, du, wu = _undirected_pairs(edges)
    perm = rng.permutation(su.size)
    su, du, wu = su[perm], du[perm], wu[perm]
    n_stream = int(round(su.size * args.stream_frac))
    n_base = su.size - n_stream
    base = symmetrize(edge_list_from_numpy(
        su[:n_base], du[:n_base], wu[:n_base], edges.num_nodes))
    return dict(name=name, edges=edges, labels=labels, k=k, opts=opts,
                rng=rng, su=su, du=du, wu=wu, n_stream=n_stream,
                n_base=n_base, base=base)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sbm", type=int, default=None)
    ap.add_argument("--dataset", default=None,
                    help=f"one of {sorted(TABLE2)}")
    ap.add_argument("--stream-frac", type=float, default=0.2,
                    help="fraction of undirected edges replayed as a stream")
    ap.add_argument("--batch", type=int, default=64,
                    help="undirected edge inserts per delta batch")
    ap.add_argument("--label-frac", type=float, default=0.02,
                    help="label flips per batch, as a fraction of --batch")
    ap.add_argument("--verify-every", type=int, default=20,
                    help="full-recompute check every this many batches")
    ap.add_argument("--max-batches", type=int, default=None,
                    help="cap on stream batches (CI smoke runs)")
    ap.add_argument("--lap", action="store_true")
    ap.add_argument("--diag", action="store_true")
    ap.add_argument("--cor", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--snapshot-dir", default=None,
                    help="run crash-safe: WAL every batch + periodic "
                         "snapshots under this directory")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="batches between snapshots (with --snapshot-dir)")
    ap.add_argument("--recover", action="store_true",
                    help="resume from the newest snapshot in --snapshot-dir "
                         "(+ WAL replay) instead of starting fresh")
    ap.add_argument("--prefetch-windows", type=int, default=None,
                    help="sets REPRO_GEE_PREFETCH_WINDOWS for this process: "
                         "windows staged ahead by any streamed fold it runs "
                         "(0 = synchronous reads)")
    obs_cli.add_flags(ap)
    args = ap.parse_args(argv)
    if args.recover and not args.snapshot_dir:
        ap.error("--recover requires --snapshot-dir")
    if args.prefetch_windows is not None:
        import os
        from repro.graph.prefetch import ENV_PREFETCH_WINDOWS
        os.environ[ENV_PREFETCH_WINDOWS] = str(args.prefetch_windows)
    obs_cli.setup(args)
    enable_compile_cache()

    st = prepare_stream(args)
    name, edges, labels, k, opts = (st["name"], st["edges"], st["labels"],
                                    st["k"], st["opts"])
    rng, su, du, wu = st["rng"], st["su"], st["du"], st["wu"]
    n_stream, n_base = st["n_stream"], st["n_base"]
    print(f"{name}: N={edges.num_nodes} K={k} [{opts.tag()}]  "
          f"base E={n_base} streaming E={n_stream} in batches of {args.batch}"
          f"  platform={jax.default_backend()}")

    n_labels = max(1, int(round(args.batch * args.label_frac))) \
        if args.label_frac > 0 else 0
    n_batches = -(-n_stream // args.batch)
    if args.max_batches is not None:
        n_batches = min(n_batches, args.max_batches)
    snapshotter = index = service = None
    start_batch = 0

    if args.recover:
        from repro.search.service import GEEQueryService
        from repro.serve.snapshot import GEESnapshotter, recover

        t0 = time.perf_counter()
        rec = recover(args.snapshot_dir)
        inc, index = rec.inc, rec.index
        # Resume position: the snapshot records the last batch folded into
        # it; WAL records replayed past it may carry a later one.
        start_batch = max(int(rec.extra.get("batch", -1)),
                          int(rec.last_meta.get("batch", -1))) + 1
        print(f"  recovered snapshot step {rec.snapshot_step} "
              f"(watermark {rec.snapshot_watermark}) + "
              f"{rec.replayed_deltas} replayed deltas in "
              f"{(time.perf_counter()-t0)*1e3:.1f} ms; "
              f"resuming at batch {start_batch}/{n_batches}")
        if args.trace:
            for ev in rec.timeline:
                print(f"    recovery: {ev}")
        # Replay the RNG draws the applied batches consumed, so the resumed
        # stream continues the exact sequence of the uninterrupted run.
        for _ in range(start_batch if n_labels else 0):
            rng.integers(0, edges.num_nodes, n_labels)
            rng.integers(0, k, n_labels)
        if index is not None:
            service = GEEQueryService(index, inc, flush_every=10**9)
        snapshotter = GEESnapshotter(args.snapshot_dir,
                                     every=args.snapshot_every)
        snapshotter.log = rec.log              # reuse the scanned WAL handle
    else:
        t0 = time.perf_counter()
        inc = IncrementalGEE.from_graph(st["base"], labels, k, opts)
        inc.embedding()
        print(f"  initial fit + materialize: "
              f"{(time.perf_counter()-t0)*1e3:.1f} ms")

    if args.snapshot_dir and snapshotter is None:
        from repro.search.index import ClassPartitionedIndex
        from repro.search.service import GEEQueryService
        from repro.serve.snapshot import GEESnapshotter

        index = ClassPartitionedIndex.build(inc.embedding(), labels, k)
        service = GEEQueryService(index, inc, flush_every=10**9)
        snapshotter = GEESnapshotter(args.snapshot_dir,
                                     every=args.snapshot_every)
        # Baseline snapshot before any stream batch: a kill during batch 0
        # still recovers (to the base fit) instead of refitting.
        snapshotter.snapshot(inc, index, service=service,
                             extra={"batch": -1})

    if snapshotter is not None:
        # One explicit flush per stream batch -> the batch's edge and label
        # deltas commit as ONE atomic WAL record (no torn batches at a
        # kill point); auto-flush would split them.
        server = GEEDeltaServer(inc, flush_every=10**9, log=snapshotter.log)
    else:
        server = GEEDeltaServer(inc, flush_every=args.batch)

    y = inc.labels.copy() if args.recover else labels.copy()
    update_ts, recompute_ts, max_err = [], [], 0.0
    for b in range(start_batch, n_batches):
        lo, hi = n_base + b * args.batch, n_base + min((b + 1) * args.batch,
                                                       n_stream)
        delta = symmetrize_delta(edge_delta_from_numpy(
            su[lo:hi], du[lo:hi], wu[lo:hi]))
        t0 = time.perf_counter()
        server.meta = {"batch": b}
        server.submit(delta)
        if n_labels:
            nodes = rng.integers(0, edges.num_nodes, n_labels)
            newl = rng.integers(0, k, n_labels).astype(np.int32)
            server.submit(label_delta_from_numpy(nodes, newl))
            y[nodes] = newl
        server.flush()
        server.embed()
        update_ts.append(time.perf_counter() - t0)
        if snapshotter is not None:
            snapshotter.tick(inc, index, service=service,
                             delta_server=server, extra={"batch": b})

        if args.verify_every and (b + 1) % args.verify_every == 0:
            cur = inc.to_edge_list()
            zr = gee_sparse_jax(cur, jnp.asarray(y), k, opts)
            jax.block_until_ready(zr)           # compile outside the timing
            t0 = time.perf_counter()
            jax.block_until_ready(gee_sparse_jax(cur, jnp.asarray(y), k,
                                                 opts))
            recompute_ts.append(time.perf_counter() - t0)
            err = float(np.abs(inc.embedding() - np.asarray(zr)).max())
            max_err = max(max_err, err)
            print(f"  batch {b+1:4d}/{n_batches}: verify max_err={err:.2e}  "
                  f"recompute={recompute_ts[-1]*1e3:.1f} ms")

    if snapshotter is not None:
        # Final snapshot at the stream end, then release the writer thread.
        snapshotter.snapshot(inc, index, service=service,
                             delta_server=server,
                             extra={"batch": n_batches - 1})
        print(f"  snapshotter stats: {snapshotter.stats}  "
              f"wal head_seq={snapshotter.log.head_seq}")
        snapshotter.close()
    if service is not None:
        service.close()

    ts = np.asarray(update_ts) * 1e3 if update_ts else np.zeros(1)
    print(f"  update latency over {len(update_ts)} batches: "
          f"mean={ts.mean():.2f} ms p50={np.percentile(ts, 50):.2f} ms "
          f"p95={np.percentile(ts, 95):.2f} ms")
    if recompute_ts:
        rc = float(np.mean(recompute_ts)) * 1e3
        print(f"  full recompute: {rc:.2f} ms -> "
              f"update/recompute = {ts.mean()/rc:.2f}x  "
              f"(max verify err {max_err:.2e})")
    print(f"  server stats: {server.stats}")
    print(f"  incremental stats: {inc.stats}")
    obs_cli.finish(args)
    return {"update_ms_mean": float(ts.mean()),
            "recompute_ms": float(np.mean(recompute_ts)) * 1e3
            if recompute_ts else None,
            "max_err": max_err,
            "batches_run": len(update_ts),
            "watermark": int(inc.applied_seq)}


if __name__ == "__main__":
    main()
