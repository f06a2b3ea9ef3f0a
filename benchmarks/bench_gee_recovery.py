"""Crash-safe serving benchmark: kill-and-recover exactness + replica scaling.

Three scenarios, one JSON (``BENCH_serve.json``):

* **kill-and-recover** -- runs the streaming driver
  (``repro.launch.gee_stream --snapshot-dir``) as a subprocess, SIGKILLs it
  mid-stream once a few snapshots exist, resumes it with ``--recover``, and
  compares the final recovered state against an uninterrupted reference
  run: max |dZ| must be <= ``--tol`` (1e-5) and the recovered index's
  full-probe neighbors must exactly match brute force on the reference
  embedding.  Also reports time-to-recover and deltas replayed.
* **saturation / replica scaling** -- hydrates N read replicas from one
  snapshot directory, one per OS process (single-threaded XLA each, so the
  scaling measured is replication, not intra-op threads), and measures
  aggregate read QPS at each replica count.  ``--min-scaling`` gates the
  2-replica speedup (CI uses 1.6; pass 0 on single-core boxes).
* **load shedding** -- drives an in-process ``ReplicaRouter`` over
  bounded-queue services past saturation and checks every rejected read is
  *counted* (``shed + served == attempted``), never silently dropped.

Every child process is pinned to the CPU (several processes cannot share
one accelerator), and each child reports the platform it ran on: the rows
and the JSON name it, so these numbers are never read as device numbers.

  PYTHONPATH=src JAX_PLATFORMS=cpu python benchmarks/bench_gee_recovery.py \
      [--sbm 400] [--replicas 1,2] [--min-scaling 1.6] [--json BENCH_serve.json]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# Single-threaded XLA/BLAS for replica workers: each replica must cost one
# core, so aggregate QPS growth measures replication, not hidden intra-op
# parallelism already saturating the machine.
_WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": ("--xla_cpu_multi_thread_eigen=false "
                  "intra_op_parallelism_threads=1"),
    "OMP_NUM_THREADS": "1",
    "OPENBLAS_NUM_THREADS": "1",
}


def _stream_args(args, snapshot_dir: str) -> list[str]:
    return ["--sbm", str(args.sbm), "--stream-frac", str(args.stream_frac),
            "--batch", str(args.batch), "--verify-every", "0",
            "--label-frac", str(args.label_frac),
            "--snapshot-every", str(args.snapshot_every),
            "--seed", str(args.seed), "--lap", "--diag",
            "--snapshot-dir", snapshot_dir]


def _run_stream(args, snapshot_dir: str, extra: list[str] = ()):
    cmd = [sys.executable, "-m", "repro.launch.gee_stream",
           *_stream_args(args, snapshot_dir), *extra]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": SRC + os.pathsep + os.environ.get("PYTHONPATH", "")}
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)


def bench_kill_and_recover(args) -> dict:
    """SIGKILL the streaming driver mid-flight; recovered final state must
    match an uninterrupted run."""
    from repro.launch.gee_search import recall_at_k
    from repro.serve.snapshot import recover

    ref_dir = tempfile.mkdtemp(prefix="gee_ref_")
    kill_dir = tempfile.mkdtemp(prefix="gee_kill_")

    r = _run_stream(args, ref_dir)
    if r.returncode != 0:
        raise SystemExit(f"reference stream failed:\n{r.stdout}\n{r.stderr}")
    stream_platform = next((tok.split("=", 1)[1]
                            for tok in r.stdout.split()
                            if tok.startswith("platform=")), "unknown")

    cmd = [sys.executable, "-m", "repro.launch.gee_stream",
           *_stream_args(args, kill_dir)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": SRC + os.pathsep + os.environ.get("PYTHONPATH", "")}
    child = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
    snap_sub = os.path.join(kill_dir, "snapshots")
    deadline = time.time() + 300
    killed = False
    while time.time() < deadline and child.poll() is None:
        done = len([s for s in os.listdir(snap_sub)
                    if s.startswith("step_")]) if os.path.isdir(snap_sub) \
            else 0
        if done >= args.kill_after_snapshots:
            child.send_signal(signal.SIGKILL)       # no cleanup, no atexit
            child.wait()
            killed = True
            break
        time.sleep(0.05)
    if not killed:
        child.kill()
        child.wait()
        raise SystemExit(
            "stream finished before the kill point; increase --sbm or "
            "lower --kill-after-snapshots")

    t0 = time.perf_counter()
    r = _run_stream(args, kill_dir, extra=["--recover"])
    t_resume = time.perf_counter() - t0
    if r.returncode != 0:
        raise SystemExit(f"recovery run failed:\n{r.stdout}\n{r.stderr}")
    resumed_line = next((ln for ln in r.stdout.splitlines()
                         if "recovered snapshot" in ln), "")

    # Compare the two final states (each run closes with a snapshot).
    t0 = time.perf_counter()
    ref = recover(ref_dir)
    rec = recover(kill_dir)
    t_recover = time.perf_counter() - t0
    z_ref = ref.inc.embedding()
    z_rec = rec.inc.embedding()
    max_err = float(np.abs(z_ref.astype(np.float64)
                           - z_rec.astype(np.float64)).max())

    rng = np.random.default_rng(args.seed)
    q_rows = rng.integers(0, ref.inc.n, 64)
    ids_b, sc_b = (np.asarray(a) for a in
                   ref.index.search(z_ref[q_rows], args.k, brute_force=True))
    ids_r, sc_r = (np.asarray(a) for a in
                   rec.index.search(z_rec[q_rows], args.k,
                                    nprobe=rec.index.num_cells))
    neighbor_recall = recall_at_k(ids_r, sc_r, ids_b, sc_b)

    row = {
        "stream_platform": stream_platform,
        "killed_mid_stream": killed,
        "watermark_ref": int(ref.inc.applied_seq),
        "watermark_recovered": int(rec.inc.applied_seq),
        "max_abs_z_err": max_err,
        "neighbor_recall_full_probe": float(neighbor_recall),
        "t_resume_run": t_resume,
        "t_recover_state": t_recover,
        "resumed": resumed_line.strip(),
    }
    print(f"kill-and-recover ({stream_platform}): max|dZ|={max_err:.2e}  "
          f"neighbor recall={neighbor_recall:.3f}  "
          f"recover={t_recover*1e3:.1f} ms")
    if max_err > args.tol:
        raise SystemExit(f"recovered Z deviates {max_err:.2e} > tol "
                         f"{args.tol:.0e} from the uninterrupted run")
    if neighbor_recall < 1.0:
        raise SystemExit(f"recovered index neighbor recall "
                         f"{neighbor_recall:.4f} < 1.0 vs reference")
    row["snapshot_dir"] = kill_dir     # reused by the saturation scenario
    return row


# ---------------------------------------------------------------------------
# saturation: one replica per process, aggregate read QPS
# ---------------------------------------------------------------------------

def _worker_main(args) -> None:
    """Subprocess body: recover a replica, handshake, serve reads for a
    fixed duration, report the count."""
    t0 = time.perf_counter()
    import jax

    from repro.serve.replica import GEEReplica

    replica = GEEReplica.from_directory(
        args.snapshot_dir, name=f"w{args.worker_seed}",
        flush_every=10**9, pad_multiple=args.batch_queries)
    n = replica.inc.n
    rng = np.random.default_rng(args.worker_seed)
    rows = rng.integers(0, n, (64, args.batch_queries))
    # warm the jitted search path before the measured window
    replica.service.submit_rows(rows[0], args.k)
    replica.service.flush()
    print(f"READY {(time.perf_counter() - t0) * 1e3:.1f} "
          f"{jax.default_backend()}", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return
    served, i = 0, 0
    t_end = time.perf_counter() + args.duration
    while time.perf_counter() < t_end:
        replica.service.submit_rows(rows[i % rows.shape[0]], args.k)
        replica.service.flush()
        served += args.batch_queries
        i += 1
    print(f"DONE {served}", flush=True)


def _measure_replicas(args, snapshot_dir: str, n_replicas: int) -> dict:
    env = {**os.environ, **_WORKER_ENV,
           "PYTHONPATH": SRC + os.pathsep + os.environ.get("PYTHONPATH", "")}
    cmd_base = [sys.executable, os.path.abspath(__file__), "--worker",
                "--snapshot-dir", snapshot_dir,
                "--duration", str(args.duration),
                "--batch-queries", str(args.batch_queries),
                "--k", str(args.k)]
    procs = [subprocess.Popen(cmd_base + ["--worker-seed", str(i)],
                              env=env, stdin=subprocess.PIPE,
                              stdout=subprocess.PIPE, text=True, bufsize=1)
             for i in range(n_replicas)]
    recover_ms, platforms = [], set()
    try:
        for p in procs:                      # barrier: all replicas hydrated
            line = p.stdout.readline().split()
            if not line or line[0] != "READY":
                raise SystemExit(f"replica worker failed to start: {line}")
            recover_ms.append(float(line[1]))
            platforms.add(line[2])
        t0 = time.perf_counter()
        for p in procs:
            p.stdin.write("GO\n")
            p.stdin.flush()
        served = 0
        for p in procs:
            line = p.stdout.readline().split()
            if not line or line[0] != "DONE":
                raise SystemExit(f"replica worker died mid-run: {line}")
            served += int(line[1])
        elapsed = time.perf_counter() - t0
    finally:
        for p in procs:
            p.kill()
            p.wait()
    return {"replicas": n_replicas, "platform": ",".join(sorted(platforms)),
            "served": served, "qps": served / max(elapsed, 1e-9),
            "recover_ms_mean": float(np.mean(recover_ms))}


def bench_saturation(args, snapshot_dir: str) -> dict:
    rows = [_measure_replicas(args, snapshot_dir, n)
            for n in args.replica_counts]
    base = rows[0]["qps"]
    for r in rows:
        r["scaling_vs_1"] = r["qps"] / max(base, 1e-9)
        print(f"replicas={r['replicas']} ({r['platform']})  "
              f"qps={r['qps']:10,.0f}  "
              f"scaling={r['scaling_vs_1']:.2f}x  "
              f"recover={r['recover_ms_mean']:.0f} ms")
    two = next((r for r in rows if r["replicas"] == 2), None)
    if args.min_scaling and two is not None \
            and two["scaling_vs_1"] < args.min_scaling:
        raise SystemExit(
            f"2-replica read scaling {two['scaling_vs_1']:.2f}x is below "
            f"--min-scaling {args.min_scaling} "
            f"(qps_1={base:,.0f}, qps_2={two['qps']:,.0f})")
    return {"rows": rows, "duration_s": args.duration,
            "batch_queries": args.batch_queries}


def bench_shedding(args, snapshot_dir: str) -> dict:
    """Past saturation, every rejected read must be counted, not dropped."""
    from repro.serve.replica import (GEEReplica, LoadShedError,
                                     ReplicaRouter)

    replicas = [GEEReplica.from_directory(snapshot_dir, name=f"r{i}",
                                          flush_every=10**9, max_pending=32)
                for i in range(2)]
    router = ReplicaRouter(replicas, max_lag=0)
    rng = np.random.default_rng(args.seed)
    n = replicas[0].inc.n
    attempted, served, shed = 0, 0, 0
    for i in range(64):                      # 64 batches of 8 vs 2x32 slots
        attempted += 1
        try:
            router.submit_rows(rng.integers(0, n, 8), args.k)
            served += 1
        except LoadShedError:
            shed += 1
        if i % 16 == 15:
            router.flush_all()               # drain, then saturate again
    router.flush_all()
    counted = int(router.stats["shed_reads"])
    print(f"shedding: attempted={attempted} served={served} shed={shed} "
          f"(router counted {counted})")
    if shed == 0:
        raise SystemExit("saturation never shed -- max_pending bound inert")
    if shed != counted or served + shed != attempted:
        raise SystemExit(
            f"shed accounting broken: {served}+{shed}!={attempted} or "
            f"counter {counted}!={shed}")
    router.close()
    return {"attempted": attempted, "served": served, "shed": shed,
            "shed_counted": counted}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sbm", type=int, default=400)
    ap.add_argument("--stream-frac", type=float, default=0.4)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--label-frac", type=float, default=0.02)
    ap.add_argument("--snapshot-every", type=int, default=4)
    ap.add_argument("--kill-after-snapshots", type=int, default=3,
                    help="SIGKILL the stream once this many snapshots exist")
    ap.add_argument("--tol", type=float, default=1e-5)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--replicas", type=str, default="1,2",
                    help="comma-separated replica counts to measure")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="seconds of sustained reads per replica count")
    ap.add_argument("--batch-queries", type=int, default=64)
    ap.add_argument("--min-scaling", type=float, default=0.0,
                    help="fail if 2-replica QPS scaling is below this "
                         "(CI: 1.6; keep 0 on single-core machines)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", type=str, default="BENCH_serve.json",
                    help="output JSON path ('' disables)")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--worker-seed", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--snapshot-dir", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker:
        _worker_main(args)
        return None

    args.replica_counts = tuple(int(x) for x in args.replicas.split(",") if x)
    recovery = bench_kill_and_recover(args)
    snapshot_dir = recovery.pop("snapshot_dir")
    saturation = bench_saturation(args, snapshot_dir)
    shedding = bench_shedding(args, snapshot_dir)

    payload = {"benchmark": "gee_serve",
               "sbm": args.sbm, "tol": args.tol,
               "recovery": recovery, "saturation": saturation,
               "shedding": shedding}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}")
    return payload


if __name__ == "__main__":
    main()
