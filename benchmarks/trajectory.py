"""Perf trajectory: headline numbers per commit, committed to the repo.

Every benchmark writes a detailed ``BENCH_*.json``; this tool distills each
into a handful of *headline metrics* and maintains
``benchmarks/BENCH_trajectory.json`` -- an append-only series of
``{commit, date, metrics}`` entries committed alongside the code, so the
performance history travels with the repository instead of living in CI
artifact retention.

Two modes:

* ``--compare`` (CI, warn-only): extract headlines from the BENCH files in
  the working directory and compare against the *last committed* trajectory
  entry.  Any metric regressing by more than ``--factor`` (default 1.5x,
  direction-aware) prints a GitHub ``::warning::`` annotation.  Exit code
  stays 0 -- shared runners are too noisy to hard-gate on, but the warning
  surfaces on the PR.
* ``--append``: add a new entry (commit hash from ``git rev-parse`` unless
  ``--commit`` is given) to the trajectory file.  Run locally on a quiet
  machine and commit the result; CI also uploads the would-be file as an
  artifact for convenience.

  PYTHONPATH=src python benchmarks/trajectory.py --compare
  PYTHONPATH=src python benchmarks/trajectory.py --append && git add \
      benchmarks/BENCH_trajectory.json
"""

from __future__ import annotations

import argparse
import datetime
import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAJECTORY = os.path.join(REPO, "benchmarks", "BENCH_trajectory.json")

LOWER, HIGHER = "lower", "higher"      # which direction is better


def _last_row(payload):
    return payload["rows"][-1] if payload.get("rows") else None


def _extract(payload: dict) -> dict:
    """BENCH payload -> {metric_name: (value, better)} headline dict."""
    bench = payload.get("benchmark")
    if bench is None and "worst_speedup" in payload:
        bench = "gee_plan"                   # plan bench predates the key
    out: dict[str, tuple[float, str]] = {}

    def put(name, value, better):
        if value is not None and value == value:     # drop None/NaN
            out[f"{bench}.{name}"] = (float(value), better)

    if bench == "gee_sbm":
        row = _last_row(payload)
        if row:
            put("sparse_jax_s", row.get("sparse_jax"), LOWER)
            put("scipy_s", row.get("scipy"), LOWER)
    elif bench == "gee_pallas":
        row = _last_row(payload)
        if row:
            put("pallas_bucketed_s", row.get("t_pallas_bucketed"), LOWER)
            put("sparse_jax_s", row.get("t_sparse_jax"), LOWER)
    elif bench == "gee_incremental":
        row = _last_row(payload)
        if row:
            put("edge_update_median_s", row.get("t_update_edge_median"),
                LOWER)
            put("recompute_s", row.get("t_recompute"), LOWER)
    elif bench == "gee_chunked":
        put("max_slowdown", payload.get("max_slowdown"), LOWER)
        put("prefetch_speedup", payload.get("prefetch_speedup"), HIGHER)
    elif bench == "gee_stream_shard":
        put("eps_max_shards", payload.get("eps_max_shards"), HIGHER)
        put("scaling_2x", payload.get("scaling_2x"), HIGHER)
        put("rss_growth", payload.get("rss_growth"), LOWER)
        put("prefetch_speedup", payload.get("prefetch_speedup"), HIGHER)
    elif bench == "gee_plan":
        put("prep_reuse_speedup", payload.get("worst_speedup"), HIGHER)
        put("tracer_overhead_pct", payload.get("tracer_overhead_pct"),
            LOWER)
    elif bench == "gee_search":
        row = _last_row(payload)
        if row:
            put("qps_ivf", row.get("qps_ivf"), HIGHER)
            put("recall_at_k", row.get("recall_at_k_default"), HIGHER)
        put("fused_query_speedup", payload.get("fused_query_speedup"),
            HIGHER)
    elif bench == "gee_serve":
        rec = payload.get("recovery", {})
        put("recover_state_s", rec.get("t_recover_state"), LOWER)
        for r in payload.get("saturation", {}).get("rows", []):
            put(f"qps_{r['replicas']}_replica", r.get("qps"), HIGHER)
    return out


def collect(files) -> dict:
    metrics: dict[str, tuple[float, str]] = {}
    for path in files:
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"skipping {path}: {e}")
            continue
        metrics.update(_extract(payload))
    return metrics


def load_trajectory() -> list:
    if not os.path.exists(TRAJECTORY):
        return []
    with open(TRAJECTORY) as f:
        return json.load(f)["entries"]


def _git_head() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              cwd=REPO, capture_output=True,
                              text=True).stdout.strip() or "unknown"
    except OSError:
        return "unknown"


def append(files, commit: str | None, out: str) -> int:
    metrics = collect(files)
    if not metrics:
        print("no headline metrics found; nothing appended")
        return 1
    entries = load_trajectory()
    entry = {
        "commit": commit or _git_head(),
        "date": datetime.date.today().isoformat(),
        "metrics": {k: v for k, (v, _d) in sorted(metrics.items())},
    }
    entries.append(entry)
    directions = {k: d for k, (_v, d) in metrics.items()}
    payload = {"benchmark": "trajectory", "directions": directions,
               "entries": entries}
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"appended entry for {entry['commit']} "
          f"({len(metrics)} metrics) -> {out}")
    return 0


def compare(files, factor: float) -> int:
    """Warn (exit 0) on direction-aware regressions vs the last entry."""
    current = collect(files)
    entries = load_trajectory()
    if not entries:
        print("no committed trajectory yet; nothing to compare against")
        return 0
    last = entries[-1]
    print(f"comparing {len(current)} current metrics against committed "
          f"entry {last['commit']} ({last['date']})")
    regressions = 0
    for name, (value, better) in sorted(current.items()):
        base = last["metrics"].get(name)
        if base is None or base == 0:
            print(f"  {name}: {value:.6g} (new metric, no baseline)")
            continue
        ratio = value / base
        regressed = ratio > factor if better == LOWER \
            else ratio < 1.0 / factor
        tag = "REGRESSED" if regressed else "ok"
        print(f"  {name}: {value:.6g} vs {base:.6g} "
              f"({ratio:.2f}x, {better} is better) {tag}")
        if regressed:
            regressions += 1
            print(f"::warning title=perf regression::{name} moved "
                  f"{ratio:.2f}x vs commit {last['commit']} "
                  f"({base:.6g} -> {value:.6g}, {better} is better, "
                  f"threshold {factor}x)")
    if regressions:
        print(f"{regressions} metric(s) regressed beyond {factor}x "
              f"(warning only -- shared-runner noise makes this advisory)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--append", action="store_true")
    mode.add_argument("--compare", action="store_true")
    ap.add_argument("--files", nargs="*", default=None,
                    help="BENCH json files (default: ./BENCH_*.json, "
                         "trajectory file excluded)")
    ap.add_argument("--factor", type=float, default=1.5,
                    help="regression threshold for --compare")
    ap.add_argument("--commit", default=None,
                    help="commit id recorded by --append (default: git HEAD)")
    ap.add_argument("--out", default=TRAJECTORY,
                    help="trajectory file written by --append")
    args = ap.parse_args(argv)
    files = args.files if args.files else [
        p for p in sorted(glob.glob("BENCH_*.json"))
        if os.path.basename(p) != os.path.basename(TRAJECTORY)]
    if args.append:
        return append(files, args.commit, args.out)
    return compare(files, args.factor)


if __name__ == "__main__":
    sys.exit(main())
