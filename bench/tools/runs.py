"""Run one cell several times, one process after another, and summarise.

  python3 bench/tools/runs.py --workload W --seeds 11,12,13 --seconds 10 \
      [--trace 0,0,1] [--out runs/W.jsonl] [--save-traces DIR]

Each run is ``bench/run.py`` in a process of its own (this process never
touches JAX, so each child has the chips to itself).  Every run's result
line, exit code and the tail of its standard error go to ``--out`` as one
JSON line; the summary gives, per metric, the median and the spread
(interquartile range of ``statistics.quantiles(values, n=4)`` over the
median) of the untraced runs.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--save-traces", default=None)
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)

    seeds = [int(s) for s in args.seeds.split(",")]
    traces = [int(t) for t in args.trace.split(",")] if args.trace else []
    traces += [0] * (len(seeds) - len(traces))
    rows = []
    for seed, tr in zip(seeds, traces):
        cmd = [sys.executable, str(BENCH / "run.py"), "--workload",
               args.workload, "--seed", str(seed), "--seconds",
               str(args.seconds), "--trace", str(tr)]
        if tr and args.save_traces:
            cmd += ["--save-trace", str(Path(args.save_traces)
                                        / f"{args.workload}.{seed}")]
        t0 = time.perf_counter()
        p = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=args.timeout)
        wall = time.perf_counter() - t0
        lines = p.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            result = None
        row = {"workload": args.workload, "seed": seed, "trace": tr,
               "rc": p.returncode, "wall_s": wall, "result": result,
               "stderr_tail": p.stderr[-3000:]}
        rows.append(row)
        short = {k: (result or {}).get(k) for k in ("correct", "attempted",
                                                    "metrics", "checks")}
        print(json.dumps({"seed": seed, "trace": tr, "rc": p.returncode,
                          "wall_s": round(wall, 1), **short}), flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")

    untraced = [r["result"] for r in rows
                if r["result"] and not r["trace"] and r["rc"] == 0]
    names = sorted({m for r in untraced for m in r["metrics"]})
    summary = {}
    for m in names:
        vals = [r["metrics"][m]["value"] for r in untraced
                if m in r["metrics"]]
        summary[m] = {"n": len(vals), "median": statistics.median(vals),
                      "spread": spread(vals), "values": vals}
    print(json.dumps({"summary": summary,
                      "all_correct": all(r["result"] and r["result"]["correct"]
                                         for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
