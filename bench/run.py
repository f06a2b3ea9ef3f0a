"""Run one cell of the GEE chip benchmark once.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in BENCHMARK.json; its configuration,
traffic mix, limits and per-layer readers are files under bench/ found by
name (see bench/harness/spec.py).  Set-up makes the graph and labels from
``--seed``, hands them to the program and compiles every program the
window uses; the window then runs fits back to back for ``--seconds``;
afterwards a sample of the window's answers is compared with the float64
reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of
the window), ``device``, with ``--trace 1`` a ``breakdown``, and last the
``checks``, each compared number beside its limit.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None, metavar="DIR",
                    help="with --trace 1, also keep the profiler's trace "
                         "in DIR, with the reduction's input as "
                         "DIR/captured.json")
    args = ap.parse_args(argv)

    from harness import runner, spec

    cell = spec.load_cell(args.workload)
    try:
        result = runner.run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), T_START,
                                 save_trace=args.save_trace)
    except runner.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
