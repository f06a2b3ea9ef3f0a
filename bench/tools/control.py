"""The control's readings at a cell's own size: the reference computed one
precision below what the cell's path states, compared with the float64
reference by the numbers that decide ``correct``.

  python3 bench/tools/control.py --workload W --seeds 1,2,3 [--fits 2]

It builds each seed's graph and labels exactly as a run does (numpy only,
no device needed) and prints one JSON line per seed and fit, then the
smallest reading of each number over them all: the upper reading a limit
has to stay below.  The precision is the cell's ``control`` in
bench/limits/<workload>.json.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import check, spec  # noqa: E402
from harness.control import readings  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fits", type=int, default=2)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    precision = cell.limits["control"]
    every = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for i, r in enumerate(readings(cell.config, seed, args.fits,
                                       precision)):
            every.append(r)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fit": i, "precision": precision, **r}),
                  flush=True)
    print(json.dumps({"workload": args.workload, "precision": precision,
                      "upper": {name: min(r[name] for r in every)
                                for name in check.NUMBERS}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
