"""Peak host memory of the check that decides ``correct``: the reference
and the comparison of one kept answer, whole-array or by row blocks.

  python3 bench/tools/check_memory.py --form blocks|whole \
      [--nodes 2000000 --edges 29100000 --classes 172 --labelled 21800]

One form a process (numpy only, no device).  It makes a graph with the
benchmark's sampler and a float32 answer of N x K, then builds the
reference and compares the answer with it: ``whole`` as one N x K array
(``check.gaps(z, ref.embed(y, k))``), ``blocks`` as a run does it
(``check.answer_gaps`` over ``ref.blocks(k)``).  It prints one JSON line:
the resident memory before the reference (graph and answer held), the
process's peak after the check, the difference, and the reading, which
is the same in both forms.
"""

import argparse
import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import check, graphgen  # noqa: E402
from harness.reference import Reference  # noqa: E402

OPTIONS = {"laplacian": True, "diag_aug": True, "correlation": True}


def rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * resource.getpagesize()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--form", choices=("blocks", "whole"), required=True)
    ap.add_argument("--nodes", type=int, default=2_000_000)
    ap.add_argument("--edges", type=int, default=29_100_000)
    ap.add_argument("--classes", type=int, default=172)
    ap.add_argument("--labelled", type=int, default=21_800)
    ap.add_argument("--seed", type=int, default=2**31 + 19)
    args = ap.parse_args(argv)
    n, k = args.nodes, args.classes

    s, d = graphgen.base_graph(n, args.edges, k, graph_seed=0)
    s, d = graphgen.relabel(s, d, n, args.seed)
    y = graphgen.draw_labels(n, k, args.labelled, args.seed, 0)
    z = np.random.default_rng(args.seed).standard_normal((n, k), np.float32)
    z *= 0.01
    before = rss_bytes()

    t = time.perf_counter()
    ref = Reference(np.concatenate([s, d]), np.concatenate([d, s]), n,
                    OPTIONS)
    if args.form == "whole":
        reading = check.gaps(z, ref.embed(y, k))
        blocks = 1
    else:
        bounds = ref.blocks(k)
        reading = check.answer_gaps(
            z, lambda lo, hi: ref.embed_rows(y, k, lo, hi), bounds)
        blocks = len(bounds)
    seconds = time.perf_counter() - t
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    print(json.dumps({"form": args.form, "nodes": n, "edges": args.edges,
                      "classes": k, "blocks": blocks,
                      "answer_bytes": z.nbytes, "rss_before": before,
                      "peak": peak, "peak_above": peak - before,
                      "seconds": seconds, **reading}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
