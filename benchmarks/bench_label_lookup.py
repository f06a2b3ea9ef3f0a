"""Where the in-kernel label lookup stops beating XLA's gather.

Times a Pallas fit (``kernels/gee_fused.py::gee_fused_from_bucketed``) of
a one-bucket synthetic packing, once with the bucket's labels looked up
inside the kernel from the packed VMEM table and once with XLA's gather
``labels[cols]`` into planes, for a sweep of vertex counts N and class
counts K.  The route is forced by setting ``LOOKUP_MAX_TABLE_ROWS`` around
each fit: to the table's rows for the lookup, to 0 for the gather.  The
scan's cost grows with the table's rows T (N / 1,024 codes a row at 4
bits, N / 512 at 8 bits); the gather's with the slots alone.  So the
ratio of the two per fit, read against T, gives the crossover that
``gee_fused.LOOKUP_MAX_TABLE_ROWS`` holds.

  PYTHONPATH=src python benchmarks/bench_label_lookup.py \\
      --nodes 92482,500000,1000000 --classes 5,47 --json out.json

It runs on a TPU only and exits 2 elsewhere: interpreted kernels say
nothing of a chip's times.  Each line names the device and reports both
routes' median seconds a fit, and whether their Z agree bit for bit.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gee import GEEOptions
from repro.graph.ell import BucketedELL, ELLBucket
from repro.kernels import gee_fused

OPTS = GEEOptions(laplacian=True, diag_aug=True, correlation=True)


def one_bucket(n: int, rows: int, width: int, seed: int = 0):
    """A packing of ``rows`` rows of ``width`` neighbours drawn uniformly
    from ``n`` vertices, and its label-free scaling."""
    rng = np.random.default_rng(seed)
    bucket = ELLBucket(
        cols=jnp.asarray(rng.integers(0, n, (rows, width)), jnp.int32),
        vals=jnp.asarray(rng.uniform(0.1, 1.0, (rows, width)), jnp.float32),
        row_ids=jnp.asarray(rng.permutation(n)[:rows], jnp.int32),
        num_rows=rows, width=width, num_edges=rows * width)
    bell = BucketedELL(buckets=(bucket,), num_nodes=n)
    return bell, gee_fused.scale_buckets(bell, laplacian=OPTS.laplacian,
                                         diag_aug=OPTS.diag_aug)


@contextlib.contextmanager
def route(lookup: bool, table_rows: int):
    """Send every fit inside to the lookup (True) or the gather route."""
    kept = gee_fused.LOOKUP_MAX_TABLE_ROWS
    gee_fused.LOOKUP_MAX_TABLE_ROWS = table_rows if lookup else 0
    try:
        yield
    finally:
        gee_fused.LOOKUP_MAX_TABLE_ROWS = kept


def seconds_a_fit(fit, repeats: int) -> list[float]:
    jax.block_until_ready(fit())                # compile
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fit())
        out.append(time.perf_counter() - t0)
    return out


def run(nodes, classes, rows: int, width: int, repeats: int, device: str):
    lines = []
    for k in classes:
        for n in nodes:
            bell, scaling = one_bucket(n, rows, width)
            labels = jnp.asarray(np.random.default_rng(1).integers(0, k, n),
                                 jnp.int32)
            t_rows = gee_fused.label_table_rows(n, k)
            bd = gee_fused.choose_fused_block_sizes(rows, width, k)[1]
            z, t = {}, {}
            for lookup in (True, False):
                with route(lookup, t_rows):
                    if gee_fused.lookup_routes(n, k, [bd]) != (lookup,):
                        raise SystemExit(f"width {width} cannot take the "
                                         f"lookup route (K={k}, N={n})")

                    def fit():
                        return gee_fused.gee_fused_from_bucketed(
                            bell, labels, k, OPTS, scaling=scaling)
                    z[lookup] = np.asarray(fit())
                    t[lookup] = seconds_a_fit(fit, repeats)
            line = {"device": device, "n": n, "k": k, "table_rows": t_rows,
                    "slots": rows * width,
                    "vmem_s": statistics.median(t[True]),
                    "gather_s": statistics.median(t[False]),
                    "vmem_runs": t[True], "gather_runs": t[False],
                    "bit_equal": bool(np.array_equal(z[True], z[False]))}
            line["vmem_over_gather"] = line["vmem_s"] / line["gather_s"]
            print(json.dumps(line), flush=True)
            lines.append(line)
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", default="92482,250000,500000,1000000")
    ap.add_argument("--classes", default="5,47")
    ap.add_argument("--rows", type=int, default=24_980)
    ap.add_argument("--width", type=int, default=512)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"bench_label_lookup: needs a TPU, found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    device = str(jax.devices()[0].device_kind)
    lines = run([int(x) for x in args.nodes.split(",")],
                [int(x) for x in args.classes.split(",")],
                args.rows, args.width, args.repeats, device)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"device": device, "lines": lines}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
