"""Drive a whole run of a cell, minus the look for a chip, at a size a test
run holds, with the program broken underneath the timed path.

  python3 bench/tests/faults.py <workload> <fault>    (prints the result line)

Faults, each planted in the program and not in the harness:

  none      the program as it is
  stale     every fit returns the first fit's Z: a step that returns its
            state unchanged
  half      the contraction sees only the first half of the edges
  altered   one entry of each Z is moved by 1e-3 where it is produced
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from contextlib import ExitStack
from pathlib import Path
from unittest import mock

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

FAULTS = ("none", "stale", "half", "altered")
TEST_PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


def cells(chips: int) -> tuple:
    """Names of BENCHMARK.json's cells that ask for ``chips`` chips."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return tuple(w["name"] for w in bench["workloads"]
                 if w["chips"] == chips)


def tiny_cell(workload: str, num_nodes: int = 1500, num_edges: int = 15000):
    """The committed cell, limits included, on a graph a test can hold;
    the labelled share is kept."""
    from harness import spec

    cell = spec.load_cell(workload)
    c = cell.config
    labelled = max(1, round(c["labelled"] / c["num_nodes"] * num_nodes))
    config = dict(c, num_nodes=num_nodes, num_edges=num_edges,
                  labelled=labelled)
    return dataclasses.replace(cell, config=config)


def _halve(weight):
    e = weight.shape[0]
    return weight.at[e // 2:].set(0.0)


def _patches(fault: str, stack: ExitStack):
    from repro.core import api, fold, plan

    if fault == "stale":
        orig = api.GEEEmbedder.transform
        first = []

        def stale(self):
            z = orig(self)
            if not first:
                first.append(z)
            return first[0]
        stack.enter_context(mock.patch.object(api.GEEEmbedder, "transform",
                                              stale))
    elif fault == "altered":
        orig = api.GEEEmbedder.transform

        def altered(self):
            return orig(self).at[0, 0].add(1e-3)
        stack.enter_context(mock.patch.object(api.GEEEmbedder, "transform",
                                              altered))
    elif fault == "half":
        orig_sparse, orig_fold = plan.gee_sparse_jax, fold.fold_z

        def sparse(edges, *a, **kw):
            return orig_sparse(dataclasses.replace(
                edges, weight=_halve(edges.weight)), *a, **kw)

        def one_device(z, src, dst, weight, *a, **kw):
            return orig_fold(z, src, dst, _halve(weight), *a, **kw)
        stack.enter_context(mock.patch.object(plan, "gee_sparse_jax", sparse))
        stack.enter_context(mock.patch.object(fold, "fold_z", one_device))
    elif fault != "none":
        raise ValueError(f"unknown fault {fault!r}")


def run(workload: str, fault: str, seconds: float = 0.5) -> dict:
    import jax

    from harness import runner

    cell = tiny_cell(workload)
    with ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            runner, "require_devices", lambda chips: jax.devices()))
        stack.enter_context(mock.patch.object(
            runner, "enable_compile_cache", lambda: None))
        stack.enter_context(mock.patch.object(
            runner.work, "peaks", lambda kind: TEST_PEAK))
        _patches(fault, stack)
        return runner.run_cell(cell, 2**31 + 11, seconds, False,
                               time.perf_counter())


if __name__ == "__main__":
    print(json.dumps(run(sys.argv[1], sys.argv[2])))
