"""The least work one GEE fit needs, whatever implements it, and the
chip's peaks to hold it against.

Per fit of a graph of N vertices, E undirected (2E directed) edges and K
classes:

  bytes  each directed edge's neighbour id and weight read once (8 B), each
         vertex label read once (4 B), the N x K float32 Z written once;
  flops  2 per directed edge (multiply, add), and 3 per element of Z for
         the epilogue (square, sum, divide).

Never counted from ELL slots, bucket widths, windows or lane padding: a
program that pads does more than this, not the roofline less.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def fit_work(num_nodes: int, num_edges: int, num_classes: int) -> dict:
    n, e2, k = int(num_nodes), 2 * int(num_edges), int(num_classes)
    return {"bytes": 8.0 * e2 + 4.0 * n + 4.0 * n * k,
            "flops": 2.0 * e2 + 3.0 * n * k}


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(work: dict, peak: dict, chips: int = 1) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s, with the work spread over ``chips``."""
    return max(work["flops"] / (chips * peak["flops_per_s"]),
               work["bytes"] / (chips * peak["bytes_per_s"]))
