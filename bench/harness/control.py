"""The control's readings: the reference one precision below what the
cell's path states, put in the program's place and compared with the
float64 reference by the numbers that decide ``correct``."""

from __future__ import annotations

import numpy as np

from harness import check, graphgen
from harness.reference import Reference


def readings(config: dict, seed: int, fits: int, precision: str) -> list:
    """One reading per fit of ``seed``'s graph and labels, made exactly as
    a run makes them."""
    n, e, k = config["num_nodes"], config["num_edges"], config["num_classes"]
    s, d = graphgen.base_graph(n, e, k, config["graph_seed"])
    s, d = graphgen.relabel(s, d, n, seed)
    ref = Reference(np.concatenate([s, d]), np.concatenate([d, s]), n,
                    config["options"])
    del s, d
    blocks = ref.blocks(k)
    out = []
    for i in range(fits):
        y = graphgen.draw_labels(n, k, config["labelled"], seed, i)
        out.append(check.block_gaps(
            lambda lo, hi: ref.embed_rows(y, k, lo, hi, precision),
            lambda lo, hi: ref.embed_rows(y, k, lo, hi), blocks))
    return out
