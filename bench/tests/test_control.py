"""The control of each cell comes out not correct: the reference computed
one precision below what the cell's path states (``control`` in
bench/limits/<workload>.json), put in the program's place, fails at least
one of the cell's committed limits on every seed.  Here at a size a test
holds; ``bench/tools/control.py`` reads it at the cell's own size."""

import pytest

import faults
from harness import check
from harness.control import readings

WORKLOADS = faults.cells(chips=1)


@pytest.mark.parametrize("seed", (3, 2**31 + 5, 77_000_000_001))
@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload, seed):
    cell = faults.tiny_cell(workload, num_nodes=3000, num_edges=200_000)
    got = readings(cell.config, seed, 1, cell.limits["control"])
    correct, _, _ = check.verdict(got, cell.limits)
    assert not correct, got


@pytest.mark.parametrize("workload", WORKLOADS)
def test_reference_against_itself_is_correct(workload):
    cell = faults.tiny_cell(workload, num_nodes=3000, num_edges=200_000)
    from harness import graphgen
    from harness.reference import Reference
    import numpy as np

    c = cell.config
    s, d = graphgen.base_graph(c["num_nodes"], c["num_edges"],
                               c["num_classes"], c["graph_seed"])
    ref = Reference(np.concatenate([s, d]), np.concatenate([d, s]),
                    c["num_nodes"], c["options"])
    y = graphgen.draw_labels(c["num_nodes"], c["num_classes"], c["labelled"],
                             9, 0)
    z = ref.embed(y, c["num_classes"])
    correct, _, _ = check.verdict([check.gaps(z.astype("float32"), z)],
                                  cell.limits)
    assert correct
