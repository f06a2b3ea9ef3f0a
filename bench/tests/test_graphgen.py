"""The benchmark's copy of the sampler draws what the repository's
``synth_like`` draws, and a seed changes ids and labels but not the degree
sequence (so not the compiled shapes)."""

import numpy as np

from harness import graphgen
from repro.graph.datasets import DatasetSpec, synth_like


def test_base_graph_is_synth_like():
    spec = DatasetSpec("t", 5000, 40_000, 7)
    ds = synth_like(spec, seed=3)
    s, d = graphgen.base_graph(5000, 40_000, 7, graph_seed=3)
    e = ds.edges.num_edges // 2
    assert np.array_equal(np.asarray(ds.edges.src)[:e], s)
    assert np.array_equal(np.asarray(ds.edges.dst)[:e], d)


def test_choice_is_rng_choice():
    p = np.random.default_rng(1).random(3000)
    p /= p.sum()
    a = np.random.default_rng(9).choice(3000, size=3_000_000, p=p)
    b = graphgen.choice(np.random.default_rng(9), 3000, 3_000_000, p)
    assert np.array_equal(a, b)


def test_seed_permutes_the_same_degrees():
    s, d = graphgen.base_graph(4000, 30_000, 5, graph_seed=0)
    degs = []
    for seed in (1, 2**31 + 3):
        s2, d2 = graphgen.relabel(s, d, 4000, seed)
        deg = np.bincount(np.concatenate([s2, d2]), minlength=4000)
        degs.append(np.sort(deg))
        assert not np.array_equal(s2, s)
    assert np.array_equal(degs[0], degs[1])


def test_labels_keep_the_labelled_count():
    y = graphgen.draw_labels(10_000, 47, 803, seed=5, fit=2)
    assert (y >= 0).sum() == 803 and y.max() < 47
    assert not np.array_equal(y, graphgen.draw_labels(10_000, 47, 803, 5, 3))
