"""The graph and the labels a run works on, made from the configuration and
``--seed`` alone.

The sampler is a copy of the repository's degree-skewed stand-in sampler
(``repro.graph.datasets.synth_like``: Zipf-like stub weights ``1/sqrt(1+i)``
in shuffled order, endpoints drawn from them, self loops rerolled), kept
here so that a change to the program cannot move the yardstick.

The edge multiset is drawn once per configuration from its fixed
``graph_seed``; ``--seed`` relabels the vertices with a random permutation
and draws every fit's labels.  So every seed gives the same degree
sequence in another order: the same ELL bucket shapes, the same windows,
the same compiled programs, while the vertex ids, the labels and the
answers all differ from seed to seed.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def skewed_endpoint_probs(rng: np.random.Generator, n: int) -> np.ndarray:
    w = 1.0 / (1.0 + np.arange(n, dtype=np.float64)) ** 0.5
    rng.shuffle(w)
    return w / w.sum()


def choice(rng: np.random.Generator, n: int, count: int,
           p: np.ndarray) -> np.ndarray:
    """``rng.choice(n, size=count, p=p)``, draw for draw, with the inverse
    CDF lookup split over threads (``searchsorted`` releases the GIL):
    the same uniforms, the same CDF, the same answers, several times
    sooner on a graph of millions of vertices."""
    cdf = p.cumsum()
    cdf /= cdf[-1]
    u = rng.random(count)
    workers = min(os.cpu_count() or 1, 16)
    step = max(1 << 20, -(-count // workers))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        parts = list(ex.map(
            lambda lo: cdf.searchsorted(u[lo:lo + step], side="right"),
            range(0, count, step)))
    return np.concatenate(parts) if parts else np.zeros(0, np.int64)


def sample_loop_free_pairs(rng: np.random.Generator, n: int, count: int,
                           p: np.ndarray):
    src = choice(rng, n, count, p).astype(np.int32)
    dst = choice(rng, n, count, p).astype(np.int32)
    loops = src == dst
    dst[loops] = (src[loops] + 1 + rng.integers(0, n - 1, loops.sum())) % n
    if np.any(src == dst):
        raise RuntimeError("self loops survived the reroll")
    return src, dst


def base_graph(num_nodes: int, num_edges: int, num_classes: int,
               graph_seed: int):
    """(src, dst) of the undirected base graph, one entry per edge: the
    draws of ``synth_like(spec, seed=graph_seed)`` in the same order."""
    rng = np.random.default_rng(graph_seed)
    rng.integers(0, num_classes, size=num_nodes)   # synth_like's label draw
    p = skewed_endpoint_probs(rng, num_nodes)
    return sample_loop_free_pairs(rng, num_nodes, num_edges, p)


def relabel(src: np.ndarray, dst: np.ndarray, num_nodes: int, seed: int):
    """The base graph with its vertices renamed by a permutation drawn
    from ``seed``."""
    perm = np.random.default_rng([seed, 0]).permutation(num_nodes)
    perm = perm.astype(np.int32)
    return perm[src], perm[dst]


def draw_labels(num_nodes: int, num_classes: int, labelled: int, seed: int,
                fit: int) -> np.ndarray:
    """Labels of fit ``fit``: ``labelled`` vertices, classes uniform, the
    rest -1 (unknown)."""
    rng = np.random.default_rng([seed, 1, fit])
    if labelled >= num_nodes:
        return rng.integers(0, num_classes, size=num_nodes).astype(np.int32)
    labels = np.full(num_nodes, -1, np.int32)
    rows = rng.choice(num_nodes, size=labelled, replace=False)
    labels[rows] = rng.integers(0, num_classes, size=labelled)
    return labels
