"""Registry of the benchmark graphs: the paper's Table 2 and the OGB
node-property datasets the repository is deployed on.

The container has no network access, so the six Network-Repository datasets
are regenerated as *synthetic stand-ins with matching statistics*: the same
node count, edge count, class count and (hence) edge density as Table 2.  A
degree-skewed configuration-model-like sampler makes the degree profile
heavy-tailed, as in the real citation/protein graphs, so the sparse-vs-dense
runtime comparison (the paper's actual claim) exercises the same regime.

``OGB`` holds OGB node-property datasets (Hu et al., NeurIPS 2020,
arXiv:2005.00687) by the same recipe, plus the size of the train split:
only ``labelled`` vertices carry a class, the rest are ``-1`` (unknown),
as in semi-supervised vertex classification.

This substitution is recorded in DESIGN.md; the paper's evaluation is about
*runtime vs. sparsity*, which depends on (N, E, K) and not on ground-truth
semantics.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict

import numpy as np

from repro.graph.containers import EdgeList, edge_list_from_numpy


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    name: str
    num_nodes: int
    num_edges: int     # undirected edge count, as in paper Table 2
    num_classes: int
    labelled: int | None = None   # vertices with a known label; None: all

    @property
    def num_labelled(self) -> int:
        return self.num_nodes if self.labelled is None else self.labelled

    @property
    def density(self) -> float:
        n, e = self.num_nodes, self.num_edges
        return 2.0 * e / (n * (n - 1))


# Paper Table 2 (node/edge counts as printed; Tables 3-4 use slightly
# different CiteSeer counts -- we follow Table 2).
TABLE2: Dict[str, DatasetSpec] = {
    "citeseer": DatasetSpec("citeseer", 3_327, 4_732, 6),
    "cora": DatasetSpec("cora", 2_708, 5_429, 7),
    "proteins-all": DatasetSpec("proteins-all", 43_471, 162_088, 3),
    "pubmed": DatasetSpec("pubmed", 19_717, 44_338, 3),
    "cl-100k-1d8-l9": DatasetSpec("cl-100k-1d8-l9", 92_482, 373_986, 9),
    "cl-100k-1d8-l5": DatasetSpec("cl-100k-1d8-l5", 92_482, 10_000_000, 5),
}

# OGB node-property datasets (arXiv:2005.00687): vertices, undirected edges,
# classes, and the train split's size as the labelled count.
OGB: Dict[str, DatasetSpec] = {
    "ogbn-products": DatasetSpec("ogbn-products", 2_449_029, 61_859_140, 47,
                                 labelled=196_615),
}

REGISTRY: Dict[str, DatasetSpec] = {**TABLE2, **OGB}


@dataclasses.dataclass(frozen=True)
class GraphDataset:
    spec: DatasetSpec
    edges: EdgeList          # directed/symmetrized
    labels: np.ndarray       # [N] int32


def _skewed_endpoint_probs(rng: np.random.Generator, n: int) -> np.ndarray:
    """Zipf-ish stub weights for preferential endpoints."""
    w = 1.0 / (1.0 + np.arange(n, dtype=np.float64)) ** 0.5
    rng.shuffle(w)
    return w / w.sum()


def _sample_loop_free_pairs(rng: np.random.Generator, n: int, count: int,
                            p: np.ndarray):
    """``count`` endpoint pairs drawn from ``p``, self loops rerolled.

    The reroll offsets from *src* by 1..n-1, so the new endpoint can never
    be src again (offsetting from the old dst could land back on src).
    Shared by ``synth_like`` (one full-size draw) and ``synth_to_disk``
    (one draw per on-disk chunk), so the two samplers cannot diverge.
    """
    src = rng.choice(n, size=count, p=p).astype(np.int32)
    dst = rng.choice(n, size=count, p=p).astype(np.int32)
    loops = src == dst
    dst[loops] = (src[loops] + 1 + rng.integers(0, n - 1, loops.sum())) % n
    assert not np.any(src == dst), "self loops survived the reroll"
    return src, dst


def _unlabel(labels: np.ndarray, spec: DatasetSpec, seed: int) -> np.ndarray:
    """Keep the classes of ``spec.labelled`` vertices drawn from a stream
    of their own, and mark the rest -1.  Drawn after the edges and apart
    from the graph's stream, so a fully labelled spec's draw is untouched."""
    if spec.num_labelled >= spec.num_nodes:
        return labels
    rng = np.random.default_rng([seed, 1])
    keep = rng.choice(spec.num_nodes, size=spec.num_labelled, replace=False)
    out = np.full_like(labels, -1)
    out[keep] = labels[keep]
    return out


def synth_like(spec: DatasetSpec, seed: int = 0,
               pad_to: int | None = None) -> GraphDataset:
    """Sample a graph matching (N, E, K) with a heavy-tailed degree
    profile; only ``spec.labelled`` vertices keep a class."""
    rng = np.random.default_rng(seed)
    n, e, k = spec.num_nodes, spec.num_edges, spec.num_classes
    labels = rng.integers(0, k, size=n).astype(np.int32)
    src, dst = _sample_loop_free_pairs(rng, n, e,
                                       _skewed_endpoint_probs(rng, n))
    labels = _unlabel(labels, spec, seed)
    s = np.concatenate([src, dst])
    d = np.concatenate([dst, src])
    edges = edge_list_from_numpy(s, d, None, n, pad_to=pad_to)
    return GraphDataset(spec=spec, edges=edges, labels=labels)


def _looks_like_path(name: str) -> bool:
    from repro.graph.io import TEXT_SUFFIXES

    suffix = os.path.splitext(name)[1].lower()
    return (os.path.sep in name or os.path.exists(name)
            or suffix in (".geeb", ".npz") or suffix in TEXT_SUFFIXES)


def load_file(path: str, pad_to: int | None = None, **open_kw) -> GraphDataset:
    """Materialize an on-disk edge list (any ``repro.graph.io`` format) as
    a ``GraphDataset``: undirected storage is symmetrized, labels come
    from the ``<path>.labels.npy`` sidecar (all ``-1`` = unknown when
    absent).  For graphs too large to materialize, stream them instead:
    ``repro.core.chunked.gee_chunked_from_file`` /
    ``GEEEmbedder.fit_file``."""
    from repro.graph.io import load_labels, open_edge_list

    chunked = open_edge_list(path, **open_kw)
    edges = chunked.to_edge_list(pad_to=pad_to)
    labels = load_labels(path)
    if labels is None:
        labels = np.full(chunked.num_nodes, -1, np.int32)
    k = int(labels.max()) + 1 if labels.size and labels.max() >= 0 else 1
    # Directed storage is assumed to follow the repo convention (each
    # undirected edge stored as both directions, cf. ``symmetrize``), so
    # E//2 is its undirected count; genuinely asymmetric lists will see
    # this metadata as approximate.
    und_edges = (chunked.num_edges if chunked.undirected
                 else chunked.num_edges // 2)
    spec = DatasetSpec(
        name=os.path.splitext(os.path.basename(path))[0],
        num_nodes=chunked.num_nodes, num_edges=und_edges, num_classes=k,
        labelled=int(np.count_nonzero(labels >= 0)))
    return GraphDataset(spec=spec, edges=edges, labels=labels)


def load(name: str, seed: int = 0, pad_to: int | None = None) -> GraphDataset:
    """Resolve a registry name (Table 2 or OGB) *or* an edge-file path.

    Registry names sample a synthetic stand-in (see module docstring) and
    always win -- a stray file that happens to be called ``cora`` cannot
    shadow the registry.  Anything else that looks like a path routes
    through the ``repro.graph.io`` layer (``load_file``).
    """
    key = name.lower()
    if key in REGISTRY:
        return synth_like(REGISTRY[key], seed=seed, pad_to=pad_to)
    if _looks_like_path(name):
        return load_file(name, pad_to=pad_to)
    raise KeyError(f"unknown dataset {name!r} (not a registry name, and "
                   f"not an edge-file path); available: {sorted(REGISTRY)}")


def synth_to_disk(spec: DatasetSpec, path: str, seed: int = 0,
                  chunk_edges: int = 1 << 20) -> str:
    """Stream a ``synth_like``-style graph straight to disk.

    Generates the same degree-skewed sampler output chunk-by-chunk into a
    preallocated ``.geeb`` (or streamed text) file, so multi-million-edge
    benchmark fixtures never hold the full edge list in host memory:
    peak usage is O(N + chunk_edges).  The file stores *one entry per
    undirected edge* (``undirected=True``); the chunked pipeline folds
    both directions on the fly, and ``load``/``load_file`` symmetrize on
    materialization.  Labels land in the ``<path>.labels.npy`` sidecar.
    """
    from repro.graph.io import (TEXT_SUFFIXES, BinaryEdgeWriter,
                                save_labels)

    suffix = os.path.splitext(path)[1].lower()
    if suffix not in (".geeb",) + TEXT_SUFFIXES:
        raise ValueError(f"synth_to_disk streams to .geeb or text, "
                         f"got {suffix!r}")
    rng = np.random.default_rng(seed)
    n, e, k = spec.num_nodes, spec.num_edges, spec.num_classes
    labels = rng.integers(0, k, size=n).astype(np.int32)
    p = _skewed_endpoint_probs(rng, n)

    def chunks():
        left = e
        while left > 0:
            c = min(left, chunk_edges)
            yield _sample_loop_free_pairs(rng, n, c, p)
            left -= c

    if suffix == ".geeb":
        with BinaryEdgeWriter(path, n, e, undirected=True) as writer:
            for src, dst in chunks():
                writer.append(src, dst)
    else:
        with open(path, "w") as f:
            f.write(f"# nodes {n} edges {e} undirected 1\n")
            for src, dst in chunks():
                f.writelines(f"{s} {d}\n" for s, d in zip(src, dst))
    save_labels(path, _unlabel(labels, spec, seed))
    return path
