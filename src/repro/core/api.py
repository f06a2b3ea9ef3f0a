"""Public API: the paper's technique as a first-class, composable module.

``GEEEmbedder`` is the single front door used by the examples, the LM
featurizer and the benchmarks.  It hides backend selection (the production
``sparse_jax`` path, the Pallas kernel path, the paper's SciPy path, the
dense oracle and the distributed multi-pod path) behind one object.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gee import GEEOptions, class_counts
from repro.core.incremental import Delta, DirtyRowTracker, IncrementalGEE
from repro.core.plan import GEEPlan, PreparedGraph
from repro.graph.containers import EdgeList


@dataclasses.dataclass
class GEEEmbedder:
    """Fit/transform-style wrapper around sparse GEE.

    backend: 'sparse_jax' (default), 'pallas', 'auto', 'chunked',
             'streamed_sharded', 'dense_jax', 'scipy', 'python_loop', or
             'distributed' (see ``docs/backends.md`` for the decision
             guide).  'streamed_sharded' streams windows across all
             devices (default mesh when ``mesh`` is None) and works for
             both in-memory and file-backed fits.
    local_backend: per-shard compute used by 'distributed' and
             'streamed_sharded' -- 'segment_sum' (default) or 'pallas'
             (ELL kernel per shard).

    In-memory graphs go through ``fit``/``fit_transform``; graphs on disk
    (any ``repro.graph.io`` format) go through ``fit_file`` /
    ``fit_transform_file``, which stream in bounded memory.

    >>> import numpy as np
    >>> emb = GEEEmbedder.from_arrays(          # two triangles + a bridge
    ...     src=np.array([0, 1, 0, 3, 4, 3, 2]),
    ...     dst=np.array([1, 2, 2, 4, 5, 5, 3]),
    ...     weight=None, labels=np.array([0, 0, 0, 1, 1, 1], np.int32),
    ...     num_classes=2)
    >>> emb.transform().shape
    (6, 2)
    >>> np.asarray(emb.predict()).tolist()      # recovers the communities
    [0, 0, 0, 1, 1, 1]
    """

    num_classes: int
    options: GEEOptions = GEEOptions(laplacian=True, diag_aug=True,
                                     correlation=True)
    backend: str = "sparse_jax"
    mesh: Optional[object] = None            # required for 'distributed'
    mesh_axes: tuple = ("data",)
    local_backend: str = "segment_sum"       # 'distributed' only
    chunk_edges: Optional[int] = None        # 'chunked' / file-backed only
    # streaming backends: windows staged ahead by background threads
    # (None: REPRO_GEE_PREFETCH_WINDOWS or 2; 0: synchronous reads)
    prefetch_windows: Optional[int] = None

    _edges: Optional[EdgeList] = dataclasses.field(default=None, repr=False)
    _prepared: Optional[PreparedGraph] = dataclasses.field(default=None,
                                                          repr=False)
    _chunked: Optional[object] = dataclasses.field(default=None, repr=False)
    _labels: Optional[jax.Array] = dataclasses.field(default=None, repr=False)
    _z: Optional[jax.Array] = dataclasses.field(default=None, repr=False)
    _plan: Optional[GEEPlan] = dataclasses.field(default=None, repr=False)
    _inc: Optional[IncrementalGEE] = dataclasses.field(default=None,
                                                       repr=False)
    _index: Optional[object] = dataclasses.field(default=None, repr=False)
    _index_tracker: Optional[DirtyRowTracker] = dataclasses.field(
        default=None, repr=False)

    # -- construction helpers ------------------------------------------------
    @staticmethod
    def from_arrays(src, dst, weight, labels, num_classes: int,
                    num_nodes: int | None = None, undirected: bool = True,
                    **kw) -> "GEEEmbedder":
        prepared = PreparedGraph.from_arrays(src, dst, weight,
                                             num_nodes=num_nodes,
                                             undirected=undirected)
        emb = GEEEmbedder(num_classes=num_classes, **kw)
        return emb.fit(prepared, labels)

    # -- sklearn-ish surface -------------------------------------------------
    def fit(self, edges: "EdgeList | PreparedGraph", labels) -> "GEEEmbedder":
        """Fit an in-memory graph.  Passing a ``PreparedGraph`` (instead
        of a bare ``EdgeList``) carries its memoized prep artifacts into
        this embedder -- refits, backend switches and option sweeps then
        share them."""
        self._prepared = PreparedGraph.wrap(edges)
        self._edges = self._prepared.base
        self._chunked = None
        self._labels = jnp.asarray(labels, jnp.int32)
        self._z = None
        self._plan = None
        self._inc = None
        self._reset_index()
        return self

    def fit_file(self, path: str, labels=None, **open_kw) -> "GEEEmbedder":
        """Fit from an on-disk edge list without materializing it.

        ``path`` is any ``repro.graph.io`` format (``.geeb`` memory-maps;
        text converts to a mmap sidecar once).  ``labels=None`` reads the
        ``<path>.labels.npy`` sidecar.  ``open_kw`` is forwarded to
        :func:`repro.graph.io.open_edge_list` (``index_base``,
        ``num_nodes``, ``undirected``, ...).  ``transform`` then streams
        the two-pass chunked algorithm whatever ``backend`` says.
        """
        from repro.graph.io import (DEFAULT_CHUNK_EDGES, load_labels,
                                    open_edge_list)

        chunk = self.chunk_edges or DEFAULT_CHUNK_EDGES
        self._chunked = open_edge_list(path, chunk_edges=chunk, **open_kw)
        if labels is None:
            labels = load_labels(path)
            if labels is None:
                raise ValueError(
                    f"no labels given and no sidecar {path}.labels.npy")
        self._edges = None
        self._prepared = None
        self._labels = jnp.asarray(labels, jnp.int32)
        self._z = None
        self._plan = None
        self._inc = None
        self._reset_index()
        return self

    def fit_transform_file(self, path: str, labels=None,
                           **open_kw) -> jax.Array:
        """``fit_file`` + ``transform`` in one call (bounded memory)."""
        return self.fit_file(path, labels, **open_kw).transform()

    def partial_fit(self, delta: Delta) -> "GEEEmbedder":
        """Apply an ``EdgeDelta`` / ``LabelDelta`` (or a sequence of them)
        in O(|delta| + affected-row edges) instead of refitting O(E).

        The first call promotes the fitted graph into an ``IncrementalGEE``
        accumulator; from then on ``transform`` serves from its cached Z
        (numerically the ``sparse_jax`` contract, whatever ``backend`` says).
        """
        if self._edges is None:
            if self._chunked is not None:
                raise RuntimeError(
                    "partial_fit needs the in-memory path: file-backed fits "
                    "stream from disk and keep no live adjacency.  "
                    "fit(chunked.to_edge_list(), labels) first if the graph "
                    "fits in memory.")
            raise RuntimeError("call fit() first")
        if self._inc is None:
            self._inc = IncrementalGEE.from_graph(
                self._edges, self._labels, self.num_classes, self.options)
            # Track invalidations so a live similarity index repairs its
            # buckets instead of rebuilding (see build_index / neighbors).
            self._index_tracker = DirtyRowTracker(self._inc.n)
            self._inc.add_dirty_listener(self._index_tracker)
        self._inc.apply(delta)
        self._labels = jnp.asarray(self._inc.labels)
        self._z = None
        return self

    @property
    def incremental(self) -> Optional[IncrementalGEE]:
        """The live streaming state (None until ``partial_fit`` is called)."""
        return self._inc

    @property
    def plan(self) -> Optional[GEEPlan]:
        """The plan the last in-memory ``transform`` ran (None before it,
        and for file-backed, 'distributed' and 'streamed_sharded' fits):
        ``plan.backend`` / ``plan.fused`` say what ``backend`` resolved to."""
        return self._plan

    @property
    def prepared(self) -> Optional[PreparedGraph]:
        """The fitted graph's memoized prep artifacts (None for
        file-backed fits).  Reuse it across embedders/sweeps:
        ``GEEEmbedder(...).fit(other.prepared, labels)``."""
        return self._prepared

    def current_edges(self) -> EdgeList:
        """The graph actually embedded: the mutated one once streaming.

        For file-backed fits this *materializes* the on-disk list (and
        symmetrizes undirected storage) -- fine for inspection, contrary
        to the point at out-of-core scale.
        """
        if self._inc is not None:
            return self._inc.to_edge_list()
        if self._chunked is not None:
            return self._chunked.to_edge_list()
        if self._edges is None:
            raise RuntimeError("call fit() first")
        return self._edges

    def _num_nodes(self) -> int:
        if self._chunked is not None:
            return self._chunked.num_nodes
        return self._edges.num_nodes

    def transform(self) -> jax.Array:
        if self._edges is None and self._chunked is None:
            raise RuntimeError("call fit() first")
        if self._inc is not None:
            # Re-upload host Z only when rows are actually stale, so repeat
            # reads between deltas serve the cached device copy for free.
            if self._z is None or self._inc.num_pending_rows:
                self._z = jnp.asarray(self._inc.embedding())
            return self._z
        if self._z is None:
            self._z = self._compute()
        return self._z

    def fit_transform(self, edges: EdgeList, labels) -> jax.Array:
        return self.fit(edges, labels).transform()

    # -- classification on top of the embedding ------------------------------
    def class_means(self) -> jax.Array:
        """Per-class mean of Z over labeled vertices, [K, K].

        Empty classes (no labeled member, e.g. an over-provisioned
        ``num_classes``) get ``inf`` rows -- the same guard as
        ``repro.core.ensemble._assign_nearest_centroid`` -- so ``predict``
        can never assign a vertex to a class with zero members (an origin
        row would win every small-norm vertex, isolated ones above all).
        """
        z = self.transform()
        z = z[: self._num_nodes()]
        onehot = jax.nn.one_hot(self._labels, self.num_classes, dtype=z.dtype)
        counts = onehot.sum(0)
        means = (onehot.T @ z) / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where((counts > 0)[:, None], means, jnp.inf)

    def predict(self, rows: jax.Array | None = None) -> jax.Array:
        """Nearest-class-mean vertex classification (the standard GEE
        downstream evaluation).  ``rows`` restricts to a vertex subset:
        any array-like of ids, single-element and scalar included (always
        returns a 1-D label array)."""
        z = self.transform()[: self._num_nodes()]
        if rows is not None:
            z = z[jnp.atleast_1d(jnp.asarray(rows))]
        means = self.class_means()
        d2 = jnp.sum((z[:, None, :] - means[None, :, :]) ** 2, axis=-1)
        d2 = jnp.where(jnp.isnan(d2), jnp.inf, d2)   # inf-mean arithmetic
        return jnp.argmin(d2, axis=-1).astype(jnp.int32)

    # -- similarity retrieval on top of the embedding ------------------------
    def build_index(self, *, metric: str = "l2", nprobe: int | None = None,
                    pad_multiple: int | None = None, impl: str = "auto"):
        """Build (and cache) a vertex-similarity index over the embedding.

        Returns a :class:`repro.search.index.ClassPartitionedIndex` whose
        coarse cells are this embedder's class structure.  Works for every
        backend, file-backed fits included (it indexes ``transform()``'s
        output).  After ``partial_fit`` deltas the cached index is
        *repaired* in place on the next :meth:`neighbors` call -- stale
        rows move between buckets; no rebuild.
        """
        from repro.search.index import (DEFAULT_PAD_MULTIPLE,
                                        ClassPartitionedIndex)

        z = self.transform()[: self._num_nodes()]
        self._index = ClassPartitionedIndex.build(
            z, np.asarray(self._labels), self.num_classes, metric=metric,
            nprobe=nprobe,
            pad_multiple=pad_multiple or DEFAULT_PAD_MULTIPLE, impl=impl)
        if self._index_tracker is not None:
            self._index_tracker.drain()   # fresh index == already repaired
        return self._index

    def neighbors(self, query_rows=None, k: int = 10, *, queries=None,
                  nprobe: int | None = None, brute_force: bool = False):
        """Top-``k`` most similar vertices per query.

        ``query_rows`` queries by vertex id (each vertex is its own best
        hit); ``queries`` passes explicit [Q, K] vectors instead.  Builds
        the index on first use and repairs it after ``partial_fit`` deltas.
        Returns ``(ids [Q, k] int32, scores [Q, k] f32)``.
        """
        if self._index is None:
            self.build_index()
        self._repair_index()
        if queries is not None:
            return self._index.search(queries, k, nprobe=nprobe,
                                      brute_force=brute_force)
        if query_rows is None:
            raise ValueError("pass query_rows (vertex ids) or queries "
                             "(explicit vectors)")
        return self._index.search_rows(np.asarray(query_rows), k,
                                       nprobe=nprobe,
                                       brute_force=brute_force)

    @property
    def index(self):
        """The cached similarity index (None until ``build_index`` /
        ``neighbors``)."""
        return self._index

    def _reset_index(self):
        self._index = None
        self._index_tracker = None   # a new graph gets a new tracker

    def _repair_index(self):
        """Fold ``partial_fit`` invalidations into the cached index."""
        if self._index is None or self._index_tracker is None \
                or not self._index_tracker.pending:
            return
        rows = self._index_tracker.drain()
        z = self.transform()[: self._num_nodes()]
        self._index.update_rows(rows, z[jnp.asarray(rows)])

    # -- internals -----------------------------------------------------------
    def _compute(self) -> jax.Array:
        labels = self._labels
        if self.backend == "streamed_sharded":
            from repro.core.fold import gee_streamed_sharded
            from repro.graph.io import DEFAULT_CHUNK_EDGES

            source = (self._chunked if self._chunked is not None
                      else self._prepared.chunked(
                          self.chunk_edges or DEFAULT_CHUNK_EDGES))
            return gee_streamed_sharded(source, labels, self.num_classes,
                                        self.options, mesh=self.mesh,
                                        axes=self.mesh_axes,
                                        local_backend=self.local_backend,
                                        prefetch_windows=self.prefetch_windows)
        if self._chunked is not None:
            from repro.core.chunked import gee_chunked

            return gee_chunked(self._chunked, labels, self.num_classes,
                               self.options,
                               prefetch_windows=self.prefetch_windows)
        if self.backend == "distributed":
            from repro.core.distributed import gee_distributed

            if self.mesh is None:
                raise ValueError("distributed backend needs a mesh")
            z = gee_distributed(self._prepared, labels, self.num_classes,
                                self.options, mesh=self.mesh,
                                axes=self.mesh_axes,
                                local_backend=self.local_backend)
            return z[: self._edges.num_nodes]
        # Everything else is one plan over the shared PreparedGraph, so a
        # refit / option change / backend switch reuses all prep artifacts
        # (the chunked route reuses its cached chunk manifest too).
        self._plan = GEEPlan.build(
            self._prepared, self.num_classes, self.options,
            backend=self.backend, chunk_edges=self.chunk_edges,
            prefetch_windows=self.prefetch_windows)
        return self._plan.execute(labels)


def node_features(edges: EdgeList, labels, num_classes: int,
                  options: GEEOptions = GEEOptions(laplacian=True,
                                                   diag_aug=True,
                                                   correlation=True),
                  backend: str = "sparse_jax") -> jax.Array:
    """One-call functional form: graph + labels -> [N, K] features."""
    return GEEEmbedder(num_classes=num_classes, options=options,
                       backend=backend).fit_transform(edges, labels)
