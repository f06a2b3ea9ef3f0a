"""Class-partitioned ANN index over GEE embeddings.

GEE already *is* a coarse quantizer: the embedding places every vertex near
the mean of its class (the One-Hot GEE view -- Z rows are per-class
neighborhood profiles), so the natural IVF cell structure is the class
structure itself.  ``ClassPartitionedIndex`` buckets vertices by nearest
class mean and answers k-nearest-vertex queries by scanning only the
``nprobe`` nearest cells:

  build    class means from the labels (empty classes are inactive cells),
           every vertex assigned to its nearest *active* mean -- including
           unknown-label (-1) vertices, which have no class of their own.
  layout   one [C, B] int32 cell table, rows padded with -1 to a common
           bucket capacity B (a ``pad_multiple`` multiple).  One static
           shape for the whole table means the jitted query path traces
           once and survives incremental repairs that don't overflow B.
  query    probe scores vs the C centroids (masked pairwise kernel), take
           the top ``nprobe`` cells, gather their member rows, score them
           with the batched masked kernel, top-k.  ``nprobe == num_cells``
           scans every bucket and is exact by construction (each vertex
           lives in exactly one bucket); ``brute_force=True`` bypasses the
           cells entirely and scores all N rows.
  repair   ``update_rows`` moves re-embedded vertices between buckets in
           O(|rows|) host work (swap-with-last removal, append insertion,
           capacity growth by ``pad_multiple`` when a bucket fills) -- no
           rebuild, no re-assignment of untouched vertices.  The serving
           layer (``repro.search.service``) drives this off
           ``IncrementalGEE`` dirty-row notifications.

Scoring runs through ``repro.kernels.topk_score`` (Pallas on TPU, pure-JAX
fallback elsewhere); both metrics the GEE literature uses for vertex
nomination are supported (``l2``, ``cosine`` -- with the correlation option
on, Z rows are unit-norm and the two rank identically).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.topk_score import (fused_topk_enabled, pairwise_scores,
                                      scored_topk, scored_topk_gathered)
from repro.obs import metrics as obs_metrics

DEFAULT_PAD_MULTIPLE = 128
# device bytes one IVF query batch may gather: [Q, nprobe * B, K] candidate
# rows, lane-padded to 128 on the Pallas path; larger batches are split
GATHER_BUDGET_BYTES = 1 << 30


def index_stats_view(builds: int = 0) -> "obs_metrics.StatsView":
    """The index's registry-backed stats dict (one scope per instance);
    shared with ``repro.serve.snapshot.restore_index`` so a restored
    index counts into the same metric names as a built one."""
    return obs_metrics.get_registry().stats_view(
        "gee.index", {"builds": builds, "queries": 0,
                      "brute_force_queries": 0, "cells_probed": 0,
                      "candidates_scored": 0, "repaired_rows": 0,
                      "bucket_moves": 0, "table_grows": 0})


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def default_nprobe(num_cells: int) -> int:
    """ceil(sqrt(C)), the classic IVF default, never below 1."""
    return max(1, int(np.ceil(np.sqrt(max(num_cells, 1)))))


@dataclasses.dataclass
class ClassPartitionedIndex:
    """IVF-style vertex index whose coarse cells are GEE class means.

    Build with :meth:`build`; query with :meth:`search` /
    :meth:`search_rows`; keep fresh with :meth:`update_rows`.
    """

    metric: str
    nprobe: int
    pad_multiple: int
    impl: str
    _z: jax.Array                    # [N, K] database embeddings (device)
    _centroids: jax.Array            # [C, K] cell centers (device)
    _active: np.ndarray              # [C] bool: cell has a centroid
    _table: np.ndarray               # [C, B] int32 member ids, -1 = empty
    _cell_len: np.ndarray            # [C] int64 live entries per cell
    _row_cell: np.ndarray            # [N] int32 cell of each vertex
    _row_slot: np.ndarray            # [N] int64 slot within its cell row
    _table_dev: jax.Array | None     # device copy of _table (lazy refresh)
    stats: dict

    # -- construction --------------------------------------------------------
    @classmethod
    def build(cls, z, labels, num_classes: int, *, metric: str = "l2",
              nprobe: int | None = None,
              pad_multiple: int = DEFAULT_PAD_MULTIPLE,
              impl: str = "auto") -> "ClassPartitionedIndex":
        """Index ``z`` [N, K] using the class structure of ``labels``.

        ``labels`` may contain ``-1`` (unknown): such vertices contribute to
        no centroid but are still indexed (assigned to their nearest active
        cell).  If *every* label is unknown the index degenerates to a
        single cell holding everything (= brute force).
        """
        z = jnp.asarray(z, jnp.float32)
        n, dim = z.shape
        y = np.asarray(labels, np.int64)
        if y.shape[0] != n:
            raise ValueError(f"labels shape {y.shape} != num rows {n}")
        c = int(num_classes)

        valid = y >= 0
        counts = np.bincount(y[valid], minlength=c).astype(np.float64)
        active = counts > 0
        if active.any():
            seg = jnp.where(jnp.asarray(valid), jnp.asarray(y, jnp.int32), c)
            sums = jax.ops.segment_sum(z, seg, num_segments=c + 1)[:c]
            centroids = sums / jnp.maximum(jnp.asarray(counts, jnp.float32),
                                           1.0)[:, None]
        else:
            # all-unknown labels: one catch-all cell at the global mean
            active = np.zeros(c, bool)
            active[0] = True
            centroids = jnp.zeros((c, dim), jnp.float32)
            centroids = centroids.at[0].set(jnp.mean(z, axis=0))
        centroids = jnp.where(jnp.asarray(active)[:, None], centroids, 0.0)

        # Assign every vertex to its nearest active centroid (same metric
        # the queries will use, through the same kernel).
        cscores = pairwise_scores(z, centroids,
                                  jnp.asarray(active, jnp.float32),
                                  metric=metric, impl=impl)
        assign = np.asarray(jnp.argmax(cscores, axis=1), np.int64)

        cell_len = np.bincount(assign, minlength=c).astype(np.int64)
        cap = _ceil_to(max(int(cell_len.max()) if n else 1, 1),
                       max(int(pad_multiple), 1))
        table = np.full((c, cap), -1, np.int32)
        order = np.argsort(assign, kind="stable")
        starts = np.zeros(c, np.int64)
        np.cumsum(cell_len[:-1], out=starts[1:])
        slot = np.arange(n, dtype=np.int64) - starts[assign[order]]
        table[assign[order], slot] = order.astype(np.int32)
        row_slot = np.empty(n, np.int64)
        row_slot[order] = slot

        self = cls(
            metric=metric,
            nprobe=int(nprobe) if nprobe is not None
            else default_nprobe(int(active.sum())),
            pad_multiple=int(pad_multiple), impl=impl,
            _z=z, _centroids=centroids, _active=active,
            _table=table, _cell_len=cell_len,
            _row_cell=assign.astype(np.int32), _row_slot=row_slot,
            _table_dev=None,
            stats=index_stats_view(builds=1),
        )
        return self

    # -- introspection -------------------------------------------------------
    @property
    def num_points(self) -> int:
        return int(self._z.shape[0])

    @property
    def dim(self) -> int:
        return int(self._z.shape[1])

    @property
    def num_cells(self) -> int:
        """Active cells (classes with at least one labeled member)."""
        return int(self._active.sum())

    @property
    def bucket_capacity(self) -> int:
        return int(self._table.shape[1])

    @property
    def z(self) -> jax.Array:
        """The indexed embeddings (device, [N, K]); kept current by
        ``update_rows``."""
        return self._z

    def padding_fraction(self) -> float:
        """Wasted table slots / total (the jit-stability cost)."""
        total = self._table.size
        return 1.0 - float(self._cell_len.sum()) / max(total, 1)

    # -- queries -------------------------------------------------------------
    def _table_device(self) -> jax.Array:
        if self._table_dev is None:
            self._table_dev = jnp.asarray(self._table)
        return self._table_dev

    def search(self, queries, k: int = 10, *, nprobe: int | None = None,
               brute_force: bool = False) -> tuple[jax.Array, jax.Array]:
        """Top-``k`` database rows for each query vector.

        ``queries``: [Q, K] (or a single [K] vector).  Returns
        ``(ids [Q, k] int32, scores [Q, k] f32)``; ``ids == -1`` marks
        slots with fewer than k reachable candidates.  ``nprobe`` overrides
        the index default for this call; ``nprobe >= num_cells`` (or
        ``brute_force=True``) gives exact results.
        """
        queries = jnp.asarray(queries, jnp.float32)
        squeeze = queries.ndim == 1
        if squeeze:
            queries = queries[None, :]
        if queries.shape[1] != self.dim:
            raise ValueError(f"query dim {queries.shape[1]} != index dim "
                             f"{self.dim}")
        self.stats["queries"] += int(queries.shape[0])
        p = self.nprobe if nprobe is None else int(nprobe)
        p = max(1, min(p, int(self._active.shape[0])))
        fused = fused_topk_enabled(self.impl)
        if brute_force:
            self.stats["brute_force_queries"] += int(queries.shape[0])
            ids, scores = _exact_search(queries, self._z, k=int(k),
                                        metric=self.metric, impl=self.impl,
                                        fused=fused)
        else:
            self.stats["cells_probed"] += int(queries.shape[0]) * p
            self.stats["candidates_scored"] += (int(queries.shape[0]) * p
                                                * self.bucket_capacity)
            ids, scores = self._ivf_batches(queries, int(k), p, fused)
        if squeeze:
            return ids[0], scores[0]
        return ids, scores

    def _ivf_batches(self, queries, k: int, nprobe: int, fused: bool):
        """IVF search in query batches whose gathered candidates fit
        ``GATHER_BUDGET_BYTES``; every batch but a lone short one has the
        same (power-of-two) height, so the search traces at most twice."""
        q = int(queries.shape[0])
        per_query = nprobe * self.bucket_capacity * _ceil_to(self.dim, 128) * 4
        batch = max(1, GATHER_BUDGET_BYTES // per_query)
        batch = 1 << (batch.bit_length() - 1)           # power of two
        run = functools.partial(
            _ivf_search, z=self._z, centroids=self._centroids,
            active=jnp.asarray(self._active, jnp.float32),
            table=self._table_device(), k=k, nprobe=nprobe,
            metric=self.metric, impl=self.impl, fused=fused)
        if q <= batch:
            return run(queries)
        parts = []
        for lo in range(0, q, batch):
            chunk = queries[lo:lo + batch]
            short = batch - chunk.shape[0]
            if short:                                   # pad the tail batch
                chunk = jnp.pad(chunk, ((0, short), (0, 0)))
            ids, scores = run(chunk)
            parts.append((ids[:batch - short], scores[:batch - short]))
        return (jnp.concatenate([i for i, _ in parts]),
                jnp.concatenate([s for _, s in parts]))

    def search_rows(self, rows, k: int = 10, *, nprobe: int | None = None,
                    brute_force: bool = False) -> tuple[jax.Array, jax.Array]:
        """Like :meth:`search` with the queries taken from the index itself
        (vertex-id queries).  Each vertex is its own best match under both
        metrics; callers wanting strict neighbors drop the self hit."""
        rows = jnp.asarray(rows, jnp.int32)
        return self.search(self._z[rows], k, nprobe=nprobe,
                           brute_force=brute_force)

    # -- incremental repair --------------------------------------------------
    def update_rows(self, rows, z_rows) -> int:
        """Re-embed ``rows`` with ``z_rows`` and repair their buckets.

        O(|rows|) host bookkeeping + one device row update; centroids stay
        fixed (they are the *coarse* structure -- repair moves members, a
        full :meth:`build` re-derives cells).  Returns the number of rows
        that changed buckets.
        """
        rows = np.asarray(rows, np.int64).reshape(-1)
        if rows.size == 0:
            return 0
        z_rows = jnp.asarray(z_rows, jnp.float32).reshape(rows.size, self.dim)
        self._z = self._z.at[jnp.asarray(rows)].set(z_rows)

        cscores = pairwise_scores(z_rows, self._centroids,
                                  jnp.asarray(self._active, jnp.float32),
                                  metric=self.metric, impl=self.impl)
        new_cell = np.asarray(jnp.argmax(cscores, axis=1), np.int32)

        # Vectorized mover prefilter: the Python bucket surgery below runs
        # only over rows that actually changed cells (rare), not over the
        # whole batch -- a full-invalidation repair passes all N rows.
        movers = np.flatnonzero(new_cell != self._row_cell[rows])
        moved = int(movers.size)
        for r, nc in zip(rows[movers].tolist(),
                         new_cell[movers].tolist()):
            oc = int(self._row_cell[r])
            # swap-with-last removal from the old bucket
            slot = int(self._row_slot[r])
            last = int(self._cell_len[oc]) - 1
            tail = int(self._table[oc, last])
            self._table[oc, slot] = tail
            self._row_slot[tail] = slot
            self._table[oc, last] = -1
            self._cell_len[oc] = last
            # append to the new bucket, growing capacity if it is full
            if int(self._cell_len[nc]) == self.bucket_capacity:
                grow = np.full((self._table.shape[0], self.pad_multiple), -1,
                               np.int32)
                self._table = np.concatenate([self._table, grow], axis=1)
                self.stats["table_grows"] += 1
            self._table[nc, int(self._cell_len[nc])] = r
            self._row_slot[r] = int(self._cell_len[nc])
            self._cell_len[nc] += 1
            self._row_cell[r] = nc
        if moved:
            self._table_dev = None
        self.stats["repaired_rows"] += int(rows.size)
        self.stats["bucket_moves"] += moved
        return moved


# ---------------------------------------------------------------------------
# jitted query paths (module level so the trace cache is shared across
# index instances with the same shapes/statics)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "metric", "impl", "fused"))
def _exact_search(queries, z, *, k, metric, impl, fused=False):
    """Brute force: score all N rows, top-k.  The recall oracle.

    ``fused=True`` routes through the fused score-and-top-k kernel
    (``repro.kernels.topk_score.scored_topk``) so the [Q, N] score matrix
    never materializes; staged otherwise -- identical results either way.
    """
    return scored_topk(queries, z, None, k, metric=metric, impl=impl,
                       fused=fused)


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "metric", "impl",
                                             "fused"))
def _ivf_search(queries, z, centroids, active, table, *, k, nprobe, metric,
                impl, fused=False):
    """Probe -> gather -> batched masked score -> top-k, one trace per
    (Q, nprobe, k, table shape) combination."""
    cscores = pairwise_scores(queries, centroids, active, metric=metric,
                              impl=impl)                        # [Q, C]
    _, cells = jax.lax.top_k(cscores, nprobe)                   # [Q, P]
    ids = table[cells]                                          # [Q, P, B]
    q = ids.shape[0]
    ids = ids.reshape(q, nprobe * table.shape[1])               # [Q, P*B]
    # Over-probing (nprobe > active cells) selects NEG_INF cells whose
    # table rows are all -1 -- masked out below, never scored as real.
    cand = z[jnp.clip(ids, 0, z.shape[0] - 1)]                  # [Q, P*B, K]
    mask = (ids >= 0).astype(jnp.float32)
    return scored_topk_gathered(queries, cand, mask, ids, k, metric=metric,
                                impl=impl, fused=fused)
