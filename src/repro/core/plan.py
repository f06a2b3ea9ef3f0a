"""Plan/executor layer: prepare a graph once, embed it many times.

The paper's contribution is eliminating redundant work on sparse graphs,
yet a naive client redoes the *same* O(E) preparation -- symmetrize,
self-loop augmentation, the degree fold, the Laplacian edge reweighting,
ELL packing, the chunk manifest -- on every fit, every option setting of
an ensemble sweep, and every ``--compare`` cell.  One-Hot GEE
(arXiv 2109.13098) shows the embedding itself is a cheap linear pass, so
that preparation dominates repeated fits; Edge-Parallel GEE
(arXiv 2402.04403) gets its speedup precisely by hoisting graph prep out
of the per-run path.  This module makes that structural:

  ``PreparedGraph``  an immutable wrapper over ``EdgeList`` that lazily
                     computes and memoizes every derived artifact, so a
                     second fit, another option setting, an ensemble
                     replicate, or a ``--compare`` sweep never re-derives
                     them.
  ``GEEPlan``        resolves ``(backend="auto", opts, device)`` into
                     explicit stages -- prep, scatter/SpMM, epilogue --
                     and executes them against a labels vector.  The
                     epilogue always runs through ``repro.core.epilogue``
                     (the single numerics source of truth).
  ``select_backend`` the cost model behind ``backend="auto"``: Pallas
                     on a real MXU with lane-sized K, ``chunked`` when
                     the working-set estimate exceeds the memory budget,
                     ``sparse_jax`` otherwise.
  ``sweep_options``  the many-settings fast path: correlation is a pure
                     row postprocess, so the 8 canonical option settings
                     need only 4 scatter passes over shared prep.

``gee()``, ``GEEEmbedder``, the ensemble clusterer, the distributed
sharder and the launch CLIs are all thin consumers of this layer.

>>> import numpy as np
>>> from repro.core.gee import ALL_OPTION_SETTINGS, GEEOptions
>>> prep = PreparedGraph.from_arrays(     # symmetrized + uploaded ONCE
...     np.array([0, 1, 2]), np.array([1, 2, 3]), None, num_nodes=4)
>>> labels = np.array([0, 1, 0, 1], np.int32)
>>> plan = GEEPlan.build(prep, 2, GEEOptions(laplacian=True, diag_aug=True,
...                                          correlation=True))
>>> [s.name for s in plan.stages]
['effective_edges', 'segment_scatter', 'row_l2_normalize']
>>> plan.execute(labels).shape
(4, 2)
>>> zs = sweep_options(prep, labels, 2)   # all 8 settings, prep shared
>>> len(zs), zs[GEEOptions(correlation=True)].shape
(8, (4, 2))
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, Iterable, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import epilogue
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.core.gee import (ALL_OPTION_SETTINGS, GEEOptions, gee_dense_jax,
                            gee_python_loop, gee_scipy, gee_sparse_jax,
                            laplacian_edge_weights)
from repro.graph.containers import (EdgeList, add_self_loops,
                                    edge_list_from_numpy, symmetrize)

KNOWN_BACKENDS = ("sparse_jax", "pallas", "chunked", "streamed_sharded",
                  "dense_jax", "scipy", "python_loop")

# Working-set budget for the cost model's route-to-chunked decision.
ENV_MEMORY_BUDGET = "REPRO_GEE_MEMORY_BUDGET_BYTES"
DEFAULT_MEMORY_BUDGET = 16 << 30    # 16 GiB: a generous laptop/host default

# The Pallas kernel pays off only while the one-hot fits a few 128-lanes.
PALLAS_MAX_CLASSES = 4 * 128


@jax.jit
def _laplacian_fold(edges: EdgeList) -> EdgeList:
    """Fold d_i^{-1/2} d_j^{-1/2} into the edge weights (device, jitted)."""
    return dataclasses.replace(edges,
                               weight=laplacian_edge_weights(edges))


_add_self_loops_jit = jax.jit(add_self_loops)


def _block_tree(x):
    """``jax.block_until_ready`` on the array leaves of a stage result.

    Host-only results (chunk manifests, numpy triples) have nothing to
    wait for and are skipped by leaf type, so a device fault raised while
    waiting on a real array still surfaces."""
    for leaf in jax.tree_util.tree_leaves(x):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()
    return x


def _chunk_key(chunk_edges: int | None) -> int:
    """The ``("chunked", ...)`` cache-key component for a window size."""
    from repro.graph.io import DEFAULT_CHUNK_EDGES

    return int(chunk_edges or DEFAULT_CHUNK_EDGES)


# ---------------------------------------------------------------------------
# PreparedGraph: the memoized prep artifacts
# ---------------------------------------------------------------------------

class PreparedGraph:
    """Immutable wrapper over an ``EdgeList`` memoizing derived artifacts.

    Artifacts (all lazy, each computed at most once per instance):

      * ``with_self_loops()``          the diag-aug edge list (A + I)
      * ``degrees(diag_aug)``          weighted degrees of the (augmented)
                                       graph
      * ``effective_edges(opts)``      self-loop-augmented AND
                                       Laplacian-folded edges -- the exact
                                       input of the scatter stage, keyed
                                       on ``(diag_aug, laplacian)`` (the
                                       correlation flag never affects prep)
      * ``ell(diag_aug)`` /
        ``bucketed_ell(diag_aug)``     the Pallas kernel's packing planes
      * ``chunked(chunk_edges)``       the chunk manifest of the streaming
                                       backend
      * ``host_arrays()``              the valid-prefix numpy triple the
                                       SciPy / python-loop backends consume

    The wrapped ``EdgeList`` must not be mutated afterwards (they are
    frozen dataclasses; nothing in the repo mutates them).
    """

    def __init__(self, edges: EdgeList):
        if isinstance(edges, PreparedGraph):
            raise TypeError("already a PreparedGraph; use PreparedGraph.wrap")
        if not isinstance(edges, EdgeList):
            raise TypeError(f"expected an EdgeList, got "
                            f"{type(edges).__name__}")
        self._edges = edges
        self._cache: Dict[tuple, object] = {}
        self._hits = 0
        self._misses = 0

    # -- construction --------------------------------------------------------
    @staticmethod
    def wrap(graph: "PreparedGraph | EdgeList") -> "PreparedGraph":
        """Idempotent constructor: wrap an ``EdgeList``, pass a
        ``PreparedGraph`` through untouched (preserving its caches)."""
        return graph if isinstance(graph, PreparedGraph) \
            else PreparedGraph(graph)

    @staticmethod
    def from_arrays(src, dst, weight=None, num_nodes: int | None = None,
                    undirected: bool = True,
                    pad_to: int | None = None) -> "PreparedGraph":
        """Build from raw host arrays: symmetrize (for undirected input)
        and upload exactly once -- the cold-start prep a per-call sweep
        would otherwise repeat."""
        src = np.asarray(src)
        dst = np.asarray(dst)
        n = int(num_nodes if num_nodes is not None
                else max(int(src.max(initial=-1)),
                         int(dst.max(initial=-1))) + 1)
        edges = edge_list_from_numpy(
            src, dst, None if weight is None else np.asarray(weight), n,
            pad_to=pad_to)
        if undirected:
            edges = symmetrize(edges)
        return PreparedGraph(edges)

    # -- basics --------------------------------------------------------------
    @property
    def base(self) -> EdgeList:
        """The wrapped (already-directed) edge list."""
        return self._edges

    @property
    def num_nodes(self) -> int:
        return self._edges.num_nodes

    @property
    def num_edges(self) -> int:
        return self._edges.num_edges

    def _memo(self, key: tuple, build):
        hit = self._cache.get(key)
        if hit is not None:
            self._hits += 1
            return hit
        self._misses += 1
        value = build()
        self._cache[key] = value
        return value

    def is_cached(self, key: tuple) -> bool:
        return key in self._cache

    def cache_info(self) -> dict:
        """Which artifacts are resident, plus hit/miss counters (the
        no-rebuild regression tests key on this)."""
        return {"keys": tuple(sorted(map(str, self._cache))),
                "entries": len(self._cache),
                "hits": self._hits, "misses": self._misses}

    # -- prep artifacts ------------------------------------------------------
    def with_self_loops(self) -> EdgeList:
        """The diagonal-augmented list (A + I), spliced after the valid
        prefix exactly like ``repro.graph.containers.add_self_loops``."""
        return self._memo(("self_loops",),
                          lambda: _add_self_loops_jit(self._edges))

    def augmented(self, diag_aug: bool) -> EdgeList:
        return self.with_self_loops() if diag_aug else self._edges

    def degrees(self, diag_aug: bool = False) -> jax.Array:
        """Weighted out-degrees of the (augmented) graph, [N] f32."""
        def build():
            e = self.augmented(diag_aug)
            return jax.ops.segment_sum(e.weight, e.src,
                                       num_segments=e.num_nodes)
        return self._memo(("degrees", bool(diag_aug)), build)

    def laplacian_inv_sqrt(self, diag_aug: bool = False) -> jax.Array:
        """d^{-1/2} of the (augmented) degrees, shared-epilogue clamped."""
        return self._memo(
            ("dinv", bool(diag_aug)),
            lambda: epilogue.inv_sqrt_degrees(self.degrees(diag_aug)))

    def effective_edges(self, opts: GEEOptions) -> EdgeList:
        """The scatter stage's exact input: self loops appended when
        ``opts.diag_aug``, weights Laplacian-folded when ``opts.laplacian``
        (degrees of the *augmented* graph, per the shared option order).
        Keyed on ``(diag_aug, laplacian)`` only -- correlation is pure
        epilogue and never invalidates prep.
        """
        key = ("eff", bool(opts.diag_aug), bool(opts.laplacian))

        def build():
            e = self.augmented(opts.diag_aug)
            return _laplacian_fold(e) if opts.laplacian else e
        return self._memo(key, build)

    def ell(self, diag_aug: bool = False):
        """Single-plane ELL packing of the (augmented) graph (host-side
        O(E); by far the most expensive prep artifact -- cache pays)."""
        from repro.graph.ell import edges_to_ell  # deferred: keep core light

        return self._memo(("ell", bool(diag_aug)),
                          lambda: edges_to_ell(self.augmented(diag_aug)))

    def bucketed_ell(self, diag_aug: bool = False):
        """Degree-bucketed ELL packing of the (augmented) graph."""
        from repro.graph.ell import edges_to_bucketed_ell

        return self._memo(
            ("bucketed_ell", bool(diag_aug)),
            lambda: edges_to_bucketed_ell(self.augmented(diag_aug)))

    def chunked(self, chunk_edges: int | None = None):
        """The streaming backend's chunk manifest over the valid prefix
        (one manifest per distinct window size)."""
        from repro.graph.io import DEFAULT_CHUNK_EDGES, ChunkedEdgeList

        chunk = int(chunk_edges or DEFAULT_CHUNK_EDGES)
        return self._memo(
            ("chunked", chunk),
            lambda: ChunkedEdgeList.from_edge_list(self._edges, chunk))

    def host_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Valid-prefix ``(src, dst, weight)`` numpy triple (the SciPy and
        python-loop backends' input)."""
        return self._memo(("host",), self._edges.valid_arrays)


# ---------------------------------------------------------------------------
# the cost model behind backend="auto"
# ---------------------------------------------------------------------------

def _bucketed_slot_estimate(edges: EdgeList) -> int:
    """Total ELL slots after degree-bucketed packing of the augmented
    graph (host-side O(E) bincount; the pow2 ladder is the packer's own).

    On a skewed (power-law) degree distribution this is the number that
    actually sizes the Pallas working set: every row occupies its
    bucket's full width, so a graph whose *edge count* fits the budget
    can still blow past it after packing (a hub row of degree d costs
    pow2(d) slots; the long tail of degree-1 rows cost 8 slots each).
    """
    from repro.graph.ell import bucket_widths  # the ladder the packer uses

    e = edges.num_edges
    src = np.asarray(edges.src)[:e]
    w = np.asarray(edges.weight)[:e]
    deg = np.bincount(src[w != 0], minlength=edges.num_nodes) + 1  # + loop
    widths = np.asarray(bucket_widths(int(deg.max(initial=1))))
    return int(widths[np.searchsorted(widths, deg)].sum())


def estimate_working_set_bytes(graph: PreparedGraph | EdgeList,
                               num_classes: int, *,
                               backend: str = "sparse_jax") -> int:
    """Rough in-memory working set, per backend family.

    The default (``sparse_jax``) counts base + effective edge triples
    (src/dst/weight, self loops included), the degree vector, and Z.
    ``backend="pallas"`` instead counts the *post-packing* ELL slots
    (:func:`_bucketed_slot_estimate`): cols + vals + the ylab/contrib
    planes are 16 bytes per slot, and on skewed degree distributions
    slots >> E -- the raw edge estimate would route graphs to ``pallas``
    that cannot fit after bucketed packing.
    """
    edges = graph.base if isinstance(graph, PreparedGraph) else graph
    n = edges.num_nodes
    base_bytes = 3 * 4 * edges.padded_size
    z_deg_bytes = 4 * n + 4 * n * int(num_classes)
    if backend == "pallas":
        if isinstance(graph, PreparedGraph):
            slots = graph._memo(("ell_slots",),
                                lambda: _bucketed_slot_estimate(edges))
        else:
            slots = _bucketed_slot_estimate(edges)
        return base_bytes + 16 * slots + z_deg_bytes
    e_eff = edges.padded_size + n                    # with self loops
    return base_bytes + 3 * 4 * e_eff + z_deg_bytes


def memory_budget_bytes() -> int:
    """The route-to-chunked threshold: ``REPRO_GEE_MEMORY_BUDGET_BYTES``
    or a 16 GiB default."""
    return int(os.environ.get(ENV_MEMORY_BUDGET, DEFAULT_MEMORY_BUDGET))


def select_backend(graph: PreparedGraph | EdgeList, num_classes: int, *,
                   device: str | None = None,
                   budget_bytes: int | None = None,
                   num_devices: int | None = None) -> str:
    """The ``backend="auto"`` cost model.

    1. If the estimated working set exceeds the memory budget, stream:
       ``streamed_sharded`` when more than one device can fold disjoint
       sub-windows in parallel, ``chunked`` on a single device -- either
       way peak memory is O(window + N*K) whatever E is.
    2. On a real TPU with K within a few 128-lanes *and* the ELL-aware
       pallas estimate also inside the budget (bucketed packing can blow
       up far past E on skewed degree distributions), the Pallas kernel
       wins the contraction.
    3. Everywhere else, the O(E) segment-sum path is the safe default (on
       CPU the kernel would run in interpret mode, strictly slower).

    ``auto`` never selects ``distributed`` or the host reference backends:
    those change *where the data lives*, which is the caller's decision
    (``streamed_sharded`` builds its own default mesh over the local
    devices, so it stays a pure capacity decision).
    ``num_devices=None`` asks jax for the local device count.
    """
    device = device or jax.default_backend()
    budget = memory_budget_bytes() if budget_bytes is None else budget_bytes
    if estimate_working_set_bytes(graph, num_classes) > budget:
        p = jax.device_count() if num_devices is None else int(num_devices)
        return "streamed_sharded" if p > 1 else "chunked"
    if (device == "tpu" and num_classes <= PALLAS_MAX_CLASSES
            and estimate_working_set_bytes(
                graph, num_classes, backend="pallas") <= budget):
        return "pallas"
    return "sparse_jax"


def select_fused(backend: str, opts: GEEOptions, *,
                 device: str | None = None) -> bool:
    """The fused-epilogue stage's cost model (``fused="auto"``).

    The fused megakernel (``repro.kernels.gee_fused``) replaces the
    staged scatter + epilogue of the ``pallas`` backend, eliminating one
    full [N, K] materialization -- it pays off exactly when (a) the
    backend is ``pallas``, (b) there is an epilogue to fuse (diag-aug or
    correlation; with neither the fused kernel degenerates to the staged
    scatter), and (c) the device is a real TPU (off-TPU both paths run in
    interpret mode and fusion saves nothing).  ``REPRO_GEE_FUSED=1/0``
    overrides (b) and (c) but never (a): the fused stage only exists on
    the Pallas path, so the override is a no-op for other backends.
    """
    if backend != "pallas":
        return False
    from repro.kernels.gee_fused import fused_override  # deferred: keep light

    override = fused_override()
    if override is not None:
        return bool(override)
    device = device or jax.default_backend()
    return device == "tpu" and bool(opts.diag_aug or opts.correlation)


# ---------------------------------------------------------------------------
# GEEPlan: resolved stages + executor
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanStage:
    """One resolved execution stage (introspection / logging surface)."""

    kind: str            # "prep" | "compute" | "epilogue"
    name: str
    cached: bool = False  # artifact already resident in the PreparedGraph
    detail: str = ""


@dataclasses.dataclass(frozen=True)
class GEEPlan:
    """An executable embedding plan: resolved backend + staged pipeline.

    Build once with :meth:`build` (which resolves ``backend="auto"``
    through the cost model), then :meth:`execute` against any labels
    vector.  All prep flows through the shared :class:`PreparedGraph`, so
    repeated executions -- other option settings, ensemble replicates,
    refreshed labels -- reuse every artifact.
    """

    prepared: PreparedGraph
    num_classes: int
    opts: GEEOptions
    backend: str                      # resolved; never "auto"
    chunk_edges: Optional[int] = None
    impl: str = "auto"                # epilogue row-norm impl
    fused: bool = False               # pallas-only: fused-epilogue megakernel
    # streaming backends only: windows staged ahead by background threads
    # (resolved by build(); None defers to the env default at execute time)
    prefetch_windows: Optional[int] = None
    # per-stage wall times (ms) of the last *traced* execution; a mutable
    # cell on a frozen plan -- excluded from eq/repr, never reassigned
    _timings: dict = dataclasses.field(default_factory=dict, compare=False,
                                       repr=False)

    @staticmethod
    def build(graph: PreparedGraph | EdgeList, num_classes: int,
              opts: GEEOptions = GEEOptions(), *, backend: str = "auto",
              device: str | None = None, chunk_edges: int | None = None,
              budget_bytes: int | None = None, impl: str = "auto",
              fused: "bool | str" = "auto",
              prefetch_windows: int | None = None) -> "GEEPlan":
        prepared = PreparedGraph.wrap(graph)
        if backend == "auto":
            backend = select_backend(prepared, num_classes, device=device,
                                     budget_bytes=budget_bytes)
        if backend not in KNOWN_BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; known: {KNOWN_BACKENDS} "
                f"(+ 'auto'; 'distributed' needs an explicit mesh -- use "
                f"GEEEmbedder, or 'streamed_sharded' for the default mesh)")
        if fused == "auto":
            fused = select_fused(backend, opts, device=device)
        if backend in ("chunked", "streamed_sharded"):
            from repro.graph.prefetch import resolve_prefetch_depth
            prefetch_windows = resolve_prefetch_depth(prefetch_windows)
        else:
            prefetch_windows = None      # knob only exists for streaming
        return GEEPlan(prepared=prepared, num_classes=int(num_classes),
                       opts=opts, backend=backend, chunk_edges=chunk_edges,
                       impl=impl, fused=bool(fused) and backend == "pallas",
                       prefetch_windows=prefetch_windows)

    # -- introspection -------------------------------------------------------
    @property
    def _prefetch_detail(self) -> str:
        """Human-readable prefetch depth for ``stages``/``describe()``."""
        return "env" if self.prefetch_windows is None \
            else str(self.prefetch_windows)

    @property
    def stages(self) -> Tuple[PlanStage, ...]:
        p, o = self.prepared, self.opts
        out = []
        if self.backend == "sparse_jax":
            out.append(PlanStage(
                "prep", "effective_edges",
                cached=p.is_cached(("eff", o.diag_aug, o.laplacian)),
                detail="self-loop augment + laplacian fold"))
            out.append(PlanStage("compute", "segment_scatter",
                                 detail="flat segment-sum, O(E)"))
        elif self.backend == "pallas":
            # both packings hold the *base* graph: diag-aug folds in as
            # deg+1 plus the per-row addend
            out.append(PlanStage(
                "prep", "bucketed_ell",
                cached=p.is_cached(("bucketed_ell", False)),
                detail="degree-bucketed ELL packing (host, O(E))"))
            if self.fused:
                out.append(PlanStage(
                    "compute", "gee_spmm_fused",
                    detail="scatter + diag-aug + row-norm fused in VMEM"))
            else:
                out.append(PlanStage(
                    "compute", "gee_spmm",
                    detail="MXU one-hot contraction per bucket"))
        elif self.backend == "chunked":
            from repro.graph.io import DEFAULT_CHUNK_EDGES

            chunk = int(self.chunk_edges or DEFAULT_CHUNK_EDGES)
            out.append(PlanStage("prep", "chunk_manifest",
                                 cached=p.is_cached(("chunked", chunk)),
                                 detail=f"window={chunk} edges, "
                                        f"prefetch={self._prefetch_detail}"))
            out.append(PlanStage("compute", "two_pass_stream",
                                 detail="degree fold + per-class fold"))
        elif self.backend == "streamed_sharded":
            from repro.graph.io import DEFAULT_CHUNK_EDGES

            chunk = int(self.chunk_edges or DEFAULT_CHUNK_EDGES)
            out.append(PlanStage(
                "prep", "chunk_manifest",
                cached=p.is_cached(("chunked", chunk)),
                detail=f"window={chunk} edges, "
                       f"prefetch={self._prefetch_detail}, "
                       f"split across devices"))
            out.append(PlanStage(
                "compute", "window_shard_fold",
                detail="per-device sub-window fold, donated partials"))
            out.append(PlanStage(
                "epilogue", "reduce_scatter_epilogue",
                detail="psum_scatter + row-local diag-aug/row-norm"))
        elif self.backend == "dense_jax":
            out.append(PlanStage("compute", "dense_matmul",
                                 detail="A @ W oracle, O(N^2)"))
        else:                          # scipy / python_loop host references
            out.append(PlanStage("prep", "host_arrays",
                                 cached=p.is_cached(("host",)),
                                 detail="valid-prefix numpy triple"))
            out.append(PlanStage("compute", self.backend))
        if o.correlation and not self.fused \
                and self.backend not in ("chunked", "streamed_sharded",
                                         "dense_jax", "scipy",
                                         "python_loop"):
            out.append(PlanStage("epilogue", "row_l2_normalize",
                                 detail=f"impl={self.impl}"))
        return tuple(out)

    def describe(self, timings: bool = False) -> str:
        """One line per stage, e.g. for ``--plan`` CLI output.

        ``timings=True`` appends each stage's wall time from the last
        *traced* execution (run :meth:`execute` with the tracer enabled
        first -- untraced executions skip the stage-boundary syncs that
        make per-stage times honest, so they record nothing).
        """
        head = (f"GEEPlan(backend={self.backend}"
                + (", fused" if self.fused else "")
                + f", opts={self.opts.tag()}, "
                f"N={self.prepared.num_nodes}, "
                f"E={self.prepared.num_edges}, K={self.num_classes})")
        timed = self._timings if timings else {}
        lines = [head]
        for s in self.stages:
            line = (f"  [{s.kind:8s}] {s.name}"
                    + (" (cached)" if s.cached else "")
                    + (f" -- {s.detail}" if s.detail else ""))
            if s.name in timed:
                line += f"  [{timed[s.name]:.2f} ms]"
            lines.append(line)
        if timings:
            if "total_ms" in timed:
                lines.append(f"  total {timed['total_ms']:.2f} ms "
                             f"(stage syncs forced by tracing)")
            else:
                lines.append("  (no traced execution yet: enable the "
                             "tracer, then execute())")
        return "\n".join(lines)

    @property
    def last_timings(self) -> dict:
        """``{stage_name: ms, "total_ms": ms}`` from the last traced
        execution (empty until one happens)."""
        return dict(self._timings)

    # -- execution -----------------------------------------------------------
    def _stage(self, kind: str, name: str, cached: bool, fn):
        """Run one pipeline stage under a ``plan.stage.<name>`` span.

        With the tracer disabled this is a plain call.  With it enabled,
        the stage result is blocked-on before the span closes -- jax
        dispatch is async, so without the sync every stage but the last
        would bill its device time to whoever touches the value next.
        """
        tr = obs_trace.get_tracer()
        if not tr.enabled:
            return fn()
        t0 = time.perf_counter()
        with tr.span("plan.stage." + name, kind=kind, cached=cached):
            out = _block_tree(fn())
        self._timings[name] = (time.perf_counter() - t0) * 1e3
        return out

    def execute(self, labels) -> jax.Array:
        """Run the staged pipeline for one labels vector.

        With the global tracer enabled, every stage runs under a
        ``plan.stage.*`` span (tagged with its prep-cache status) inside
        one ``plan.execute`` root span, and per-stage wall times are kept
        for :meth:`describe(timings=True) <describe>`.
        """
        tr = obs_trace.get_tracer()
        if not tr.enabled:
            return self._execute_stages(labels)
        self._timings.clear()
        p = self.prepared
        hits0, misses0 = p._hits, p._misses
        t0 = time.perf_counter()
        with tr.span("plan.execute", backend=self.backend,
                     n=p.num_nodes, e=p.num_edges, k=self.num_classes,
                     opts=self.opts.tag(), fused=self.fused) as root:
            z = _block_tree(self._execute_stages(labels))
            root.tag(cache_hits=p._hits - hits0,
                     cache_misses=p._misses - misses0)
        total_ms = (time.perf_counter() - t0) * 1e3
        self._timings["total_ms"] = total_ms
        reg = obs_metrics.get_registry()
        reg.counter("plan.executions").inc()
        reg.counter("plan.cache_hits").inc(p._hits - hits0)
        reg.counter("plan.cache_misses").inc(p._misses - misses0)
        reg.histogram("plan.execute_ms").observe(total_ms)
        return z

    def _execute_stages(self, labels) -> jax.Array:
        k, o, p = self.num_classes, self.opts, self.prepared
        if self.backend == "sparse_jax":
            eff = self._stage(
                "prep", "effective_edges",
                p.is_cached(("eff", o.diag_aug, o.laplacian)),
                lambda: p.effective_edges(o))
            # prep already applied: the scatter runs with bare options
            z = self._stage(
                "compute", "segment_scatter", False,
                lambda: gee_sparse_jax(eff, jnp.asarray(labels), k,
                                       GEEOptions()))
            if o.correlation:
                z = self._stage(
                    "epilogue", "row_l2_normalize", False,
                    lambda: epilogue.row_l2_normalize(z, impl=self.impl))
            return z
        if self.backend == "pallas":
            # base-graph packing: diag-aug folds in as deg+1 + the per-row
            # addend, so the augmented packing never builds
            bell = self._stage(
                "prep", "bucketed_ell",
                p.is_cached(("bucketed_ell", False)),
                lambda: p.bucketed_ell(False))
            if self.fused:
                from repro.kernels.gee_fused import gee_fused_from_bucketed

                return self._stage(
                    "compute", "gee_spmm_fused", False,
                    lambda: gee_fused_from_bucketed(
                        bell, jnp.asarray(labels), k, o))
            from repro.kernels.ops import gee_pallas_from_bucketed

            z = self._stage(
                "compute", "gee_spmm", False,
                lambda: gee_pallas_from_bucketed(
                    bell, jnp.asarray(labels), k,
                    GEEOptions(laplacian=o.laplacian, diag_aug=o.diag_aug)))
            if o.correlation:      # epilogue honors this plan's impl choice
                z = self._stage(
                    "epilogue", "row_l2_normalize", False,
                    lambda: epilogue.row_l2_normalize(z, impl=self.impl))
            return z
        if self.backend == "chunked":
            from repro.core.chunked import gee_chunked

            chunk = self.chunk_edges
            manifest = self._stage(
                "prep", "chunk_manifest",
                p.is_cached(("chunked", _chunk_key(chunk))),
                lambda: p.chunked(chunk))
            return self._stage(
                "compute", "two_pass_stream", False,
                lambda: gee_chunked(manifest, labels, k, o, impl=self.impl,
                                    prefetch_windows=self.prefetch_windows))
        if self.backend == "streamed_sharded":
            from repro.core.fold import gee_streamed_sharded

            chunk = self.chunk_edges
            manifest = self._stage(
                "prep", "chunk_manifest",
                p.is_cached(("chunked", _chunk_key(chunk))),
                lambda: p.chunked(chunk))
            # default mesh over all local devices; rows come back [:N]
            return self._stage(
                "compute", "window_shard_fold", False,
                lambda: gee_streamed_sharded(
                    manifest, labels, k, o,
                    prefetch_windows=self.prefetch_windows))
        if self.backend == "dense_jax":
            return self._stage(
                "compute", "dense_matmul", False,
                lambda: gee_dense_jax(p.base, jnp.asarray(labels), k, o))
        src, dst, w = self._stage("prep", "host_arrays",
                                  p.is_cached(("host",)), p.host_arrays)
        y = np.asarray(labels)
        if self.backend == "scipy":
            return self._stage(
                "compute", "scipy", False,
                lambda: gee_scipy(src, dst, w, y, k, o,
                                  num_nodes=p.num_nodes))
        assert self.backend == "python_loop"
        return self._stage(
            "compute", "python_loop", False,
            lambda: gee_python_loop(src, dst, w, y, k, o,
                                    num_nodes=p.num_nodes))


# ---------------------------------------------------------------------------
# the many-settings fast path (ensemble / --compare sweeps)
# ---------------------------------------------------------------------------

def sweep_options(graph: PreparedGraph | EdgeList, labels, num_classes: int,
                  settings: Iterable[GEEOptions] = ALL_OPTION_SETTINGS, *,
                  backend: str = "sparse_jax", chunk_edges: int | None = None,
                  impl: str = "auto") -> Mapping[GEEOptions, jax.Array]:
    """Embed one graph under many option settings with all prep shared.

    Two sharing levels, both exact:

      * every setting reuses the ``PreparedGraph`` artifacts (symmetrized
        upload, self-loop augmentation, Laplacian fold, packing);
      * correlation is a pure row postprocess, so settings that differ
        only in it share the same scatter pass -- the 8 canonical
        settings cost 4 scatters + 4 row normalizations.

    Returns ``{opts: Z}`` in the order given.
    """
    prepared = PreparedGraph.wrap(graph)
    raw: Dict[Tuple[bool, bool], jax.Array] = {}
    out: Dict[GEEOptions, jax.Array] = {}
    for opts in settings:
        key = (bool(opts.laplacian), bool(opts.diag_aug))
        if key not in raw:
            base = GEEOptions(laplacian=opts.laplacian,
                              diag_aug=opts.diag_aug)
            raw[key] = GEEPlan.build(
                prepared, num_classes, base, backend=backend,
                chunk_edges=chunk_edges, impl=impl).execute(labels)
        z = raw[key]
        if opts.correlation:
            z = epilogue.row_l2_normalize(jnp.asarray(z), impl=impl)
        out[opts] = z
    return out


Graph = Union[PreparedGraph, EdgeList]

__all__ = ["PreparedGraph", "GEEPlan", "PlanStage", "select_backend",
           "select_fused", "sweep_options", "estimate_working_set_bytes",
           "memory_budget_bytes", "KNOWN_BACKENDS", "ENV_MEMORY_BUDGET",
           "DEFAULT_MEMORY_BUDGET", "PALLAS_MAX_CLASSES"]
