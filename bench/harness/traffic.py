"""The one generator every traffic mix runs through.

A mix is a data file, bench/traffic/<mix>.json, of parameters:

  mode              "refit": fits of one in-memory graph, prepared once in
                    set-up (``GEEEmbedder.fit(prepared, labels).transform()``);
                    "file": fits of the graph written as a ``.geeb`` file in
                    set-up (``GEEEmbedder.fit_transform_file(path, labels)``)
  backend           the ``GEEEmbedder`` backend ("auto", "streamed_sharded", ...)
  label_pool        label vectors drawn in set-up; fit i uses vector i mod pool
  check_fits        fits whose Z is compared with the reference, a reservoir
                    sample drawn from the seed over all the window's fits
  program_spans     whether the traced run turns the program's tracer on

Fits run back to back in a closed loop, each one uploading its labels and
ending when its Z is ready on the device.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

import numpy as np

from harness import graphgen
from harness.reference import Reference


class Mix:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.n = int(config["num_nodes"])
        self.e = int(config["num_edges"])
        self.k = int(config["num_classes"])
        self.labelled = int(config["labelled"])
        self.pool = []
        self._dir = None
        self._src = self._dst = None
        self.resolved = None

    # -- set-up ------------------------------------------------------------
    def setup(self) -> dict:
        """Make the graph and the labels, hand them to the program, and run
        one fit so that every program the window uses is compiled."""
        import jax

        from repro.core.gee import GEEOptions

        pieces = {}
        t = time.perf_counter()
        c = self.config
        s, d = graphgen.base_graph(self.n, self.e, self.k, c["graph_seed"])
        self._src, self._dst = graphgen.relabel(s, d, self.n, self.seed)
        del s, d
        self.pool = [graphgen.draw_labels(self.n, self.k, self.labelled,
                                          self.seed, i)
                     for i in range(int(self.traffic["label_pool"]))]
        pieces["generate_s"] = time.perf_counter() - t
        self.options = GEEOptions(**c["options"])

        t = time.perf_counter()
        if self.traffic["mode"] == "refit":
            self._prepare_in_memory()
            pieces["prepare_s"] = time.perf_counter() - t
        elif self.traffic["mode"] == "file":
            self._write_file()
            pieces["write_s"] = time.perf_counter() - t
        else:
            raise ValueError(f"unknown mode {self.traffic['mode']!r}")

        t = time.perf_counter()
        jax.block_until_ready(self.fit(len(self.pool) - 1))
        pieces["first_fit_s"] = time.perf_counter() - t
        return pieces

    def _embedder(self):
        from repro.core.api import GEEEmbedder

        return GEEEmbedder(num_classes=self.k, options=self.options,
                           backend=self.traffic["backend"])

    def _prepare_in_memory(self):
        from repro.core.plan import PreparedGraph
        from repro.graph.containers import edge_list_from_numpy

        src = np.concatenate([self._src, self._dst])
        dst = np.concatenate([self._dst, self._src])
        self.prepared = PreparedGraph.wrap(
            edge_list_from_numpy(src, dst, None, self.n))

    def _write_file(self):
        from repro.graph.io import BinaryEdgeWriter, save_labels

        self._dir = tempfile.mkdtemp(prefix="gee-bench-")
        self.path = os.path.join(self._dir, "graph.geeb")
        step = 1 << 20
        with BinaryEdgeWriter(self.path, self.n, self.e,
                              undirected=True) as writer:
            for lo in range(0, self.e, step):
                writer.append(self._src[lo:lo + step],
                              self._dst[lo:lo + step])
        save_labels(self.path, self.pool[0])

    # -- the timed call ----------------------------------------------------
    def labels(self, i: int) -> np.ndarray:
        return self.pool[i % len(self.pool)]

    def fit(self, i: int):
        """Fit ``i``: its Z, as the device returns it."""
        emb = self._embedder()
        if self.traffic["mode"] == "refit":
            z = emb.fit(self.prepared, self.labels(i)).transform()
            if emb.plan is not None:
                self.resolved = {"backend": emb.plan.backend,
                                 "fused": emb.plan.fused}
            return z
        return emb.fit_transform_file(self.path, labels=self.labels(i))

    # -- after the window --------------------------------------------------
    def release_program_state(self):
        self.prepared = None

    def reference(self) -> Reference:
        src = np.concatenate([self._src, self._dst])
        dst = np.concatenate([self._dst, self._src])
        return Reference(src, dst, self.n, self.config["options"])

    def close(self):
        if self._dir is not None:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
