"""Observability layer: span tracer semantics (nesting, exceptions,
bounded buffers, Perfetto export), spans reaching a profiler capture as
annotations, tracing that adds no device sync, the plan, bucket and fold
spans, the metrics registry + legacy ``stats`` compat views, the
disabled-instrumentation overhead gate, and the structured recovery
timeline."""

import json

import numpy as np
import pytest

from repro.core.gee import GEEOptions, gee
from repro.core.incremental import IncrementalGEE
from repro.core.plan import GEEPlan, PreparedGraph
from repro.graph.delta import edge_delta_from_numpy
from repro.graph.sbm import sample_sbm
from repro.obs.metrics import (BoundedSeries, Histogram, MetricsRegistry,
                               get_registry, set_registry)
from repro.obs.trace import (Tracer, get_tracer, set_tracer, span,
                             tracer_overhead_pct)

OPTS_ALL = GEEOptions(laplacian=True, diag_aug=True, correlation=True)


@pytest.fixture
def fresh_obs():
    """Isolate the process-global tracer + registry per test."""
    tracer = Tracer(enabled=False, annotate_device=False)
    registry = MetricsRegistry()
    prev_t, prev_r = set_tracer(tracer), set_registry(registry)
    try:
        yield tracer, registry
    finally:
        set_tracer(prev_t)
        set_registry(prev_r)


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_depths_and_close_order():
    t = Tracer(enabled=True, annotate_device=False)
    with t.span("outer", backend="x"):
        with t.span("mid"):
            with t.span("inner"):
                assert t.open_spans() == ("outer", "mid", "inner")
    assert t.open_spans() == ()
    ev = t.events()
    assert [e.name for e in ev] == ["inner", "mid", "outer"]  # close order
    assert [e.depth for e in ev] == [2, 1, 0]
    outer = ev[-1]
    for child in ev[:-1]:
        assert outer.ts_us <= child.ts_us
        assert child.ts_us + child.dur_us <= outer.ts_us + outer.dur_us + 1.0


def test_spans_close_and_record_under_exceptions():
    t = Tracer(enabled=True, annotate_device=False)
    with pytest.raises(ValueError):
        with t.span("outer"):
            with t.span("inner"):
                raise ValueError("boom")
    # both spans recorded, stack fully unwound, error tagged on both
    assert t.open_spans() == ()
    ev = {e.name: e for e in t.events()}
    assert set(ev) == {"outer", "inner"}
    assert ev["inner"].args["error"] == "ValueError"
    assert ev["outer"].args["error"] == "ValueError"
    # the tracer still works after the exception
    with t.span("after"):
        pass
    assert t.events()[-1].name == "after"


def test_disabled_span_is_shared_singleton(fresh_obs):
    tracer, _ = fresh_obs
    s1, s2 = span("a"), span("b", big=1)
    assert s1 is s2                       # no allocation on the hot path
    with s1 as s:
        s.tag(ignored=True)               # no-op tag
    assert tracer.events() == ()

    tracer.enable()
    with span("live", x=1) as s:
        s.tag(y=2)
    (e,) = tracer.events()
    assert e.name == "live" and e.args == {"x": 1, "y": 2}


def test_max_events_bound_drops_and_counts():
    t = Tracer(enabled=True, max_events=3, annotate_device=False)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert len(t.events()) == 3
    assert t.dropped == 2
    t.clear()
    assert t.events() == () and t.dropped == 0


def test_chrome_trace_is_valid_perfetto_input(tmp_path):
    t = Tracer(enabled=True, annotate_device=False)
    with t.span("fit", backend="sparse_jax"):
        with t.span("scatter", edges=10):
            pass
    path = t.write(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())          # round-trips as JSON
    assert set(doc) == {"displayTimeUnit", "traceEvents"}
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(meta) == 1 and meta[0]["name"] == "process_name"
    assert {e["name"] for e in spans} == {"fit", "scatter"}
    for e in spans:                               # complete-event schema
        assert {"name", "ph", "cat", "ts", "dur", "pid", "tid",
                "args"} <= set(e)
    fit = next(e for e in spans if e["name"] == "fit")
    sc = next(e for e in spans if e["name"] == "scatter")
    assert fit["ts"] <= sc["ts"]                  # containment
    assert sc["ts"] + sc["dur"] <= fit["ts"] + fit["dur"] + 1.0
    assert sc["args"]["depth"] == fit["args"]["depth"] + 1


def test_threaded_spans_keep_per_thread_stacks():
    import threading

    t = Tracer(enabled=True, annotate_device=False)
    errs = []

    def work(i):
        try:
            with t.span(f"outer{i}"):
                with t.span(f"inner{i}"):
                    pass
        except Exception as e:                    # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    [th.start() for th in threads]
    [th.join() for th in threads]
    assert not errs
    ev = {e.name: e for e in t.events()}
    assert len(ev) == 8
    for i in range(4):                            # each thread nests 0 -> 1
        assert ev[f"outer{i}"].depth == 0
        assert ev[f"inner{i}"].depth == 1
        assert ev[f"inner{i}"].tid == ev[f"outer{i}"].tid


def _profile_events(log_dir, prefix):
    """(name, stats dict) of every host event named ``prefix*`` in the
    newest ``.xplane.pb`` under ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(str(log_dir), "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    assert files, "the profiler wrote no trace"
    return [(ev.name, dict(ev.stats))
            for plane in ProfileData.from_file(files[-1]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def test_disabled_tracer_annotates_while_profiling(fresh_obs, sbm_small,
                                                   tmp_path):
    """A disabled tracer still puts its spans, tags included, into a
    profiler capture, and records nothing in memory."""
    import jax

    from repro.core.chunked import gee_chunked

    tracer, _ = fresh_obs
    tracer.annotate_device = True
    chunked = PreparedGraph.wrap(sbm_small.edges).chunked(512)
    gee_chunked(chunked, sbm_small.labels, sbm_small.num_classes, OPTS_ALL,
                prefetch_windows=0)                       # compile first
    with jax.profiler.trace(str(tmp_path)):
        with span("fold.probe", when="profiling") as sp:
            sp.tag(late=7)
        gee_chunked(chunked, sbm_small.labels, sbm_small.num_classes,
                    OPTS_ALL, prefetch_windows=0)
    assert tracer.events() == ()

    windows = _profile_events(tmp_path, "fold.window")
    assert len(windows) == 2 * chunked.num_windows
    assert {tags["phase"] for _, tags in windows} == {"degrees", "scatter"}
    scatter = sorted(tags["idx"] for _, tags in windows
                     if tags["phase"] == "scatter")
    assert scatter == list(range(chunked.num_windows))
    assert sum(tags["edges"] for _, tags in windows
               if tags["phase"] == "scatter") == chunked.num_edges
    # tags known only at the end reach the annotation through tag()
    passes = dict((tags["phase"], tags)
                  for _, tags in _profile_events(tmp_path, "fold.pass"))
    assert passes["scatter"]["windows"] == chunked.num_windows
    assert passes["scatter"]["edges"] == chunked.num_edges
    (probe,) = _profile_events(tmp_path, "fold.probe")
    assert probe[1] == {"when": "profiling", "late": 7}
    assert _profile_events(tmp_path, "fold.epilogue")


def test_disabled_tracer_without_profiler_is_null(fresh_obs):
    import jax

    from repro.obs.trace import _NULL

    tracer, _ = fresh_obs
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert span("a", idx=1) is _NULL
    tracer.annotate_device = True
    assert span("a", idx=1) is _NULL
    assert Tracer(enabled=False).span("b") is _NULL


def test_annotate_device_false_keeps_spans_out_of_profiler(fresh_obs,
                                                           tmp_path):
    import jax

    from repro.obs.trace import _NULL

    tracer, _ = fresh_obs                     # annotate_device=False
    with jax.profiler.trace(str(tmp_path)):
        assert span("fold.hidden") is _NULL
        assert Tracer(enabled=False).span("fold.shown") is not _NULL


# ---------------------------------------------------------------------------
# metrics registry + legacy stats compat
# ---------------------------------------------------------------------------

def test_histogram_bounded_with_exact_aggregates():
    h = Histogram("lat", cap=16)
    for i in range(1000):
        h.observe(float(i))
    assert h.count == 1000
    assert h.total == sum(range(1000))
    assert (h.vmin, h.vmax) == (0.0, 999.0)
    assert len(h.values()) == 16                  # bounded store
    s = h.summary()
    assert s["count"] == 1000 and s["mean"] == pytest.approx(499.5)
    assert 0.0 <= s["p50"] <= 999.0 and s["p50"] <= s["p95"] <= s["p99"]


def test_histogram_exact_below_cap_and_reproducible():
    h1, h2 = Histogram("a", cap=8), Histogram("a", cap=8)
    for h in (h1, h2):
        for v in (3.0, 1.0, 2.0):
            h.observe(v)
    assert h1.values() == [3.0, 1.0, 2.0]         # exact, insertion order
    for h in (h1, h2):
        for i in range(100):
            h.observe(float(i))
    assert h1.values() == h2.values()             # seeded reservoir


def test_stats_view_legacy_semantics(fresh_obs):
    _, reg = fresh_obs
    stats = reg.stats_view("svc", {"flushes": 0, "flush_ms": [],
                                   "routed": {"a": 0, "b": 0}})
    stats["flushes"] += 3
    assert stats["flushes"] == 3                  # int compare
    stats["flush_ms"].append(5.0)
    stats["flush_ms"].append(7.0)
    assert isinstance(stats["flush_ms"], BoundedSeries)
    np.testing.assert_allclose(np.asarray(stats["flush_ms"]), [5.0, 7.0])
    assert float(np.percentile(np.asarray(stats["flush_ms"]), 50)) == 6.0
    assert stats["flush_ms"]                      # truthiness
    assert reg.snapshot()["histograms"]["svc.flush_ms"]["count"] == 2
    stats["flush_ms"].clear()
    assert not stats["flush_ms"] and len(stats["flush_ms"]) == 0
    stats["routed"]["a"] += 2
    assert sum(stats["routed"].values()) == 2
    # every write landed in the registry under the claimed scope
    snap = reg.snapshot()
    assert snap["counters"]["svc.flushes"] == 3
    assert snap["counters"]["svc.routed.a"] == 2
    # dict-ish surface: iteration order, items, to_dict
    assert list(stats) == ["flushes", "flush_ms", "routed"]
    assert stats.to_dict()["routed"] == {"a": 2, "b": 0}
    assert "flushes" in dict(stats.items() if hasattr(stats, "items")
                             else [])


def test_stats_view_scope_uniquification_and_close(fresh_obs):
    _, reg = fresh_obs
    a = reg.stats_view("gee.query", {"flushes": 0})
    b = reg.stats_view("gee.query", {"flushes": 0})
    assert a.scope == "gee.query" and b.scope == "gee.query#1"
    a["flushes"] += 1
    b["flushes"] += 5
    snap = reg.snapshot()["counters"]
    assert snap["gee.query.flushes"] == 1
    assert snap["gee.query#1.flushes"] == 5
    b.close()                                     # instance shutdown
    snap = reg.snapshot()["counters"]
    assert "gee.query#1.flushes" not in snap
    assert snap["gee.query.flushes"] == 1         # first scope untouched
    c = reg.stats_view("gee.query", {"flushes": 0})
    assert c.scope == "gee.query#1"               # name freed for reuse


def test_prometheus_exposition(fresh_obs):
    _, reg = fresh_obs
    reg.counter("wal.appends").inc(4)
    reg.gauge("serve.queries_per_sec").set(1.5e6)
    reg.histogram("fold.prefetch_stall_ms").observe(2.0)
    text = reg.to_prometheus()
    assert "# TYPE wal_appends counter\nwal_appends 4" in text
    assert "serve_queries_per_sec 1500000.0" in text
    assert 'fold_prefetch_stall_ms{quantile="0.50"} 2.0' in text
    assert "fold_prefetch_stall_ms_count 1" in text


def test_registry_json_snapshot_roundtrip(fresh_obs, tmp_path):
    _, reg = fresh_obs
    reg.counter("x.n").inc()
    reg.histogram("x.ms").observe(3.0)
    path = reg.write_json(str(tmp_path / "metrics.json"))
    doc = json.loads(open(path).read())
    assert doc["counters"]["x.n"] == 1
    assert doc["histograms"]["x.ms"]["count"] == 1


# ---------------------------------------------------------------------------
# service/serving stats ride the registry (exact equality with legacy)
# ---------------------------------------------------------------------------

def _service_scenario(n=150, seed=0):
    from repro.search.index import ClassPartitionedIndex
    from repro.search.service import GEEQueryService

    s = sample_sbm(n, seed=seed)
    inc = IncrementalGEE.from_graph(s.edges, s.labels, s.num_classes,
                                    OPTS_ALL)
    index = ClassPartitionedIndex.build(inc.embedding(), s.labels,
                                        s.num_classes)
    return GEEQueryService(index, inc, flush_every=8), inc, s


def test_query_service_stats_backed_by_registry(fresh_obs):
    _, reg = fresh_obs
    service, inc, s = _service_scenario()
    rng = np.random.default_rng(0)
    for lo in range(0, 32, 8):
        service.submit_rows(rng.integers(0, 150, 8))
    service.flush()
    assert service.stats["flushes"] >= 1
    assert service.stats["queries_scored"] >= 32
    snap = reg.snapshot()
    scope = service.stats.scope
    # the registry sees exactly what the legacy dict reports
    assert snap["counters"][f"{scope}.flushes"] == service.stats["flushes"]
    assert (snap["counters"][f"{scope}.queries_scored"]
            == service.stats["queries_scored"])
    # flush latency is a bounded histogram now, not an unbounded list
    assert (snap["histograms"][f"{scope}.flush_ms"]["count"]
            == len(service.stats["flush_ms"]))
    assert snap["gauges"]["serve.queries_per_sec"] > 0
    service.close()
    assert f"{scope}.flushes" not in reg.snapshot()["counters"]


def test_delta_server_stats_backed_by_registry(fresh_obs):
    from repro.search.service import GEEDeltaServer

    _, reg = fresh_obs
    s = sample_sbm(150, seed=1)
    inc = IncrementalGEE.from_graph(s.edges, s.labels, s.num_classes,
                                    OPTS_ALL)
    server = GEEDeltaServer(inc, flush_every=10**9)
    rng = np.random.default_rng(1)
    server.submit(edge_delta_from_numpy(rng.integers(0, 150, 16),
                                        rng.integers(0, 150, 16),
                                        rng.random(16)))
    server.flush()
    snap = reg.snapshot()["counters"]
    scope = server.stats.scope
    for key in ("submitted", "flushes", "applied_deltas"):
        assert snap[f"{scope}.{key}"] == server.stats[key]
    assert server.stats["applied_deltas"] == 16


def test_batch_occupancy_is_bounded(fresh_obs):
    """The decode server's per-tick occupancy list no longer grows without
    bound: past the histogram cap the store stays fixed while the exact
    count keeps counting."""
    _, reg = fresh_obs
    stats = reg.stats_view("serve.decode", {"ticks": 0, "tokens_out": 0,
                                            "batch_occupancy": []})
    cap = stats["batch_occupancy"].histogram.cap
    for i in range(cap + 500):
        stats["ticks"] += 1
        stats["batch_occupancy"].append((i % 8) / 8.0)
    assert len(stats["batch_occupancy"]) == cap
    h = stats["batch_occupancy"].histogram
    assert h.count == cap + 500 and stats["ticks"] == cap + 500


# ---------------------------------------------------------------------------
# plan instrumentation
# ---------------------------------------------------------------------------

@pytest.fixture
def sync_counter(monkeypatch):
    """Counts host waits on the device: ``Array.block_until_ready`` calls
    and ``jax.block_until_ready`` calls (which may batch several arrays
    past the method)."""
    import jax
    from jaxlib._jax import ArrayImpl

    calls = {"n": 0}
    method, function = ArrayImpl.block_until_ready, jax.block_until_ready

    def counted_method(self):
        calls["n"] += 1
        return method(self)

    def counted_function(x):
        calls["n"] += 1
        return function(x)

    monkeypatch.setattr(ArrayImpl, "block_until_ready", counted_method)
    monkeypatch.setattr(jax, "block_until_ready", counted_function)
    return calls


def _syncs(calls, fn):
    before = calls["n"]
    out = np.asarray(fn())              # the one wait both runs share
    return calls["n"] - before, out


def _traced_against_untraced(tracer, calls, fn):
    """(syncs untraced, syncs traced, Z untraced, Z traced) of ``fn``,
    warmed up first so neither run compiles."""
    fn()
    syncs_off, z_off = _syncs(calls, fn)
    assert tracer.events() == ()
    tracer.enable()
    syncs_on, z_on = _syncs(calls, fn)
    return syncs_off, syncs_on, z_off, z_on


def test_plan_traced_execution_matches_untraced(fresh_obs, sbm_small,
                                                sync_counter):
    tracer, reg = fresh_obs
    prep = PreparedGraph.wrap(sbm_small.edges)
    plan = GEEPlan.build(prep, sbm_small.num_classes, OPTS_ALL)
    syncs_off, syncs_on, z_ref, z_traced = _traced_against_untraced(
        tracer, sync_counter, lambda: plan.execute(sbm_small.labels))
    np.testing.assert_allclose(z_traced, z_ref, rtol=1e-6, atol=1e-6)
    # tracing adds no wait on the device: spans time the host's work
    assert syncs_on <= syncs_off, (syncs_on, syncs_off)

    # stage spans nest directly under plan.execute, inside its interval
    events = tracer.events()
    (root,) = [e for e in events if e.name == "plan.execute"]
    stages = [e for e in events if e.name.startswith("plan.stage.")]
    assert stages
    for e in stages:
        assert e.depth == root.depth + 1 and e.tid == root.tid
        assert root.ts_us <= e.ts_us
        assert e.ts_us + e.dur_us <= root.ts_us + root.dur_us + 1.0
    # the counters move on every execution, traced or not
    assert reg.snapshot()["counters"]["plan.executions"] == 3


@pytest.mark.parametrize("prefetch", [0, 2])
def test_stream_traced_fit_adds_no_sync(fresh_obs, sbm_small, sync_counter,
                                        prefetch):
    """The streamed fold waits on no window because it is traced."""
    tracer, _ = fresh_obs
    prep = PreparedGraph.wrap(sbm_small.edges)
    plan = GEEPlan.build(prep, sbm_small.num_classes, OPTS_ALL,
                         backend="chunked", chunk_edges=512,
                         prefetch_windows=prefetch)
    syncs_off, syncs_on, z_ref, z_traced = _traced_against_untraced(
        tracer, sync_counter, lambda: plan.execute(sbm_small.labels))
    np.testing.assert_allclose(z_traced, z_ref, rtol=1e-6, atol=1e-6)
    assert syncs_on <= syncs_off, (syncs_on, syncs_off)
    windows = [e for e in tracer.events() if e.name == "fold.window"]
    assert len(windows) == 2 * prep.chunked(512).num_windows


@pytest.mark.pallas_interpret
def test_bucket_spans_carry_packing_counts(fresh_obs):
    """The fit dispatches as one program under one ``plan.fit`` span
    tagged with the packing's totals -- buckets, packed rows, slots and
    real entries -- and opens no per-bucket host span.  The host
    packing's own span says the same.  The label-independent degree fold
    and scaling run under the ``bucket_scaling`` prep stage of the first
    execute only."""
    tracer, _ = fresh_obs
    tracer.enable()
    s = sample_sbm(120, seed=4)
    prep = PreparedGraph.wrap(s.edges)
    plan = GEEPlan.build(prep, s.num_classes, OPTS_ALL, backend="pallas")
    z = plan.execute(s.labels)
    z_ref = gee(prep, s.labels, s.num_classes, OPTS_ALL,
                backend="sparse_jax")
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), atol=1e-5)

    bell = prep.bucketed_ell(False)
    real = int(np.count_nonzero(np.asarray(s.edges.weight)
                                [: s.edges.num_edges]))
    events = tracer.events()
    (pack,) = [e for e in events if e.name == "plan.pack.bucketed_ell"]
    assert (pack.args["slots"], pack.args["edges"]) == (bell.total_slots,
                                                        real)
    bucket_spans = ("plan.bucket", "plan.bucket.planes",
                    "plan.bucket.launch", "plan.bucket.scatter",
                    "plan.bucket.residual")
    (fit,) = [e for e in events if e.name == "plan.fit"]
    # every bucket of this graph is narrower than a vreg: XLA gathers
    assert fit.args == {"buckets": len(bell.buckets),
                        "rows": pack.args["rows"],
                        "slots": bell.total_slots, "edges": real,
                        "lookup": "gather"}
    assert bell.total_edges == real
    assert not [e for e in events if e.name in bucket_spans]
    # the one-time build: degrees once, one scale span per bucket, both
    # inside the bucket_scaling prep stage
    (stage,) = [e for e in events if e.name == "plan.stage.bucket_scaling"]
    assert stage.args == {"kind": "prep", "cached": False}
    built = [e for e in events if e.name in ("plan.bucket.degrees",
                                             "plan.bucket.scale")]
    assert [e.name for e in built].count("plan.bucket.degrees") == 1
    assert sorted(e.args["idx"] for e in built
                  if e.name == "plan.bucket.scale") \
        == list(range(len(bell.buckets)))
    for e in built:
        assert e.depth > stage.depth
        assert stage.ts_us <= e.ts_us <= stage.ts_us + stage.dur_us

    # a second fit reuses it: no build spans, the same per-fit spans
    tracer.clear()
    z2 = plan.execute(s.labels)
    np.testing.assert_array_equal(np.asarray(z2), np.asarray(z))
    events = tracer.events()
    names = [e.name for e in events]
    assert "plan.bucket.degrees" not in names
    assert "plan.bucket.scale" not in names
    (stage,) = [e for e in events if e.name == "plan.stage.bucket_scaling"]
    assert stage.args["cached"] is True
    assert names.count("plan.fit") == 1
    assert not set(names) & set(bucket_spans)


def _lookup_graph(clique: int, path: int):
    """A clique of ``clique`` vertices (every row in one 128-lane-or-wider
    bucket when ``clique`` > 65) and a path of ``path`` more (narrow
    rows)."""
    from repro.graph.containers import edge_list_from_numpy, symmetrize

    a, b = np.triu_indices(clique, 1)
    p = np.arange(clique, clique + path - 1)
    src = np.concatenate([a, p])
    dst = np.concatenate([b, p + 1])
    n = clique + path
    return symmetrize(edge_list_from_numpy(src, dst, None, n)), n


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("clique,path,route", [
    (130, 0, "vmem"), (130, 40, "mixed"), (0, 60, "gather")])
def test_fit_span_tags_label_lookup_route(fresh_obs, clique, path, route):
    """``plan.fit`` is tagged with the buckets' label ``lookup`` route,
    and ``plan.fit.lookup.vmem`` / ``plan.fit.lookup.gather`` move by the
    buckets that take each route, once a fit."""
    tracer, reg = fresh_obs
    tracer.enable()
    edges, n = _lookup_graph(clique, path)
    labels = (np.arange(n) % 3).astype(np.int32)
    prep = PreparedGraph.wrap(edges)
    plan = GEEPlan.build(prep, 3, OPTS_ALL, backend="pallas")
    widths = [b.width for b in prep.bucketed_ell(False).buckets]
    wide = sum(w >= 128 for w in widths)
    for fit in (1, 2):
        z = plan.execute(labels)
        counters = reg.snapshot()["counters"]
        assert counters["plan.fit.lookup.vmem"] == fit * wide
        assert counters["plan.fit.lookup.gather"] \
            == fit * (len(widths) - wide)
    fits = [e for e in tracer.events() if e.name == "plan.fit"]
    assert [e.args["lookup"] for e in fits] == [route, route]
    z_ref = gee(prep, labels, 3, OPTS_ALL, backend="sparse_jax")
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), atol=1e-5)


@pytest.mark.pallas_interpret
def test_labels_span_once_per_fit_in_both_drivers(fresh_obs, sync_counter):
    """A bucketed fit's host label step runs under one ``plan.labels``
    span inside the plan's compute stage, before the fit's one
    ``plan.fit`` dispatch; ``plan.labels.vertices`` moves every fit,
    ``plan.labels.known`` only for host labels, and the span adds no wait
    on the device."""
    import jax.numpy as jnp

    tracer, reg = fresh_obs
    s = sample_sbm(120, seed=4)
    labels = np.asarray(s.labels).copy()
    labels[::3] = -1
    known = int(np.count_nonzero(labels >= 0))
    prep = PreparedGraph.wrap(s.edges)
    plan = GEEPlan.build(prep, s.num_classes, OPTS_ALL, backend="pallas")
    syncs_off, syncs_on, z_off, z_on = _traced_against_untraced(
        tracer, sync_counter, lambda: plan.execute(labels))
    np.testing.assert_array_equal(z_on, z_off)
    assert syncs_on == syncs_off == 0, (syncs_on, syncs_off)

    events = tracer.events()
    (stage,) = [e for e in events if e.name == "plan.stage.gee_spmm_fused"]
    (lab,) = [e for e in events if e.name == "plan.labels"]
    assert lab.args == {"n": 120, "k": s.num_classes}
    assert lab.depth == stage.depth + 1 and lab.tid == stage.tid
    assert stage.ts_us <= lab.ts_us
    assert lab.ts_us + lab.dur_us <= stage.ts_us + stage.dur_us + 1.0
    (fit,) = [e for e in events if e.name == "plan.fit"]
    assert fit.ts_us >= lab.ts_us + lab.dur_us - 1.0
    assert fit.depth == lab.depth

    counters = reg.snapshot()["counters"]        # three fits so far
    assert counters["plan.labels.vertices"] == 3 * 120
    assert counters["plan.labels.known"] == 3 * known
    # labels already on the device are never read back to count them
    plan.execute(jnp.asarray(labels))
    counters = reg.snapshot()["counters"]
    assert counters["plan.labels.vertices"] == 4 * 120
    assert counters["plan.labels.known"] == 3 * known
    assert [e.name for e in tracer.events()].count("plan.labels") == 2


def test_embedder_spans_name_resolve_open_and_epilogue(fresh_obs,
                                                       sbm_small, tmp_path):
    from repro.core.api import GEEEmbedder
    from repro.graph.io import BinaryEdgeWriter

    tracer, _ = fresh_obs
    tracer.enable()
    emb = GEEEmbedder(num_classes=sbm_small.num_classes, options=OPTS_ALL,
                      backend="auto")
    emb.fit(sbm_small.edges, sbm_small.labels).transform()
    (resolve,) = [e for e in tracer.events() if e.name == "plan.resolve"]
    assert resolve.args == {"backend": "auto", "resolved": emb.plan.backend,
                            "fused": emb.plan.fused}

    e = sbm_small.edges
    src = np.asarray(e.src)[: e.num_edges]
    dst = np.asarray(e.dst)[: e.num_edges]
    path = str(tmp_path / "g.geeb")
    with BinaryEdgeWriter(path, e.num_nodes, e.num_edges,
                          undirected=False) as w:
        w.append(src, dst)
    tracer.clear()
    emb = GEEEmbedder(num_classes=sbm_small.num_classes, options=OPTS_ALL,
                      chunk_edges=1024)
    emb.fit_transform_file(path, labels=sbm_small.labels)
    names = [ev.name for ev in tracer.events()]
    for name in ("fold.open", "fold.pass", "fold.window", "fold.epilogue"):
        assert name in names
    passes = {ev.args["phase"]: ev.args for ev in tracer.events()
              if ev.name == "fold.pass"}
    assert passes["scatter"]["edges"] == e.num_edges
    assert passes["scatter"]["windows"] == -(-e.num_edges // 1024)


def test_plan_cache_hit_tags(fresh_obs, sbm_small):
    tracer, _ = fresh_obs
    tracer.enable()
    prep = PreparedGraph.wrap(sbm_small.edges)
    plan = GEEPlan.build(prep, sbm_small.num_classes, OPTS_ALL)
    plan.execute(sbm_small.labels)                        # cold: misses
    first = [e for e in tracer.events() if e.name == "plan.execute"][-1]
    tracer.clear()
    plan.execute(sbm_small.labels)                        # warm: hits
    second = [e for e in tracer.events() if e.name == "plan.execute"][-1]
    assert first.args["cache_misses"] >= 1
    assert second.args["cache_misses"] == 0
    assert second.args["cache_hits"] >= 1
    warm_stages = [e for e in tracer.events()
                   if e.name.startswith("plan.stage.")]
    assert any(e.args.get("cached") for e in warm_stages)


def test_fold_window_spans_and_throughput(fresh_obs, sbm_small):
    tracer, reg = fresh_obs
    tracer.enable()
    prep = PreparedGraph.wrap(sbm_small.edges)
    z = gee(prep, sbm_small.labels, sbm_small.num_classes, OPTS_ALL,
            backend="chunked")
    assert np.asarray(z).shape[0] == sbm_small.edges.num_nodes
    windows = [e for e in tracer.events() if e.name == "fold.window"]
    assert windows and {e.args["phase"] for e in windows} == {"degrees",
                                                             "scatter"}
    degrees = sum(1 for e in windows if e.args["phase"] == "degrees")
    scatter = sum(1 for e in windows if e.args["phase"] == "scatter")
    snap = reg.snapshot()
    # each logical window counts once: the laplacian degree pre-pass is a
    # separate counter, never inflating fold.windows/fold.edges 2x
    assert snap["counters"]["fold.windows"] == scatter
    assert snap["counters"]["fold.windows.scatter"] == scatter
    assert snap["counters"]["fold.windows.degrees"] == degrees
    scatter_edges = sum(e.args["edges"] for e in windows
                        if e.args["phase"] == "scatter")
    assert snap["counters"]["fold.edges"] == scatter_edges > 0


# ---------------------------------------------------------------------------
# the overhead gate
# ---------------------------------------------------------------------------

def test_disabled_tracer_overhead_under_gate(sbm_small):
    prep = PreparedGraph.wrap(sbm_small.edges)
    labels, k = sbm_small.labels, sbm_small.num_classes

    def fit():
        return gee(prep, labels, k, OPTS_ALL)

    r = tracer_overhead_pct(fit, repeats=3, calibration_calls=20_000)
    assert r["span_count"] >= 3                   # instrumentation is live
    assert r["disabled_span_ns"] < 5_000          # ns-scale null path
    # the CI headline: disabled instrumentation costs <= 2% of a fit
    assert r["overhead_pct"] <= 2.0, r
    assert not get_tracer().enabled               # state restored


# ---------------------------------------------------------------------------
# recovery timeline
# ---------------------------------------------------------------------------

def _stream_to_disk(tmp_path, n=150, seed=5, batches=3):
    from repro.search.service import GEEDeltaServer
    from repro.serve.snapshot import GEESnapshotter

    s = sample_sbm(n, seed=seed)
    inc = IncrementalGEE.from_graph(s.edges, s.labels, s.num_classes,
                                    OPTS_ALL)
    snap = GEESnapshotter(str(tmp_path), every=10**9, keep_last=5)
    server = GEEDeltaServer(inc, flush_every=10**9, log=snap.log)
    rng = np.random.default_rng(seed)
    steps = []
    for b in range(batches):
        server.submit(edge_delta_from_numpy(rng.integers(0, n, 8),
                                            rng.integers(0, n, 8),
                                            rng.random(8)))
        server.flush()
        steps.append(snap.snapshot(inc, delta_server=server))
    server.submit(edge_delta_from_numpy(rng.integers(0, n, 8),
                                        rng.integers(0, n, 8),
                                        rng.random(8)))
    server.flush()                                 # tail past last snapshot
    snap.close()
    return inc, steps


def test_recover_emits_structured_timeline(fresh_obs, tmp_path):
    from repro.serve.snapshot import recover

    _, reg = fresh_obs
    inc, _ = _stream_to_disk(tmp_path)
    st = recover(str(tmp_path))
    np.testing.assert_array_equal(st.inc.embedding(), inc.embedding())
    events = [ev["event"] for ev in st.timeline]
    assert events == ["load_snapshot", "replay", "repair_index",
                      "recovered"] or events == ["load_snapshot", "replay",
                                                 "recovered"]
    by = {ev["event"]: ev for ev in st.timeline}
    assert by["load_snapshot"]["step"] == st.snapshot_step
    assert by["load_snapshot"]["skipped_steps"] == []
    assert by["replay"]["replayed_deltas"] == st.replayed_deltas == 1
    assert by["replay"]["bytes"] > 0
    assert by["recovered"]["ms"] >= by["replay"]["ms"]
    assert st.skipped_steps == ()
    snap = reg.snapshot()
    assert snap["counters"]["recover.runs"] == 1
    assert snap["counters"]["recover.snapshots_skipped"] == 0
    assert snap["gauges"]["wal.replay_bytes_per_sec"] > 0
    assert snap["histograms"]["recover.total_ms"]["count"] == 1


def test_recover_timeline_reports_corrupt_steps(fresh_obs, tmp_path):
    import os

    from repro.serve.snapshot import recover

    _, reg = fresh_obs
    inc, steps = _stream_to_disk(tmp_path)
    # corrupt the newest snapshot so recover falls back one step
    step_dir = os.path.join(str(tmp_path), "snapshots",
                            f"step_{steps[-1]:010d}")
    manifest = json.loads(
        open(os.path.join(step_dir, "manifest.json")).read())
    entry = sorted(manifest["index"].items())[0][1]
    path = os.path.join(step_dir, entry["file"])
    np.save(path, np.full_like(np.load(path), 7.0))

    st = recover(str(tmp_path))
    np.testing.assert_array_equal(st.inc.embedding(), inc.embedding())
    assert st.snapshot_step == steps[-2]
    assert st.skipped_steps == (steps[-1],)
    by = {ev["event"]: ev for ev in st.timeline}
    assert by["load_snapshot"]["skipped_steps"] == [steps[-1]]
    assert by["replay"]["replayed_deltas"] == st.replayed_deltas >= 2
    assert reg.snapshot()["counters"]["recover.snapshots_skipped"] == 1


def test_recover_cold_start_timeline(fresh_obs, tmp_path):
    from repro.serve.snapshot import DeltaLog, recover

    import os

    log = DeltaLog(os.path.join(str(tmp_path), "wal"))
    rng = np.random.default_rng(2)
    log.append([edge_delta_from_numpy(rng.integers(0, 20, 4),
                                      rng.integers(0, 20, 4),
                                      rng.random(4))])
    st = recover(str(tmp_path), cold_start={"num_nodes": 20,
                                            "num_classes": 2})
    events = [ev["event"] for ev in st.timeline]
    assert events[0] == "cold_start"
    assert events[-1] == "recovered"
    assert st.replayed_deltas == 1


# ---------------------------------------------------------------------------
# router + WAL metrics
# ---------------------------------------------------------------------------

def test_router_routed_counts_in_registry(fresh_obs):
    from repro.serve.replica import GEEReplica, ReplicaRouter

    _, reg = fresh_obs
    service, inc, s = _service_scenario(seed=3)
    service.close()
    from repro.search.index import ClassPartitionedIndex

    def mk(name, seed):
        st = sample_sbm(150, seed=3)
        rep_inc = IncrementalGEE.from_graph(st.edges, st.labels,
                                            st.num_classes, OPTS_ALL)
        idx = ClassPartitionedIndex.build(rep_inc.embedding(), st.labels,
                                          st.num_classes)
        return GEEReplica(rep_inc, idx, name=name, flush_every=4)

    router = ReplicaRouter([mk("r0", 0), mk("r1", 1)])
    rng = np.random.default_rng(4)
    for _ in range(6):
        router.read_rows(rng.integers(0, 150, 4), k=5)
    assert router.stats["reads"] == 6
    routed = router.stats["routed"]
    assert sum(routed.values()) == 6
    snap = reg.snapshot()["counters"]
    scope = router.stats.scope
    assert snap[f"{scope}.reads"] == 6
    routed_scope = router.stats["routed"].scope
    assert (snap[f"{routed_scope}.r0"] + snap[f"{routed_scope}.r1"]) == 6
    router.close()
    assert f"{scope}.reads" not in reg.snapshot()["counters"]


def test_wal_byte_counters(fresh_obs, tmp_path):
    from repro.serve.snapshot import DeltaLog

    _, reg = fresh_obs
    log = DeltaLog(str(tmp_path))
    rng = np.random.default_rng(6)
    log.append([edge_delta_from_numpy(rng.integers(0, 50, 8),
                                      rng.integers(0, 50, 8),
                                      rng.random(8))])
    snap = reg.snapshot()["counters"]
    appended = {k: v for k, v in snap.items()
                if k.endswith("appended_bytes")}
    assert appended and all(v > 0 for v in appended.values())
    log2 = DeltaLog(str(tmp_path))
    replayed = list(log2.replay(after_seq=-1))
    assert len(replayed) == 1
    snap = reg.snapshot()["counters"]
    replay_bytes = {k: v for k, v in snap.items()
                    if k.endswith("replayed_bytes")}
    assert any(v > 0 for v in replay_bytes.values())
