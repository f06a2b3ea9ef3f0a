"""The reduction from a trace to the per-layer numbers, checked on a small
hand-made trace whose answers are worked out below."""

import json
from pathlib import Path

import pytest

from harness import spec, tracing, work
from harness.layers import LayerContext, idle_share, roofline_share

MS = 1e6    # ns per ms

HAND = {
    "host": [["bench.fit", 0 * MS, 100 * MS],
             ["bench.fit", 100 * MS, 100 * MS],
             ["plan.stage.bucketed_ell", 10 * MS, 40 * MS],
             ["bench.setup", -50 * MS, 40 * MS]],
    "devices": [
        {"name": "/device:TPU:0",
         "ops": [["fusion.1", 0 * MS, 20 * MS],
                 ["fusion.2", 10 * MS, 20 * MS],       # overlaps fusion.1
                 ["kernel", 60 * MS, 20 * MS],
                 ["reduce-scatter.3", 150 * MS, 20 * MS],
                 ["fusion.9", 250 * MS, 10 * MS],      # after the window
                 ["fusion.0", -30 * MS, 40 * MS]]},    # straddles its start
        {"name": "/device:TPU:1",
         "ops": [["kernel", 0 * MS, 200 * MS]]},
    ],
}


def test_busy_is_the_union_inside_the_window():
    r = tracing.reduce_trace(HAND)
    assert r.fits == 2
    assert r.window_s == pytest.approx(0.2)
    # device 0: [0,30] + [60,80] + [150,170] ms; device 1: the whole window
    assert r.busy_s == pytest.approx([0.07, 0.2])
    assert r.mean_busy_s == pytest.approx(0.135)


def test_gaps_are_named_by_the_innermost_open_span():
    r = tracing.reduce_trace(HAND)
    assert sorted(r.gaps, key=lambda g: -g[1]) == [
        ("bench.fit", pytest.approx(0.07)),          # 80..150 ms
        ("plan.stage.bucketed_ell", pytest.approx(0.03)),   # 30..60 ms
        ("bench.fit", pytest.approx(0.03)),          # 170..200 ms
    ]
    top = r.breakdown(top=2)
    assert top["idle_gaps"] == [["bench.fit", pytest.approx(0.07)],
                                ["plan.stage.bucketed_ell",
                                 pytest.approx(0.03)]]


def test_collectives_and_op_totals():
    r = tracing.reduce_trace(HAND)
    assert r.collective_s == pytest.approx([0.02, 0.0])
    # per device mean: kernel (20 + 200) / 2 ms
    assert r.op_seconds["kernel"] == pytest.approx(0.11)
    assert r.op_seconds["fusion.0"] == pytest.approx(0.005)
    assert "fusion.9" not in r.op_seconds
    assert r.breakdown()["device_ops"][0] == ["kernel", pytest.approx(0.11)]


def test_shares_from_the_work_function_and_peaks():
    r = tracing.reduce_trace(HAND)
    w = work.fit_work(num_nodes=1000, num_edges=10_000, num_classes=5)
    assert w == {"bytes": 8.0 * 20_000 + 4.0 * 1000 + 4.0 * 5000,
                 "flops": 2.0 * 20_000 + 3.0 * 5000}
    peak = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    ctx = LayerContext(r, w, peak, [])
    least = max(w["flops"] / 2e12, w["bytes"] / 2e9)   # over two chips
    assert roofline_share(ctx) == pytest.approx(100 * 2 * least / 0.135)
    assert idle_share(ctx) == pytest.approx(100 * (1 - 0.135 / 0.2))


def test_peaks_are_keyed_by_device_kind():
    assert work.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_readers_are_found_by_name():
    r = tracing.reduce_trace(HAND)
    spans = [("plan.stage.bucketed_ell", 0.5, {"kind": "prep"}),
             ("plan.stage.gee_spmm_fused", 9.0, {"kind": "compute"})]
    ctx = LayerContext(r, work.fit_work(1000, 10_000, 5),
                       {"flops_per_s": 1e12, "bytes_per_s": 1e9}, spans)
    assert spec.load_reader("prep_ms.fit")(ctx) == pytest.approx(0.25)
    assert spec.load_reader("idle_share.stream")(ctx) == idle_share(ctx)
    nothing = LayerContext(r, ctx.work, ctx.peak, [])
    assert spec.load_reader("prep_ms.fit")(nothing) is None
