"""The idle-attribution readers, checked on a small hand-made trace whose
answers are worked out below: two fits on two chips, idle gaps under the
program's plan and fold spans."""

import pytest

from harness import spec, tracing
from harness.layers import LayerContext

MS = 1e6    # ns per ms

# Two 100 ms fits.  Host spans (innermost wins at a gap's middle):
#   plan.bucket        10..40 ms, with plan.bucket.scale 10..20 inside
#   fold.prefetch_wait 120..140 ms, fold.window 150..190 ms
#   fold.epilogue      190..200 ms
HAND = {
    "host": [["bench.fit", 0 * MS, 100 * MS],
             ["bench.fit", 100 * MS, 100 * MS],
             ["plan.bucket", 10 * MS, 30 * MS],
             ["plan.bucket.scale", 10 * MS, 10 * MS],
             ["fold.prefetch_wait", 120 * MS, 20 * MS],
             ["fold.window", 150 * MS, 40 * MS],
             ["fold.epilogue", 190 * MS, 10 * MS]],
    "devices": [
        # chip 0 idle: 10..20 (scale), 30..40 (bucket), 60..120 (bench.fit),
        # 125..135 (prefetch_wait), 160..170 (window), 192..198 (epilogue)
        {"name": "/device:TPU:0",
         "ops": [["f", 0 * MS, 10 * MS], ["f", 20 * MS, 10 * MS],
                 ["f", 40 * MS, 20 * MS], ["f", 120 * MS, 5 * MS],
                 ["f", 135 * MS, 25 * MS], ["f", 170 * MS, 22 * MS],
                 ["f", 198 * MS, 2 * MS]]},
        # chip 1 idle: 12..18 (scale), 126..134 (prefetch_wait)
        {"name": "/device:TPU:1",
         "ops": [["g", 0 * MS, 12 * MS], ["g", 18 * MS, 108 * MS],
                 ["g", 134 * MS, 66 * MS]]},
    ],
}


def _ctx(trace):
    return LayerContext(tracing.reduce_trace(trace), {}, {}, [])


def _read(name, trace):
    return spec.load_reader(name)(_ctx(trace))


def test_dispatch_idle_sums_plan_gaps_per_device_and_fit():
    # plan gaps: chip 0 10 + 10 ms, chip 1 6 ms; / 2 devices / 2 fits
    assert _read("dispatch_idle_ms.fit", HAND) == pytest.approx(26 / 4)


def test_stage_wait_sums_prefetch_gaps_only():
    # fold.prefetch_* gaps: chip 0 10 ms, chip 1 8 ms; fold.window and
    # fold.epilogue gaps are fold gaps but not waits on the pipeline
    assert _read("stage_wait_ms.stream", HAND) == pytest.approx(18 / 4)


def test_stage_wait_is_zero_when_fold_gaps_hold_no_prefetch_wait():
    host = [h for h in HAND["host"] if h[0] != "fold.prefetch_wait"]
    trace = dict(HAND, host=host)
    assert _read("stage_wait_ms.stream", trace) == 0.0


@pytest.mark.parametrize("name", ["dispatch_idle_ms.fit",
                                  "stage_wait_ms.stream"])
def test_lost_program_spans_read_as_missing(name):
    """A trace with only the benchmark's own spans (a program that emits
    none) gives no number, rather than 0."""
    host = [h for h in HAND["host"] if h[0].startswith("bench.")]
    assert _read(name, dict(HAND, host=host)) is None


@pytest.mark.parametrize("shift_ms", [0, 3, 55])
def test_idle_under_spans_adds_up_to_the_gaps(shift_ms):
    """Cutting the gaps at the host spans' edges loses no idle time,
    also where every program span is moved off the gaps' edges."""
    host = [[n, s + (0 if n.startswith("bench.") else shift_ms * MS), d]
            for n, s, d in HAND["host"]]
    r = tracing.reduce_trace(dict(HAND, host=host))
    assert sum(r.idle_under.values()) == pytest.approx(
        sum(s for _, s in r.gaps))
    assert set(r.idle_under) >= {"bench.fit", "plan.bucket"}


def test_readers_are_listed_for_their_cells():
    refit = {m["name"] for m in spec.load_cell("cl100k-l5.refit").per_layer}
    stream = {m["name"] for m in spec.load_cell("cl100k-l5.stream").per_layer}
    assert "dispatch_idle_ms.fit" in refit - stream
    assert "stage_wait_ms.stream" in stream - refit
