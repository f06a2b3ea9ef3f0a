"""The ``label_idle_ms.fit`` reader, on the hand-made trace of
``test_idle_readers`` with a ``plan.labels`` span added where needed."""

import pytest

from harness import spec
from test_idle_readers import HAND, MS, _read


def test_label_idle_reads_gaps_under_the_label_span_only():
    """A ``plan.labels`` span at 30..40 ms, inside ``plan.bucket``, takes
    chip 0's 30..40 ms gap (the innermost span wins); the plan total that
    ``dispatch_idle_ms.fit`` reads stays the same."""
    host = HAND["host"] + [["plan.labels", 30 * MS, 10 * MS]]
    trace = dict(HAND, host=host)
    assert _read("label_idle_ms.fit", trace) == pytest.approx(10 / 4)
    assert _read("dispatch_idle_ms.fit", trace) == pytest.approx(26 / 4)


def test_label_idle_takes_its_part_of_a_gap_under_other_spans():
    """A ``plan.labels`` span at 55..65 ms covers the first 5 ms of chip
    0's 60..120 ms gap, whose middle lies under ``bench.fit``: the label
    step reads those 5 ms, and the plan total grows by them."""
    host = HAND["host"] + [["plan.labels", 55 * MS, 10 * MS]]
    trace = dict(HAND, host=host)
    assert _read("label_idle_ms.fit", trace) == pytest.approx(5 / 4)
    assert _read("dispatch_idle_ms.fit", trace) == pytest.approx(31 / 4)


def test_label_idle_is_zero_when_the_chips_are_busy_under_the_span():
    """A ``plan.labels`` span at 0..10 ms, where both chips are busy, is
    a reading of 0 idle ms, not a missing one."""
    host = HAND["host"] + [["plan.labels", 0 * MS, 10 * MS]]
    assert _read("label_idle_ms.fit", dict(HAND, host=host)) == 0.0


@pytest.mark.parametrize("keep", ["plan", "bench"])
def test_label_idle_is_missing_without_the_label_span(keep):
    """A program without the span gives no number, rather than 0: with
    the plan's other gaps still named, and with only the benchmark's own
    spans."""
    host = [h for h in HAND["host"] if keep == "plan"
            or h[0].startswith("bench.")]
    assert _read("label_idle_ms.fit", dict(HAND, host=host)) is None


def test_label_reader_is_listed_for_both_refit_cells():
    def names(cell):
        return {m["name"] for m in spec.load_cell(cell).per_layer}
    refit, ogbn = names("cl100k-l5.refit"), names("ogbn-products.refit")
    assert {"label_idle_ms.fit", "dispatch_idle_ms.fit"} <= ogbn & refit
    assert "label_idle_ms.fit" not in names("cl100k-l5.stream")
