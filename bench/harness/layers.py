"""What a per-layer reader (bench/layer_metrics/<metric>.py) is given, and
the arithmetic several readers share.

A reader is ``read(ctx) -> float | None``.  It returns None where it finds
nothing to read, and the harness then leaves the metric out of the line.
A share of a roofline is never returned as 0 in place of nothing.
"""

from __future__ import annotations

import dataclasses

from harness.tracing import Reduced
from harness.work import least_seconds


@dataclasses.dataclass
class LayerContext:
    reduced: Reduced             # the traced window
    work: dict                   # least work of one fit (harness.work)
    peak: dict                   # the chip's peaks (harness/peaks.json)
    program_spans: list          # [(name, dur_ms, args)] of the program's tracer


def roofline_share(ctx: LayerContext) -> float | None:
    """Least time of the window's fits over the mean per-device busy time,
    in %: the work spread over all the cell's chips, at their peaks."""
    r = ctx.reduced
    if r.fits == 0 or r.mean_busy_s <= 0:
        return None
    chips = max(len(r.busy_s), 1)
    least = r.fits * least_seconds(ctx.work, ctx.peak, chips)
    return 100.0 * least / r.mean_busy_s


def idle_share(ctx: LayerContext) -> float | None:
    """1 - mean device busy / traced window, in %."""
    r = ctx.reduced
    if r.window_s <= 0 or not r.busy_s:
        return None
    return 100.0 * (1.0 - r.mean_busy_s / r.window_s)


def span_ms_per_fit(ctx: LayerContext, prefix: str, **tags) -> float | None:
    """Summed duration of the program's spans named ``prefix*`` whose tags
    include ``tags``, per fit of the window; None without such spans."""
    hits = [dur for name, dur, args in ctx.program_spans
            if name.startswith(prefix)
            and all(args.get(k) == v for k, v in tags.items())]
    if not hits or ctx.reduced.fits == 0:
        return None
    return sum(hits) / ctx.reduced.fits
