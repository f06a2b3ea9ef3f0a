"""From a JAX profiler trace to the numbers the per-layer readers use.

``capture`` turns the profiler's ``.xplane.pb`` into a plain dict, which is
also the format of the recorded trace the tests check the reduction on:

  {"devices": [{"name": "/device:TPU:0", "ops": [[name, start_ns, dur_ns], ...]}, ...],
   "host":    [[name, start_ns, dur_ns], ...]}

``devices`` holds one entry per chip: each plane named ``/device:<KIND>:<n>``
(a chip's other planes carry a suffix and are left out), with the events of
its ``XLA Ops`` line, the ops the chip ran; ``host``
holds the host spans whose names start with one of ``HOST_PREFIXES``: the
benchmark's own ``bench.*`` spans and the program's ``plan.*`` and
``fold.*`` spans.

``reduce_trace`` then gives, inside the window the ``bench.fit`` spans
cover: each device's busy time (the union of its op intervals), its idle
gaps named by the innermost host span open at the gap's middle, the idle
time under each host span (every gap cut at the host spans' edges, each
piece given to the innermost span open over it), its time in collective
ops, and the ops that took the most time.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

HOST_PREFIXES = ("bench.", "plan.", "fold.")
FIT_SPAN = "bench.fit"
COLLECTIVE_MARKS = ("reduce-scatter", "reduce_scatter", "all-reduce",
                    "all_reduce", "all-gather", "all_gather",
                    "collective-permute", "all-to-all", "psum")
OPS_LINE = "XLA Ops"
CHIP_PLANE = re.compile(r"/device:[A-Z_]+:\d+")


def capture(log_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in data.planes:
        if CHIP_PLANE.fullmatch(plane.name):
            ops = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                   for ln in plane.lines if ln.name == OPS_LINE
                   for ev in ln.events if ev.duration_ns > 0]
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
                         for ev in ln.events
                         if ev.name.startswith(HOST_PREFIXES)]
    return {"devices": devices, "host": host}


def describe(raw_dir: str) -> list[str]:
    """Plane and line names with event counts: a first look at a trace."""
    from jax.profiler import ProfileData

    out = []
    for path in glob.glob(os.path.join(raw_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for ln in plane.lines:
                evs = list(ln.events)
                names = sorted({e.name for e in evs})[:8]
                out.append(f"{plane.name} | {ln.name} | {len(evs)} | {names}")
    return out


def _union(intervals):
    """Sorted, merged [start, end] pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduced:
    window_s: float
    fits: int
    busy_s: list            # per device
    collective_s: list      # per device
    gaps: list              # [(span name, seconds)], all devices
    op_seconds: dict        # op name -> seconds, mean over devices
    # span name -> idle seconds under it as the innermost open span, summed
    # over devices; every host span name in the window has an entry, 0.0
    # where the devices were never idle under it
    idle_under: dict = dataclasses.field(default_factory=dict)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _span_at(host: list, t: float) -> str:
    """The innermost (shortest) host span open at time ``t``."""
    best, best_dur = "none", float("inf")
    for name, start, dur in host:
        if start <= t <= start + dur and dur < best_dur:
            best, best_dur = name, dur
    return best


def _innermost_segments(host: list):
    """The host spans' edges, sorted, and the innermost span open between
    each edge and the next (``"none"`` where none is), as ``_span_at``
    names it."""
    edges = sorted({t for _, start, dur in host for t in (start, start + dur)})
    index = {t: i for i, t in enumerate(edges)}
    opens: list = [[] for _ in edges]
    closes: list = [[] for _ in edges]
    for order, (name, start, dur) in enumerate(host):
        if dur <= 0:
            continue
        opens[index[start]].append((dur, order, name))
        closes[index[start + dur]].append((dur, order, name))
    names, open_now = [], set()
    for i in range(len(edges) - 1):
        open_now.difference_update(closes[i])
        open_now.update(opens[i])
        names.append(min(open_now)[2] if open_now else "none")
    return edges, names


def _idle_under(edges: list, names: list, g0: float, g1: float, out: dict):
    """Add the idle interval [g0, g1] (ns) to ``out`` in seconds, cut at
    the span edges inside it."""
    cuts = [g0] + edges[bisect.bisect_right(edges, g0):
                        bisect.bisect_left(edges, g1)] + [g1]
    for a, b in zip(cuts, cuts[1:]):
        i = bisect.bisect_right(edges, (a + b) / 2) - 1
        name = names[i] if 0 <= i < len(names) else "none"
        out[name] = out.get(name, 0.0) + (b - a) / 1e9


def reduce_trace(trace: dict) -> Reduced:
    fits = [h for h in trace["host"] if h[0] == FIT_SPAN]
    if not fits:
        raise ValueError(f"no {FIT_SPAN} span in the trace")
    lo = min(f[1] for f in fits)
    hi = max(f[1] + f[2] for f in fits)
    busy, coll, gaps = [], [], []
    op_ns: dict = {}
    span_edges, span_names = _innermost_segments(trace["host"])
    under = {name: 0.0 for name, start, dur in trace["host"]
             if start < hi and start + dur > lo}
    for dev in trace["devices"]:
        clipped = []
        c = 0.0
        for name, start, dur in dev["ops"]:
            s, e = max(start, lo), min(start + dur, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            op_ns[name] = op_ns.get(name, 0.0) + (e - s)
            if any(m in name.lower() for m in COLLECTIVE_MARKS):
                c += e - s
        merged = _union(clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        coll.append(c / 1e9)
        edges = [lo] + [x for seg in merged for x in seg] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((_span_at(trace["host"], (g0 + g1) / 2),
                             (g1 - g0) / 1e9))
                _idle_under(span_edges, span_names, g0, g1, under)
    ndev = max(len(trace["devices"]), 1)
    return Reduced(window_s=(hi - lo) / 1e9, fits=len(fits), busy_s=busy,
                   collective_s=coll, gaps=gaps,
                   op_seconds={k: v / ndev / 1e9 for k, v in op_ns.items()},
                   idle_under=under)
