"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line."""

from __future__ import annotations

import json
import random
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext

import numpy as np

from harness import check, spec, tracing, work
from harness.layers import LayerContext
from harness.traffic import Mix


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer TPU chips than the cell asks for."""


def log(**row) -> None:
    print(json.dumps(row), file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """The program's fixed persistent cache, with every program kept,
    however short its compile or small its entry."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} x {devices[0].platform}")
    return devices


class _CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",)
    DURATIONS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name in self.EVENTS:
            self.count += 1

    def _duration(self, name, _secs, **_):
        if name in self.DURATIONS:
            self.count += 1


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, save_trace: str | None = None) -> dict:
    """Run the cell once; return the result line's object."""
    enable_compile_cache()
    devices = require_devices(cell.chips)

    import jax

    seed = int(seed) % (1 << 64)
    mix = Mix(cell.config, cell.traffic, seed)
    try:
        return _run(cell, mix, devices, seed, seconds, trace, t_start, jax,
                    save_trace)
    finally:
        mix.close()


def _run(cell, mix, devices, seed, seconds, trace, t_start, jax,
         save_trace):
    log(cell=cell.name, seed=seed, seconds=seconds, trace=int(trace),
        platform=devices[0].platform, device_kind=devices[0].device_kind,
        devices=len(devices))
    compiles = _CompileCounter()
    pieces = mix.setup()
    log(setup=pieces, resolved=mix.resolved)

    keep = int(cell.traffic["check_fits"])
    rng = random.Random(seed)
    kept: list = []                       # reservoir of (fit index, Z)
    tracer = None
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="gee-trace-")
        if cell.traffic.get("program_spans"):
            from repro.obs import trace as obs_trace

            tracer = obs_trace.get_tracer()
            tracer.clear()
            tracer.enable()
        jax.profiler.start_trace(trace_dir)
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = None

    compiles_before = compiles.count
    setup_s = time.perf_counter() - t_start
    fits = 0
    t0 = time.perf_counter()
    while True:
        with (annotate(tracing.FIT_SPAN) if annotate else nullcontext()):
            z = jax.block_until_ready(mix.fit(fits))
        if len(kept) < keep:
            kept.append((fits, z))
        else:
            j = rng.randrange(fits + 1)
            if j < keep:
                kept[j] = (fits, z)
        del z
        fits += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    window_compiles = compiles.count - compiles_before

    program_spans = []
    if trace:
        jax.profiler.stop_trace()
        if tracer is not None:
            program_spans = [(e.name, e.dur_us / 1e3, dict(e.args))
                             for e in tracer.events()]
            tracer.disable()
            tracer.clear()
    device = _device_info(devices)

    # the window is closed: fetch the kept answers, free the program's
    # state, and only then run the reference
    answers = [(i, np.asarray(z)) for i, z in sorted(kept,
                                                     key=lambda t: t[0])]
    kept.clear()
    mix.release_program_state()
    t_ref = time.perf_counter()
    ref = mix.reference()
    blocks = ref.blocks(mix.k)
    readings = []
    for i, z in answers:
        y = mix.labels(i)
        readings.append(check.answer_gaps(
            z, lambda lo, hi: ref.embed_rows(y, mix.k, lo, hi), blocks))
    del ref
    correct, failed, shown = check.verdict(readings, cell.limits)
    log(checked_fits=[i for i, _ in answers], readings=readings,
        reference_s=time.perf_counter() - t_ref,
        reference_blocks=len(blocks), window_compiles=window_compiles)

    out = {"correct": correct, "attempted": fits, "failed": failed}
    if trace:
        raw = tracing.capture(trace_dir)
        if save_trace:
            shutil.copytree(trace_dir, save_trace, dirs_exist_ok=True)
            with open(f"{save_trace}/captured.json", "w") as f:
                json.dump(dict(raw, planes=tracing.describe(trace_dir)), f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = tracing.reduce_trace(raw)
        ctx = LayerContext(
            reduced=reduced,
            work=work.fit_work(mix.n, mix.e, mix.k),
            peak=work.peaks(devices[0].device_kind),
            program_spans=program_spans)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        device["busy_s"] = reduced.mean_busy_s
        device["window_s"] = reduced.window_s
        out["device"] = device
        out["breakdown"] = reduced.breakdown()
    else:
        rate = mix.e * fits / window_s
        values = {"setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] != "setup_s":
                values[m["name"]] = rate
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["setup"] = dict(pieces, setup_s=setup_s, window_s=window_s,
                        window_compiles=window_compiles)
    out["checks"] = shown
    for name, v in shown.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    return out
