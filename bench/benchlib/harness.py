"""Pieces every kind of cell shares: the compile watch, the device record, the
traced window and the result line."""
from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time

from benchlib.spec import ROOT

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class GuardError(RuntimeError):
    """A run that measured something else than the cell asks for: it
    prints no result and exits non-zero."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """Counts JAX's backend compilations (persistent-cache loads
    included) from its monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1


def device_record(devices, trace: dict | None = None) -> dict:
    """The device as JAX reports it, with the peak memory of the fullest
    chip and, for a traced run, the busy and window seconds."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        rec["busy_s"] = trace["busy_s"]
        rec["window_s"] = trace["window_s"]
    return rec


class TracedWindow:
    """``jax.profiler`` trace of (the first part of) the measured window,
    written into the checkout (``.bench_trace/``) and removed once read."""

    def __init__(self, enabled: bool):
        self.on = False
        self.path = None
        if enabled:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans, not every call
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self.on = True

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        jax.profiler.stop_trace()
        self.on = False
        found = []
        for dirpath, _, files in os.walk(TRACE_DIR):
            found += [os.path.join(dirpath, f) for f in files
                      if f.endswith(".xplane.pb")]
        self.path = sorted(found)[-1] if found else None

    def reduce(self) -> dict:
        from benchlib import tracing
        try:
            return tracing.reduce(self.path)
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)


def span(name: str, enabled: bool):
    """A host span of the benchmark on the profiler's clock, when traced."""
    if not enabled:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output,
    with the checks as its last key."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['ok'] else 'FAILED'}")
    line = dict(result)
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    print(json.dumps(line), flush=True)


def check(value: float, limit: float) -> dict:
    return {"value": float(value), "limit": float(limit),
            "ok": bool(math.isfinite(value) and value <= limit)}


def now() -> float:
    return time.perf_counter()
