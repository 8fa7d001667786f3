"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* the window: from the start of the benchmark's first host span
  (``bench.*`` annotation) to the end of its last;
* busy: the union of the intervals in which an operation ran on a chip
  (the device plane's ``XLA Ops`` line), clipped to the window, averaged
  over the chips;
* per-executable device time: the ``XLA Modules`` line, summed over the
  chips by module name without its run suffix (``jit_allocate(17)`` ->
  ``jit_allocate``);
* the device operations that took most time, and the longest idle gaps of
  the first chip, each named by the host span that overlaps it most.
"""
from __future__ import annotations

import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
_RUN_SUFFIX = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    return _RUN_SUFFIX.sub("", name)


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def device_planes(pd):
    """Planes of the chips: those with an ``XLA Ops`` line."""
    out = []
    for pl in pd.planes:
        if not pl.name.startswith("/device:"):
            continue
        if any(ln.name == OPS_LINE for ln in pl.lines):
            out.append(pl)
    return sorted(out, key=lambda p: p.name)


def _host_events(pd):
    for pl in pd.planes:
        if pl.name == HOST_PLANE:
            for ln in pl.lines:
                for ev in ln.events:
                    yield ln.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce(path: str, top: int = 10) -> dict:
    """Reduce the trace at ``path``; the window is read from the
    ``bench.*`` spans in the trace itself."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), top=top)


def reduce_profile(pd, top: int = 10) -> dict:
    host = list(_host_events(pd))
    spans = [(s, e) for _, n, s, e in host if n.startswith(SPAN_PREFIX)]
    chips = device_planes(pd)
    if not chips:
        raise ValueError("trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    if spans:
        lo = min(s for s, _ in spans)
        hi = max(e for _, e in spans)
    else:
        lo, hi = float("inf"), float("-inf")
        for pl in chips:
            for ln in pl.lines:
                for ev in ln.events:
                    lo = min(lo, ev.start_ns)
                    hi = max(hi, ev.start_ns + ev.duration_ns)
    window_ns = max(hi - lo, 0.0)

    busy_ns = []
    op_ns: dict[str, float] = defaultdict(float)
    mod_ns: dict[str, float] = defaultdict(float)
    mod_n: dict[str, int] = defaultdict(int)
    first_busy = None
    for pl in chips:
        ivs = []
        for ln in pl.lines:
            if ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in ln.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if e <= s:
                    continue
                if ln.name == OPS_LINE:
                    ivs.append((s, e))
                    op_ns[ev.name] += e - s
                else:
                    mod_ns[module_name(ev.name)] += e - s
                    mod_n[module_name(ev.name)] += 1
        merged = _union(ivs)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
    n = len(chips)
    gaps = []
    prev = lo
    for s, e in (first_busy or []) + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for gs, ge in gaps[:top]:
        best, best_ov = "host: no span", 0.0
        for _, name, s, e in host:
            if name.startswith(SPAN_PREFIX):
                continue
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append([best, (ge - gs) * 1e-9])
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / n * 1e-9,
        "n_chips": n,
        "modules_s": {k: v * 1e-9 for k, v in mod_ns.items()},
        "modules_n": dict(mod_n),
        "device_ops": [[k, v / n * 1e-9] for k, v in ops],
        "idle_gaps": named,
    }


def module_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """Device seconds (summed over the chips) and runs of the modules
    whose name matches ``pattern``."""
    rx = re.compile(pattern)
    secs = sum(v for k, v in trace["modules_s"].items() if rx.search(k))
    runs = sum(v for k, v in trace["modules_n"].items() if rx.search(k))
    return secs, runs
