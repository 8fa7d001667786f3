"""Runs controller cells: a closed loop of control intervals, each
one ``OnlineAllocator`` call from a host-side flow state to the rates
back on the host.

What it calls of the cell's deployment module: ``fabric(cfg, seed,
traffic)``, a ``benchlib.deploy.Fabric`` (R, cap, kind); and
``flow_states(cfg, fab, traffic)``, the traffic's ``n_states`` flow
states, each the five FlowState fields ([F] float32). The configuration
gives ``controller_interval_s`` and ``backfill_iters``."""
from __future__ import annotations

import numpy as np

from benchlib import reference
from benchlib.harness import (CompileWatch, GuardError, TracedWindow,
                              check, device_record, log, now, span)

LAYERS = ("controller solve", "device")

# a traced run traces the window's first seconds only: each solve runs
# some thousand device operations, and a trace of the whole window would
# be too large to read within the run
TRACE_S = 2.0


def run(cell, args, t_start: float, devices, hooks: dict) -> tuple:
    from repro.core.allocator import OnlineAllocator
    from repro.core.flowstate import FlowState

    cfg, tr, dep = cell.config, cell.traffic, cell.deployment
    dt = float(cfg["controller_interval_s"])
    iters = int(cfg["backfill_iters"])
    fab = dep.fabric(cfg, args.seed, tr)
    alloc = OnlineAllocator(fab.R, fab.cap, fab.kind, dt=dt,
                            backfill_iters=iters)
    solve = hooks.get("wrap_solve", lambda f: f)(alloc)
    states = dep.flow_states(cfg, fab, tr)
    nnz, (F, L) = int(np.count_nonzero(fab.R)), fab.R.shape
    log(f"controller: L={L} F={F} nnz(R)={nnz} states={len(states)} "
        f"dt={dt} s, solver {alloc.solver}")
    for st in states:                       # warm-up: the one shape
        np.asarray(solve(FlowState(*st)))
    watch = CompileWatch()
    compiles0 = watch.count
    setup_s = now() - t_start

    lat: list[float] = []
    answers: list[np.ndarray] = []
    traced = TracedWindow(args.trace)
    n_traced = 0
    w0 = now()
    while True:
        st = states[len(answers) % len(states)]
        with span("bench.solve", traced.on):
            t0 = now()
            answers.append(np.asarray(solve(FlowState(*st))))
            t1 = now()
        lat.append(t1 - t0)
        if traced.on and t1 - w0 >= TRACE_S:
            traced.stop()
            n_traced = len(answers)
        if t1 - w0 >= args.seconds:
            break
    w1 = now()
    if traced.on:
        traced.stop()
        n_traced = len(answers)
    if watch.count != compiles0:
        raise GuardError(f"{watch.count - compiles0} compilation(s) inside "
                         f"the window")
    trace = traced.reduce() if args.trace else None
    dev = device_record(devices, trace)

    ms = np.asarray(lat) * 1e3
    e2e = {"setup_s": setup_s,
           "solve_p50_ms": float(np.percentile(ms, 50)),
           "solve_p95_ms": float(np.percentile(ms, 95))}
    log(f"window: {len(answers)} solves in {w1 - w0:.3f} s; p50 "
        f"{e2e['solve_p50_ms']:.4f} ms, p95 {e2e['solve_p95_ms']:.4f} ms, "
        f"p99 {np.percentile(ms, 99):.4f}, max {ms.max():.4f}, "
        f"solves over 10 ms {int(np.sum(ms > 10.0))}")
    ctx = {"trace": trace, "n_solves": len(answers),
           "n_traced": n_traced,
           "work": {"nnz": nnz, "F": F, "L": L, "backfill_iters": iters},
           "device_kind": devices[0].device_kind}

    checks, n_bad = compare(fab, states, answers, dt, iters, tr["limits"])
    return e2e, ctx, checks, dev, len(answers), n_bad


def control(cell, seed: int) -> dict:
    """The checks of the control: the plain reference at ``high`` put in
    the program's place for every flow state of the cell."""
    cfg, tr, dep = cell.config, cell.traffic, cell.deployment
    fab = dep.fabric(cfg, seed, tr)
    states = dep.flow_states(cfg, fab, tr)
    dt = float(cfg["controller_interval_s"])
    iters = int(cfg["backfill_iters"])
    answers = [reference.allocate_ref(fab.R, fab.cap, fab.kind, st, dt,
                                      reference.Arith("high"),
                                      backfill_iters=iters)
               for st in states]
    checks, _ = compare(fab, states, answers, dt, iters, tr["limits"])
    return checks


def compare(fab, states, answers, dt, backfill_iters, limits):
    """Every answer of the window against the reference solve of its flow
    state. Two numbers: the widest rate gap of any answer (MB/s), and the
    most any answer loads a link above its capacity (a share of the
    capacity)."""
    ar = reference.Arith("exact")
    fi, li = np.nonzero(fab.R > 0)
    cap64 = np.asarray(fab.cap, np.float64)
    refs: dict[int, np.ndarray] = {}
    seen: set = set()
    gap = over = 0.0
    n_bad = 0
    for j, x in enumerate(answers):
        k = j % len(states)
        key = (k, x.tobytes())
        if key in seen:                    # bitwise the same answer again
            continue
        seen.add(key)
        if k not in refs:
            refs[k] = reference.allocate_ref(fab.R, fab.cap, fab.kind,
                                             states[k], dt, ar,
                                             backfill_iters=backfill_iters)
        x64 = np.asarray(x, np.float64)
        if x64.shape != refs[k].shape or not np.all(np.isfinite(x64)):
            n_bad += 1
            gap = over = float("inf")
            continue
        gap = max(gap, float(np.abs(x64 - refs[k]).max()))
        load = np.bincount(li, weights=x64[fi], minlength=cap64.shape[0])
        over = max(over, float((load / cap64).max() - 1.0))
    log(f"compared {len(seen)} distinct answers of {len(answers)} against "
        f"{len(refs)} reference solves")
    return {"rate_gap_max_mb_s": check(gap, limits["rate_gap_max_mb_s"]),
            "link_overload": check(over, limits["link_overload"])}, n_bad
