"""A ``nexmark_campaign`` deployment: NEXmark Query 5 ("hot items") as a
Storm topology on machines behind one SDN switch, its event source shaped
SINE or SQUARE in time (Apache Beam's ``NexmarkConfiguration``
``rateShape``).

A scenario is kept as plain parameters (capacity, rate shape, period,
phase, peak ratio, the SQUARE steps' times). The program's scenario is
built from them with its public constructors (:func:`program_scenario`:
``nexmark_q5``, ``big_switch``, ``LoadSchedule``); the reference builds its
own input arrays (``reference.testbed_arrays``) and its own per-tick
generation rates from the same parameters, and runs this module's
:func:`simulate_ref`: ``reference.simulate_ref`` with the generation rate
read per tick.

What the campaign loop calls: :func:`corpus`, :func:`program_scenario`,
:func:`reference_row`.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from benchlib import reference
from benchlib.reference import (EPS, INTERNAL_RATE, LAT_CAP, Arith,
                                allocate_ref, campaign_metrics, maxmin_ref)


@dataclasses.dataclass
class Q5Scenario:
    """One scenario of a NEXmark Q5 campaign on a one-switch testbed."""

    name: str
    graph: object              # Q5's parallelized instance DAG
    placement: np.ndarray      # [I] machine of every instance
    n_machines: int
    cap: float                 # MB/s of every machine link
    shape: str                 # "SINE" or "SQUARE"
    ratio: float               # nextEventRate / firstEventRate
    period_s: float            # ratePeriodSec
    phase: float               # rad
    # SQUARE: (t0, t1) of every half-period at the next rate, then
    # (inf, inf), which never starts, up to the configuration's slot count
    steps: list = dataclasses.field(default_factory=list)


def _machines(cfg: dict) -> int:
    topo = cfg["topology"]
    if topo["constructor"] != "big_switch":
        raise ValueError(f"testbed constructor {topo['constructor']!r} "
                         f"unknown")
    return int(topo["n_machines"])


def square_steps(period_s: float, phase: float, horizon_s: float) -> list:
    """The half-periods at the next rate within [0, horizon): those where
    the period fraction t / period + phase / 2pi, modulo 1, is at least
    one half."""
    off = phase / (2.0 * math.pi)
    out = []
    k = math.floor(off) - 1
    while True:
        t0 = (k + 0.5 - off) * period_s
        if t0 >= horizon_s:
            return out
        t1 = t0 + 0.5 * period_s
        if t1 > 0.0:
            out.append((max(t0, 0.0), t1))
        k += 1


def step_slots(cfg: dict) -> int:
    """Steps of every SQUARE scenario: the most half-periods at the next
    rate that the shortest period can place in the horizon. Every SQUARE
    scenario then has one shape, and the campaign's buckets, chunks and
    padded rows are the same whatever the seed draws."""
    return int(float(cfg["horizon_s"]) // float(cfg["load"]["period_s"][0])) + 1


def corpus(cfg: dict, seed: int) -> list[Q5Scenario]:
    """The campaign corpus: ``n_scenarios`` scenarios, scenario k of rate
    shape k % 2 and capacity (k // 2) % 3, its period, phase and peak
    ratio drawn from ``seed``. The events' base rate is the first rate
    (SQUARE) or the midpoint of the first and the next (SINE)."""
    from repro.streams.app import parallelize
    from repro.streams.workloads import nexmark_q5

    n_mach = _machines(cfg)
    rng = np.random.default_rng(seed)
    caps = [float(c) for c in cfg["capacities_mb_s"]]
    shapes = list(cfg["rate_shapes"])
    ld = cfg["load"]
    first = float(ld["first_events_per_s"])
    horizon = float(cfg["horizon_s"])
    slots = step_slots(cfg)
    S, C = len(shapes), len(caps)
    out = []
    for k in range(int(cfg["n_scenarios"])):
        shape = shapes[k % S]
        period = float(rng.uniform(*ld["period_s"]))
        phase = float(rng.uniform(*ld["phase_rad"]))
        ratio = float(rng.uniform(*ld["next_over_first"]))
        if shape == "SINE":
            base, steps = 0.5 * first * (1.0 + ratio), []
        elif shape == "SQUARE":
            steps = square_steps(period, phase, horizon)
            base = first
            steps += [(math.inf, math.inf)] * (slots - len(steps))
        else:
            raise ValueError(f"rate shape {shape!r} unknown")
        g = parallelize(nexmark_q5(base, int(cfg["parallelism"])), seed=seed)
        if (g.n_instances, g.n_flows) != (cfg["instances"], cfg["flows"]):
            raise ValueError(f"Q5 has I={g.n_instances}, F={g.n_flows}; "
                             f"the configuration states I="
                             f"{cfg['instances']}, F={cfg['flows']}")
        out.append(Q5Scenario(
            f"Q5_{shape}{k}", g, np.arange(g.n_instances) % n_mach, n_mach,
            caps[(k // S) % C], shape, ratio, period, phase, steps))
    return out


def program_scenario(sc: Q5Scenario):
    """The program's ``Scenario`` of one Q5 scenario, built with its
    public constructors."""
    from repro.net.topology import big_switch
    from repro.streams.load import LoadSchedule
    from repro.streams.scenarios import Scenario

    src = np.flatnonzero(sc.graph.gen_rate > 0)
    load = LoadSchedule.empty(sc.graph.n_instances)
    if sc.shape == "SINE":
        load = load.with_sine(sc.period_s, 1.0, sc.ratio, src,
                              phase=sc.phase)
    for t0, t1 in sc.steps:
        load = load.with_steps(t0, t1, sc.ratio, src)
    return Scenario(sc.name, sc.graph, big_switch(sc.n_machines, sc.cap),
                    sc.placement, load=load)


def gen_grid(sc: Q5Scenario, s: dict, ts: np.ndarray, ar: Arith
             ) -> np.ndarray:
    """Generation rate of every instance at every tick [T, I]: the
    sources' base rate times the SINE factor 1 + a sin(wt + phase),
    a = (r - 1)/(r + 1), or times r over the SQUARE steps. Amplitude,
    frequency, phase, step times and r are float32 data; steps are
    decided in float32."""
    f32 = np.float32
    gen = ar.f(s["gen_rate"])
    if sc.shape == "SINE":
        a = f32((sc.ratio - 1.0) / (sc.ratio + 1.0))
        w = f32(2.0 * np.pi / sc.period_s)
        scale = 1.0 + ar.f(a) * np.sin(ar.f(w) * ar.f(ts) + ar.f(f32(sc.phase)))
    else:
        on = np.zeros(ts.shape[0], bool)
        for t0, t1 in sc.steps:
            on |= (ts >= f32(t0)) & (ts < f32(t1))
        scale = np.where(on, ar.f(f32(sc.ratio)), 1.0)
    scale = np.where(gen[None, :] > 0, ar.f(scale)[:, None], 1.0)
    return np.maximum(gen[None, :] * scale, 0.0).astype(ar.dtype)


def reference_row(sc: Q5Scenario, policy: str, kw: dict,
                  precision: str = "exact") -> np.ndarray:
    """The plain reference's metric row ([7], ``reference.METRICS``) of
    one scenario under the campaign settings ``kw``, at ``precision``."""
    ar = Arith(precision)
    n_ticks = int(round(kw["seconds"] / kw["dt"]))
    s = reference.testbed_arrays(sc.graph, sc.placement, sc.n_machines,
                                 sc.cap)
    ts = np.arange(n_ticks, dtype=np.float32) * np.float32(kw["dt"])
    return simulate_ref(s, gen_grid(sc, s, ts, ar), policy, n_ticks,
                        kw["dt"], kw["upd_every"], kw["qcap"], ar)


def simulate_ref(s: dict, gen_sched: np.ndarray, policy: str, n_ticks: int,
                 dt: float, upd_every: int, qcap: float, ar: Arith,
                 t_event: float = 0.0) -> np.ndarray:
    """``reference.simulate_ref`` with the generation rate of tick t read
    from ``gen_sched[t]`` ([T, I]) in place of the constant
    ``s["gen_rate"]``; with that rate in every row it is the same run."""
    if policy not in ("tcp", "appaware"):
        raise NotImplementedError(f"no reference for policy {policy!r}")
    if s["route_bank"].shape[0]:
        raise NotImplementedError("no reference for mid-run rerouting")
    f = ar.f
    R, M_in = f(s["R"]), f(s["M_in"])
    on = R > 0
    F, L = R.shape
    dst, src = s["dst_of_flow"], s["src_of_flow"]
    p_in, wf = f(s["p_in"]), f(s["w_of_flow"])
    proc, sel = f(s["proc_rate"]), f(s["selectivity"])
    has_links, join_dst = s["has_links"], s["join_dst"]
    droppable, is_sink = s["droppable"], s["is_sink"]
    in_mask, out_mask = s["M_in"] > 0, (s["w_out"] > 0) & ~droppable[None, :]
    dynamic = s["sin_amp"].shape[0] > 0 or s["ev_t0"].shape[0] > 0
    ts = np.arange(n_ticks, dtype=np.float32) * np.float32(dt)
    caps_sched = (reference._caps_schedule(s, ts, ar) if dynamic else
                  np.broadcast_to(f(s["caps"])[None, :], (n_ticks, L)))

    z = np.zeros(F, ar.dtype)
    Qs, Qr, B, x, v_acc, ls, lr, prod_rate, drain_ewma = (z.copy()
                                                           for _ in range(9))
    sink = np.zeros(n_ticks, ar.dtype)
    wait = np.zeros((n_ticks, F), ar.dtype)
    load = np.zeros((n_ticks, L), ar.dtype)

    def row_min(mask, vals, empty):
        m = np.min(np.where(mask, vals[None, :], np.inf), axis=1)
        return np.where(np.isfinite(m), m, empty)

    for t in range(n_ticks):
        caps_t = caps_sched[t]
        gen = gen_sched[t]
        if upd_every == 1 or t % upd_every == 0:
            if policy == "tcp":
                send = Qs / dt + prod_rate
                rwnd = np.maximum(qcap - Qr, 0.0) / dt + drain_ewma
                demand = np.minimum(send, rwnd)
                xm = maxmin_ref(R, caps_t, demand, ar)
                x = np.where(has_links, np.minimum(xm, demand),
                             INTERNAL_RATE).astype(ar.dtype)
            else:
                xa = allocate_ref(R, caps_t, s["kinds"],
                                  (ls, lr, v_acc, Qs, B), dt * upd_every, ar)
                x = np.where(has_links, xa, INTERNAL_RATE).astype(ar.dtype)
                v_acc, ls, lr = z.copy(), Qs.copy(), B.copy()
        # network transfer
        desired = np.minimum(np.minimum(Qs, x * dt),
                             np.maximum(qcap - Qr, 0.0))
        if dynamic:
            load0 = ar.mm(desired, R)
            lscale = np.where(load0 > caps_t * dt,
                              np.clip(caps_t * dt / np.maximum(load0, EPS),
                                      0.0, 1.0), 1.0)
            transfer = desired * row_min(on, lscale, 1.0)
        else:
            transfer = desired
        transfer = transfer.astype(ar.dtype)
        Qs = Qs - transfer
        Qr = Qr + transfer
        # processing
        join_amt = np.minimum(row_min(in_mask, Qr / np.maximum(p_in, EPS),
                                      0.0), proc * dt)
        total_in = ar.mm(M_in, Qr)
        frac = np.minimum(total_in, proc * dt) / np.maximum(total_in, EPS)
        consume = np.where(join_dst, join_amt[dst] * p_in, Qr * frac[dst])
        consume = np.minimum(consume, Qr)
        # sender backpressure
        in_i = ar.mm(M_in, consume)
        prod = (sel * in_i + gen * dt)[src] * wf
        space = np.maximum(qcap - Qs, 0.0)
        scale_f = np.clip(space / np.maximum(prod, EPS), 0.0, 1.0)
        stall = row_min(out_mask, scale_f, 1.0)
        consume = (consume * stall[dst]).astype(ar.dtype)
        Qr = Qr - consume
        Qr = np.where(droppable, np.minimum(Qr, 0.5), Qr).astype(ar.dtype)
        in_i = in_i * stall
        out_i = sel * in_i + gen * dt * stall
        Qs = Qs + out_i[src] * wf
        Qs = np.where(droppable, np.minimum(Qs, 0.5), Qs).astype(ar.dtype)
        sink[t] = np.sum(np.where(is_sink, in_i, 0.0))
        drain = consume / dt
        wait[t] = np.minimum(Qs / np.maximum(x, EPS)
                             + Qr / np.maximum(drain, EPS), LAT_CAP)
        load[t] = ar.mm(transfer, R) / dt
        # policy feedback
        if policy == "tcp":
            t_in = ar.mm(M_in, transfer)
            prod_rate = ((sel * t_in + gen * dt)[src] * wf / dt
                         ).astype(ar.dtype)
            drain_ewma = (0.5 * drain_ewma + 0.5 * drain).astype(ar.dtype)
        else:
            B = np.clip(B + transfer - drain * dt, 0.0,
                        8.0 * qcap).astype(ar.dtype)
            v_acc = (v_acc + transfer).astype(ar.dtype)
    return campaign_metrics(sink, wait, load, caps_sched, f(s["path_w"]), dt,
                            t_event, ar)
