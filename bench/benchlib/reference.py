"""Plain numpy reference of the system's semantics, independent of the
program's code.

* :func:`simulate_ref` — one scenario of the fluid stream simulation
  (paper Sec. VI) under the tcp or appaware policy, tick by tick, with the
  campaign metric row at the end;
* :func:`allocate_ref` — one interval of the App-aware allocator (paper
  Alg. 1): per-link solves of eqs. (3)/(4), the min over a flow's links,
  the proportional scale-down on internal links and the backfill passes;
* :func:`maxmin_ref` — demand-limited max-min fair rates by sequential
  progressive filling (the tcp policy).

Every function takes an :class:`Arith`, which fixes the precision: the
reference itself runs in float64 (``Arith("exact")``); the control runs
elementwise work in float32 and every contraction (matrix product,
convolution) in the TPU's ``high`` precision, three bf16 passes
(``Arith("high")``).

Scenario data comes in as input arrays that :func:`testbed_arrays` builds
from the app's instance DAG (operators, flows, groupings' shares) and the
deployment's parameters: the link path of each flow, the capacity events
and the cycle. The reference reads none of the arrays the program
compiles and runs none of its code.
"""
from __future__ import annotations

import numpy as np

try:                                    # numpy's bfloat16, shipped with jax
    from ml_dtypes import bfloat16 as _BF16
except ImportError:                     # pragma: no cover
    _BF16 = None

EPS = 1e-9
INTERNAL_RATE = 1e6     # MB/s: flows between instances on one machine
LAT_CAP = 1e4           # s: cap on one flow's wait
UPLINK, DOWNLINK, INTERNAL = 0, 1, 2   # link kinds, as the program numbers them

METRICS = ("avg_tput_mb_s", "final_tput_mb_s", "avg_latency_s",
           "utilization", "dip_depth", "recovery_time_s", "total_sink_mb")


class Arith:
    """Precision of a reference run (see module docstring)."""

    def __init__(self, mode: str = "exact"):
        if mode not in ("exact", "high"):
            raise ValueError(f"unknown precision {mode!r}")
        if mode != "exact" and _BF16 is None:  # pragma: no cover
            raise RuntimeError("bf16 emulation needs ml_dtypes")
        self.mode = mode
        self.dtype = np.float64 if mode == "exact" else np.float32

    def f(self, a):
        return np.asarray(a, self.dtype)

    @staticmethod
    def _parts(a):
        """The hi and lo bf16 terms of a float32 operand."""
        a = np.asarray(a, np.float32)
        hi = a.astype(_BF16).astype(np.float32)
        return hi, (a - hi).astype(_BF16).astype(np.float32)

    def _three_pass(self, op, a, b):
        """``op`` at ``high``: hi x hi + hi x lo + lo x hi, f32 sums."""
        (ah, al), (bh, bl) = self._parts(a), self._parts(b)
        return (op(ah, bh) + op(ah, bl) + op(al, bh)).astype(np.float32)

    def mm(self, a, b):
        """Matrix product at this precision."""
        if self.mode == "exact":
            return np.matmul(np.asarray(a, np.float64),
                             np.asarray(b, np.float64))
        return self._three_pass(np.matmul, a, b)

    def convolve_same(self, a, k):
        """``np.convolve(a, k, mode="same")`` at this precision."""
        if self.mode == "exact":
            return np.convolve(np.asarray(a, np.float64),
                               np.asarray(k, np.float64), mode="same")
        return self._three_pass(
            lambda x, y: np.convolve(x, y, mode="same"), a, k)


# ---------------------------------------------------------------- maxmin
def _link_levels(R, d, unfrozen, resid):
    """Water level of every link: the theta with sum_f min(d_f, theta)
    = resid over the link's unfrozen flows, inf where their demands fit."""
    on = (R.T > 0) & unfrozen[None, :]                     # [L, F]
    n = on.sum(1)
    ds = np.sort(np.where(on, d[None, :], np.inf), axis=1)
    fin = np.where(np.isfinite(ds), ds, 0.0)
    before = np.concatenate([np.zeros((ds.shape[0], 1), ds.dtype),
                             np.cumsum(fin, axis=1)[:, :-1]], axis=1)
    k = np.arange(ds.shape[1])[None, :]
    left = np.maximum(n[:, None] - k, 1).astype(ds.dtype)
    t = (resid[:, None] - before) / left
    ok = (k < n[:, None]) & (t <= ds)
    first = np.argmax(ok, axis=1)
    theta = t[np.arange(t.shape[0]), first]
    fits = fin.sum(1) <= resid
    return np.where(ok.any(1) & ~fits & (n > 0), theta, np.inf)


def maxmin_ref(R, cap, demand, ar: Arith):
    """Demand-limited max-min fair rates by progressive filling: each
    round freezes the demand-satisfied flows, or else the flows at the
    lowest bottleneck level. Flows that cross no link get their demand."""
    R = ar.f(R)
    d0 = ar.f(demand)
    on_net = R.sum(1) > 0
    d = np.where(on_net, np.maximum(d0, 0.0), 0.0).astype(ar.dtype)
    x = np.where(on_net, 0.0, d0).astype(ar.dtype)
    frozen = ~on_net
    resid = ar.f(cap).copy()
    for _ in range(R.shape[0] + 1):
        u = ~frozen
        if not u.any():
            break
        theta = _link_levels(R, d, u, resid)
        th_flow = np.min(np.where(R > 0, theta[None, :], np.inf), axis=1)
        sated = u & (d <= th_flow)
        if sated.any():
            newf = sated
        else:
            newf = u & (th_flow <= th_flow[u].min())
        vals = np.minimum(d, th_flow).astype(ar.dtype)
        x = np.where(newf, vals, x).astype(ar.dtype)
        resid = np.maximum(resid - ar.mm(np.where(newf, vals, 0.0), R),
                           0.0).astype(ar.dtype)
        frozen = frozen | newf
    return x


# -------------------------------------------------------------- allocate
def _waterfill(backlog, rho, cap, dt):
    """Eq. (4) on one downlink: equal queue-drain times,
    x_f = max(0, theta rho_f - L_f) / dt with sum_f x_f = cap."""
    order = np.argsort(backlog / rho, kind="stable")
    Ls, rs = backlog[order], rho[order]
    th = Ls / rs
    cand = (cap * dt + np.cumsum(Ls)) / np.maximum(np.cumsum(rs), EPS)
    k = np.flatnonzero(cand >= th)
    theta = cand[k[-1]] if k.size else cand[0]
    x = np.maximum(theta * rho - backlog, 0.0) / dt
    s = x.sum()
    return x * (cap / s) if s > EPS else x


def allocate_ref(R, cap, kind, state, dt: float, ar: Arith,
                 backfill_iters: int = 8, damping: float = 0.9):
    """Alg. 1 for one interval. ``state`` is the five FlowState fields
    (ls_t, lr_t, v, ls_t1, lr_t1); returns the rate of every flow."""
    R = ar.f(R)
    cap = ar.f(cap)
    kind = np.asarray(kind)
    ls_t, lr_t, v, ls_t1, lr_t1 = (ar.f(a) for a in state)
    w = np.maximum(v + 2.0 * ls_t1 - ls_t, 0.0).astype(ar.dtype)
    rho = np.maximum((v - lr_t1 + lr_t) / dt, EPS).astype(ar.dtype)
    F, L = R.shape
    fi, li = np.nonzero(R > 0)              # (flow, link) pairs, by flow
    starts = np.flatnonzero(np.r_[True, fi[1:] != fi[:-1]])

    def flow_min(vals, empty):
        """min over each flow's pairs of ``vals`` (one per pair)."""
        out = np.full(F, empty, ar.dtype)
        if fi.size:
            out[fi[starts]] = np.minimum.reduceat(vals, starts)
        return out

    # eq. (3) on uplinks: the link's capacity in proportion to demand
    tot = np.bincount(li, weights=w[fi], minlength=L)
    n = np.bincount(li, minlength=L)
    x_pair = np.where(tot[li] > EPS, cap[li] * w[fi] / np.maximum(tot[li], EPS),
                      cap[li] / np.maximum(n[li], 1)).astype(ar.dtype)
    # eq. (4) on downlinks: water-filling of the queue-drain times
    for link in np.flatnonzero((kind == DOWNLINK) & (n > 0)):
        sel = np.flatnonzero(li == link)
        f = fi[sel]
        x_pair[sel] = _waterfill(lr_t1[f], rho[f], cap[link], dt)
    # line 22: a flow's rate is its least over its uplinks and downlinks
    x_pair = np.where(kind[li] == INTERNAL, np.inf, x_pair)
    x = flow_min(x_pair, np.inf)
    x = np.where(np.isfinite(x), x, 0.0).astype(ar.dtype)
    # lines 24-29: congested internal links scale their flows down
    load = ar.mm(x, R)
    scale = np.where((kind == INTERNAL) & (load > cap),
                     cap / np.maximum(load, EPS), 1.0)
    x = (x * flow_min(np.where(kind[li] == INTERNAL, scale[li], 1.0), 1.0)
         ).astype(ar.dtype)
    # backfill: hand leftover capacity to flows in proportion to their rate
    on_net = np.bincount(fi, minlength=F) > 0
    for _ in range(backfill_iters):
        load = ar.mm(x, R)
        ratio = np.maximum(cap - load, 0.0) / np.maximum(load, EPS)
        r_min = flow_min(ratio[li], np.inf)
        inc = np.where(on_net & np.isfinite(r_min), x * r_min, 0.0)
        x = (x + damping * inc).astype(ar.dtype)
    return x


# ------------------------------------------------------------ simulation
def _flow_volumes(graph) -> np.ndarray:
    """Open-loop steady-state MB/s of every flow: each instance emits
    its generation plus selectivity times its input, split onto its flows
    by their shares (the fixed point of a DAG, reached within I passes)."""
    I = len(graph.proc_rate)
    src, dst = graph.src_of_flow, graph.dst_of_flow
    share = graph.w_out[src, np.arange(src.shape[0])]
    inflow = np.zeros(I)
    for _ in range(I + 1):
        out = graph.gen_rate + graph.selectivity * inflow
        vol = out[src] * share
        inflow = np.bincount(dst, weights=vol, minlength=I)
    return vol


def _input_shares(graph) -> np.ndarray:
    """Share of its destination's input that each flow carries: an edge
    with a join share takes that share of the destination's input, split
    over the edge's flows by volume; the other flows split the rest by
    volume."""
    vol = _flow_volumes(graph) + 1e-12
    edges = graph.app.edges
    eid = graph.edge_of_flow
    p = np.zeros(vol.shape[0])
    for i in np.unique(graph.dst_of_flow):
        mine = np.flatnonzero(graph.dst_of_flow == i)
        fixed = [f for f in mine if edges[eid[f]].join_share is not None]
        used = 0.0
        for e in sorted({int(eid[f]) for f in fixed}):
            fe = [f for f in fixed if eid[f] == e]
            p[fe] = edges[e].join_share * vol[fe] / vol[fe].sum()
            used += edges[e].join_share
        free = [f for f in mine if edges[eid[f]].join_share is None]
        if free:
            p[free] = max(1.0 - used, 0.0) * vol[free] / vol[free].sum()
        if p[mine].sum() > 0:
            p[mine] /= p[mine].sum()
    return p


def _path_weights(graph) -> np.ndarray:
    """Per flow, the share of the app's source-to-sink instance paths
    that cross it (the latency estimate is the mean wait over paths)."""
    F = graph.src_of_flow.shape[0]
    outs = {}
    for f, s in enumerate(graph.src_of_flow):
        outs.setdefault(int(s), []).append(f)
    count = np.zeros(F)
    n_paths = 0
    stack = [(int(i), ()) for i in np.flatnonzero(graph.gen_rate > 0)]
    while stack:
        i, fl = stack.pop()
        if graph.is_sink[i]:
            count[list(fl)] += 1.0
            n_paths += 1
            continue
        stack += [(int(graph.dst_of_flow[f]), fl + (f,))
                  for f in outs.get(i, [])]
    return count / max(n_paths, 1)


def testbed_arrays(graph, placement, n_machines: int, cap: float,
                   events=(), diurnal=None) -> dict:
    """Input arrays of one scenario on a one-switch testbed: machine m's
    uplink is link 2m and its downlink 2m + 1, a flow between instances
    on two machines crosses the sender's uplink and the receiver's
    downlink. ``events`` are (machine, 0 up or 1 down, t0, t1, scale),
    ``diurnal`` (period s, amplitude, phase rad) on every link; event
    times, rates and shares are float32 data, as the configuration
    states."""
    src = np.asarray(graph.src_of_flow)
    dst = np.asarray(graph.dst_of_flow)
    F, I, L = src.shape[0], len(graph.proc_rate), 2 * n_machines
    ms, md = placement[src], placement[dst]
    ext = np.flatnonzero(ms != md)
    R = np.zeros((F, L))
    R[ext, 2 * ms[ext]] = 1.0
    R[ext, 2 * md[ext] + 1] = 1.0
    M_in = np.zeros((I, F))
    M_in[dst, np.arange(F)] = 1.0
    is_join = np.asarray(graph.is_join, bool)
    f32 = np.float32
    if diurnal is None:
        sin = np.zeros((0, L), f32)
        sins = (sin, sin, sin)
    else:
        period, amp, phase = diurnal
        sins = (np.full((1, L), amp, f32),
                np.full((1, L), 2.0 * np.pi / period, f32),
                np.full((1, L), phase, f32))
    ev = np.asarray([(2 * m + d, t0, t1, sc) for m, d, t0, t1, sc in events],
                    np.float64).reshape(-1, 4)
    return {
        "R": R, "caps": np.full(L, f32(cap)), "kinds": np.arange(L) % 2,
        "has_links": R.sum(1) > 0, "M_in": M_in,
        "w_out": np.asarray(graph.w_out, f32),
        "p_in": _input_shares(graph).astype(f32),
        "proc_rate": np.asarray(graph.proc_rate, f32),
        "selectivity": np.asarray(graph.selectivity, f32),
        "gen_rate": np.asarray(graph.gen_rate, f32),
        "is_sink": np.asarray(graph.is_sink, bool),
        "join_dst": is_join[dst],
        "droppable": np.array([graph.app.edges[e].droppable
                               for e in graph.edge_of_flow], bool),
        "dst_of_flow": dst, "src_of_flow": src,
        "w_of_flow": np.asarray(graph.w_out, f32)[src, np.arange(F)],
        "path_w": _path_weights(graph).astype(f32),
        "sin_amp": sins[0], "sin_omega": sins[1], "sin_phase": sins[2],
        "ev_t0": ev[:, 1].astype(f32), "ev_t1": ev[:, 2].astype(f32),
        "ev_link": ev[:, 0].astype(np.int64), "ev_scale": ev[:, 3].astype(f32),
        "route_bank": np.zeros((0, F, L)),
    }


def _block_diag(mats) -> np.ndarray:
    out = np.zeros((sum(m.shape[0] for m in mats),
                    sum(m.shape[1] for m in mats)))
    r = c = 0
    for m in mats:
        out[r:r + m.shape[0], c:c + m.shape[1]] = m
        r, c = r + m.shape[0], c + m.shape[1]
    return out


def concat_arrays(parts: list) -> dict:
    """Several static scenarios side by side as one: their links, flows
    and instances apart, so the joint run is each one's run."""
    off = np.cumsum([0] + [p["M_in"].shape[0] for p in parts])[:-1]
    s = {k: _block_diag([p[k] for p in parts])
         for k in ("R", "M_in", "w_out")}
    for k in ("caps", "kinds", "has_links", "p_in", "proc_rate",
              "selectivity", "gen_rate", "is_sink", "join_dst", "droppable",
              "w_of_flow", "path_w"):
        s[k] = np.concatenate([p[k] for p in parts])
    for k in ("dst_of_flow", "src_of_flow"):
        s[k] = np.concatenate([p[k] + o for p, o in zip(parts, off)])
    F, L = s["R"].shape
    z = np.zeros((0, L), np.float32)
    e = np.zeros(0, np.float32)
    s.update(sin_amp=z, sin_omega=z, sin_phase=z, ev_t0=e, ev_t1=e,
             ev_link=e.astype(np.int64), ev_scale=e,
             route_bank=np.zeros((0, F, L)))
    return s


def _caps_schedule(s, ts, ar: Arith):
    """Scheduled capacity of every link at every tick [T, L]."""
    caps = np.broadcast_to(ar.f(s["caps"])[None, :],
                           (ts.shape[0], s["caps"].shape[0])).copy()
    if s["sin_amp"].shape[0]:
        arg = (ar.f(s["sin_omega"])[None] * ar.f(ts)[:, None, None]
               + ar.f(s["sin_phase"])[None])
        caps = caps * (1.0 + np.sum(ar.f(s["sin_amp"])[None] * np.sin(arg),
                                    axis=1))
    for e in range(s["ev_t0"].shape[0]):
        # event times are float32 data: decide activity in float32
        on = (ts >= s["ev_t0"][e]) & (ts < s["ev_t1"][e])
        caps[:, int(s["ev_link"][e])] *= np.where(on, s["ev_scale"][e], 1.0)
    return np.maximum(caps, 0.0).astype(ar.dtype)


def campaign_metrics(sink, wait, load, caps_grid, path_w, dt: float,
                     t_event: float, ar: Arith, win_s: float = 5.0,
                     pre_s: float = 20.0, frac: float = 0.95,
                     hot_thresh: float = 0.5) -> np.ndarray:
    """The campaign's metric row (``METRICS``) from one run's
    trajectories: post-warm-up mean sink rate and latency, smoothed final
    rate, bottleneck utilization, dip depth and settling time after
    ``t_event``, total MB delivered."""
    T = sink.shape[0]
    warm = T // 4
    rate = sink / dt
    lat = ar.mm(wait, path_w)
    util = (load[warm:] / np.maximum(caps_grid[warm:], EPS)).mean(0)
    hot = util >= hot_thresh
    if not hot.any():
        hot = util >= util.max() * 0.999
    utilization = util[hot].sum() / max(int(hot.sum()), 1)
    w = max(int(round(win_s / dt)), 1)
    count = np.convolve(np.ones(T), np.ones(w), mode="same")
    r = ar.convolve_same(rate, np.ones(w)) / ar.f(count)
    i = min(int(round(t_event / dt)), T - 1)
    pre_mean = r[max(0, i - int(round(pre_s / dt))):max(i, 1)].mean()
    post = r[i:]
    dip = (max((pre_mean - post.min()) / max(pre_mean, EPS), 0.0)
           if pre_mean > EPS else 0.0)
    if post.shape[0] < 2:
        recovery = 0.0
    else:
        steady = post[-max(post.shape[0] // 4, 1):].mean()
        inside = (post >= frac * steady) & (post * frac <= steady)
        if inside.all():
            recovery = 0.0
        else:
            first_out = int(np.argmax(~inside))
            ok = np.flatnonzero(inside[first_out:])
            recovery = (float(first_out + ok[0]) * dt if ok.size
                        else np.inf)
    return np.array([rate[warm:].mean(), r[-1], lat[warm:].mean(),
                     utilization, dip, recovery, sink.sum()], np.float64)


def simulate_ref(s: dict, policy: str, n_ticks: int, dt: float,
                 upd_every: int, qcap: float, ar: Arith,
                 t_event: float = 0.0, observe=None) -> np.ndarray:
    """Run one scenario tick by tick and return its metric row.

    Per tick: the policy's rates (tcp every tick; appaware every
    ``upd_every`` ticks from the flow state it observed), the network
    transfer (receiver window, and the scheduled capacity enforced on
    scenarios with a schedule), processing (joins advance in lock-step
    with their inputs, other instances consume up to their rate), sender
    backpressure, emission, and the stale-data discard on droppable
    streams. ``observe``, where given, is handed the flow state
    (ls_t, lr_t, v, ls_t1, lr_t1) of every appaware update."""
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
    proc, sel, gen = f(s["proc_rate"]), f(s["selectivity"]), f(s["gen_rate"])
    has_links, join_dst = s["has_links"], s["join_dst"]
    droppable, is_sink = s["droppable"], s["is_sink"]
    in_mask, out_mask = s["M_in"] > 0, (s["w_out"] > 0) & ~droppable[None, :]
    dynamic = s["sin_amp"].shape[0] > 0 or s["ev_t0"].shape[0] > 0
    ts = np.arange(n_ticks, dtype=np.float32) * np.float32(dt)
    caps_sched = (_caps_schedule(s, ts, ar) if dynamic else
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
        if upd_every == 1 or t % upd_every == 0:
            if policy == "tcp":
                send = Qs / dt + prod_rate
                rwnd = np.maximum(qcap - Qr, 0.0) / dt + drain_ewma
                demand = np.minimum(send, rwnd)
                xm = maxmin_ref(R, caps_t, demand, ar)
                x = np.where(has_links, np.minimum(xm, demand),
                             INTERNAL_RATE).astype(ar.dtype)
            else:
                if observe is not None:
                    observe((ls, lr, v_acc, Qs, B))
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
