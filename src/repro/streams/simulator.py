"""Discrete-time fluid simulation of a distributed stream application over a
bandwidth-constrained fabric (reproduces the paper's testbed, §VI).

Each tick (``dt`` seconds):
  1. network transfer: every flow moves min(Q_s, x_f·dt) MB from its sender
     queue to its receiver queue — x is the policy's rate vector (TCP max-min,
     the paper's App-aware Alg. 1, App-Fair, or a fixed vector for the
     brute-force motivation study);
  2. processing: each instance consumes from its receiver queues — *join*
     instances advance in lock-step with their proportional inputs (a starved
     input stalls the join: the paper's core phenomenon), others consume
     work-conserving up to proc_rate;
  3. emission: consumed MB × selectivity is split over outgoing flows per the
     grouping weights; sources additionally generate gen_rate·dt.

The whole run is one `jax.lax.scan`, jitted; policies recompute rates inside
the scan (TCP every tick — idealized instant congestion control; App-aware
every Δt, matching the paper's 5 s controller interval).

**In-run network dynamics:** link capacity is a function of time. A
:class:`repro.net.topology.LinkSchedule` (sinusoidal diurnal components +
piecewise-constant failure/recovery events) compiles into per-sim arrays;
``_caps_over`` evaluates the whole ``[T, L]`` capacity trajectory once per
run and the scan consumes it as an ``xs`` stream, so the per-tick cost of a
schedule is one dynamic slice. Policies re-solve against ``caps(t_upd)`` at
their update ticks; between updates the *network itself* enforces the
current capacity (a failed link moves no bytes even while the controller's
rates are stale — that stale window is exactly the transient the paper's
Fig. 5/12 regime is about). A sim compiled without a schedule (S = 0
sinusoids, E = 0 events) skips every dynamic term *by shape* and runs the
static path unchanged.

**In-run source load:** a :class:`repro.streams.load.LoadSchedule`
(SINE sinusoids, SQUARE steps) scales the sources' generation rates.
``_load_over`` evaluates it once per run to a ``[T, I]`` rate grid that
the scan streams beside the capacities; each tick reads its row in place
of the constant ``gen_rate``. A sim without one (S_g = 0, E_g = 0) skips
it by shape.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocator import LinkProgram, allocate
from repro.core.flowstate import FlowState
from repro.core.multiapp import (
    ewma_throughput,
    group_by_throughput,
    strict_priority_alloc,
)
from repro.core.tcp import maxmin_fused_step, maxmin_order_init
from repro.net.topology import LinkSchedule, RouteSchedule, Topology
from repro.streams.app import InstanceGraph, path_weights
from repro.streams.load import LoadSchedule

_EPS = 1e-9
INTERNAL_RATE = 1e6  # MB/s: same-machine flows move at memory speed
_LAT_CAP = 1e4       # s: cap on per-flow latency contribution (stalled flows)

# f32 contractions run at HIGHEST: the chip's default is one bf16 pass,
# which rounds the rates, demands and queue volumes these products carry
_HIGHEST = jax.lax.Precision.HIGHEST

# The campaign summary vector computed by the in-program metric epilogue
# (`_metrics_epilogue`), in order. Throughput entries are MB-based (the
# per-scenario ``tuples_per_mb`` conversion is one exact scalar multiply,
# applied host-side by the consumers) so one padded fleet program serves
# scenarios with different tuple densities.
CAMPAIGN_METRICS = (
    "avg_tput_mb_s",      # post-warmup mean sink rate
    "final_tput_mb_s",    # smoothed sink rate at the last tick
    "avg_latency_s",      # post-warmup mean path latency
    "utilization",        # bottleneck-link utilization (Fig. 12 metric)
    "dip_depth",          # fractional dip after t_event (0 = none)
    "recovery_time_s",    # settling time after t_event (inf = never)
    "total_sink_mb",      # total MB delivered to sinks
)


def metric_index(name: str) -> int:
    return CAMPAIGN_METRICS.index(name)


@functools.partial(
    jax.tree_util.register_dataclass,
    meta_fields=("tuples_per_mb", "n_apps"),
    data_fields=(
        "R", "caps", "kinds", "has_links", "M_in", "w_out", "p_in",
        "proc_rate", "selectivity", "gen_rate", "is_join", "is_sink",
        "join_dst", "droppable", "dst_of_flow", "src_of_flow", "w_of_flow",
        "path_w", "app_of_flow", "app_of_inst",
        "sin_amp", "sin_omega", "sin_phase",
        "ev_t0", "ev_t1", "ev_link", "ev_scale",
        "route_bank", "route_t", "route_state",
        "load_amp", "load_omega", "load_phase",
        "load_t0", "load_t1", "load_scale",
    ),
)
@dataclasses.dataclass
class CompiledSim:
    """Structure of one simulation (pytree: arrays data, scalars static)."""

    # network
    R: Any               # [F, L]
    caps: Any            # [L] base capacities (schedule scales them in-run)
    kinds: Any           # [L]
    has_links: Any       # [F] bool
    # dataflow
    M_in: Any            # [I, F] flow f ends at instance i
    w_out: Any           # [I, F] share of inst output onto flow
    p_in: Any            # [F] proportion of dst's input expected on flow
    proc_rate: Any       # [I]
    selectivity: Any     # [I]
    gen_rate: Any        # [I]
    is_join: Any         # [I] bool
    is_sink: Any         # [I] bool
    join_dst: Any        # [F] bool: flow terminates at a join instance
    droppable: Any       # [F] bool: stale excess is discarded at the join
    dst_of_flow: Any     # [F]
    src_of_flow: Any     # [F]
    w_of_flow: Any       # [F] = w_out[src_of_flow[f], f] (the column's only
                         #      nonzero: each flow has one source instance)
    path_w: Any          # [F] per-flow latency weight = Σ_p paths[p, f]/P
                         #     (app.path_weights: the path-mean
                         #     contraction, pre-collapsed so
                         #     the scan never carries a [P, F] matvec; the
                         #     latency itself is a host-side dot — see
                         #     SimResult construction)
    tuples_per_mb: float
    app_of_flow: Any     # [F] int
    app_of_inst: Any     # [I] int
    n_apps: int
    # capacity schedule (see repro.net.topology.LinkSchedule); S = 0 / E = 0
    # means static caps and the simulator skips the dynamic terms by shape
    sin_amp: Any         # [S, L]
    sin_omega: Any       # [S, L]
    sin_phase: Any       # [S, L]
    ev_t0: Any           # [E]
    ev_t1: Any           # [E]
    ev_link: Any         # [E] int32
    ev_scale: Any        # [E]
    # mid-run rerouting bank (see repro.net.topology.RouteSchedule):
    # S_r = 0 means static routing and the simulator skips the per-tick
    # state stream and bank gather by shape. ``route_t``/``route_state``
    # share the S_r axis with the bank (S_r = max(states, intervals)):
    # padded interval slots never activate (t0 = inf) and padded bank
    # states are never indexed. Only R is banked: rerouting re-picks
    # *links*, never flow endpoints, so the per-flow fields derived from
    # the instance graph (src_of_flow / w_of_flow / path_w) and
    # ``has_links`` (dead routes are retained, not dropped) are
    # route-state-invariant.
    route_bank: Any      # [S_r, F, L] routing matrix per route state
    route_t: Any         # [S_r] interval start times (inf = padding)
    route_state: Any     # [S_r] int32 state index per interval
    # source load schedule (see repro.streams.load.LoadSchedule); S_g = 0
    # / E_g = 0 means constant generation, skipped by shape
    load_amp: Any        # [S_g, I]
    load_omega: Any      # [S_g, I]
    load_phase: Any      # [S_g, I]
    load_t0: Any         # [E_g]
    load_t1: Any         # [E_g]
    load_scale: Any      # [E_g, I]

    @property
    def program(self) -> LinkProgram:
        return LinkProgram(R=self.R, capacity=self.caps, kind=self.kinds)

    def program_at(self, caps_t, R=None) -> LinkProgram:
        return LinkProgram(R=self.R if R is None else R,
                           capacity=caps_t, kind=self.kinds)

    @property
    def is_dynamic(self) -> bool:
        """Whether a capacity schedule is attached — a *shape* predicate
        (S > 0 sinusoids or E > 0 events), so it is trace-time static and
        every consumer (scan stream, enforcement, caps_t reporting) gates
        on the same definition."""
        return self.sin_amp.shape[0] > 0 or self.ev_t0.shape[0] > 0

    @property
    def is_rerouting(self) -> bool:
        """Whether a route bank is attached — the same kind of *shape*
        predicate as :attr:`is_dynamic`: S_r = 0 sims never stream a state
        index or gather from the bank, so static-routing runs are bitwise
        the pre-reroute path."""
        return self.route_bank.shape[0] > 0

    @property
    def is_loaded(self) -> bool:
        """Whether a source load schedule is attached — a *shape*
        predicate like :attr:`is_dynamic` (S_g > 0 sinusoids or E_g > 0
        steps): a sim without one streams no rate grid and runs the
        constant-load path bitwise."""
        return self.load_amp.shape[0] > 0 or self.load_t0.shape[0] > 0


def _validate_sim_inputs(where: str, *,
                         finite_nonneg: Sequence[tuple[str, Any]] = (),
                         nonneg_inf_ok: Sequence[tuple[str, Any]] = ()
                         ) -> None:
    """Reject poisoned scenario inputs at the compile boundary with an
    error naming the offending field, instead of letting a NaN flow
    silently through the whole scan and surface as a garbage metric row.

    Two classes, because +inf is *load-bearing* in this codebase:
    ``finite_nonneg`` fields (capacities, demands, event scales) must be
    finite and ≥ 0; ``nonneg_inf_ok`` fields may be +inf — event times
    use inf for "never" (schedule padding, permanent failures) and
    ``proc_rate`` uses inf for "unbounded" (clamped at compile) — but NaN
    and negative values are always poison."""
    for field, a in finite_nonneg:
        a = np.asarray(a, np.float64)
        bad = ~np.isfinite(a) | (a < 0)
        if bad.any():
            i = int(np.flatnonzero(bad.ravel())[0])
            raise ValueError(
                f"{where}: {field} must be finite and non-negative; got "
                f"{field}.ravel()[{i}] = {a.ravel()[i]}")
    for field, a in nonneg_inf_ok:
        a = np.asarray(a, np.float64)
        bad = np.isnan(a) | (a < 0)
        if bad.any():
            i = int(np.flatnonzero(bad.ravel())[0])
            raise ValueError(
                f"{where}: {field} must be non-negative and not NaN "
                f"(+inf is allowed); got "
                f"{field}.ravel()[{i}] = {a.ravel()[i]}")


def compile_sim(
    graph: InstanceGraph,
    topo: Topology,
    machine_of_inst: np.ndarray,
    app_of_inst: np.ndarray | None = None,
    n_apps: int = 1,
    schedule: LinkSchedule | None = None,
    reroute: "bool | RouteSchedule" = False,
    load: LoadSchedule | None = None,
) -> CompiledSim:
    """Compile one scenario. ``load`` schedules the sources' generation
    rates in-run (only instances with ``gen_rate > 0`` may carry one).
    ``reroute=True`` derives a
    :class:`~repro.net.topology.RouteSchedule` from ``schedule``'s events
    (the SDN controller reprograms routes around failed links mid-run); an
    explicit ``RouteSchedule`` is used as-is. A schedule whose events never
    change the route set collapses to a single state and compiles exactly
    like ``reroute=False`` — the bank stays empty (S_r = 0) and the run is
    bitwise the static-routing path."""
    flows = graph.flow_pairs(machine_of_inst)
    R = topo.routing_matrix(flows)
    M_in = graph.in_matrix()
    # steady-state volumes -> expected input proportions per dst instance,
    # with semantic `join_share` overrides (paper's TI: the join consumes the
    # congestion stream at its *useful* rate, not its volume-average rate)
    from repro.streams.placement import _steady_state_flow_volume

    vol = _steady_state_flow_volume(graph) + 1e-12
    edges = graph.app.edges
    share = np.array(
        [edges[e].join_share if edges[e].join_share is not None else np.nan
         for e in graph.edge_of_flow]
    )
    p_in = np.zeros(graph.n_flows)
    for i in range(graph.n_instances):
        sel = graph.dst_of_flow == i
        if not sel.any():
            continue
        ov = sel & ~np.isnan(share)
        free = sel & np.isnan(share)
        # overridden edges: edge share split within the edge by volume
        used = 0.0
        for e in np.unique(graph.edge_of_flow[ov]):
            fe = ov & (graph.edge_of_flow == e)
            p_in[fe] = edges[e].join_share * vol[fe] / vol[fe].sum()
            used += edges[e].join_share
        if free.any():
            p_in[free] = max(1.0 - used, 0.0) * vol[free] / vol[free].sum()
        s = p_in[sel].sum()
        if s > 0:
            p_in[sel] /= s
    droppable = np.array([edges[e].droppable for e in graph.edge_of_flow])
    # one per-flow weight vector for the source-to-sink paths: the latency
    # estimate is linear in the per-flow waits (Σ_p Σ_f paths[p, f]
    # · wait[f] / P), so the path axis contracts at compile time — the scan
    # outputs raw waits and the SimResult takes the dot on the host, which
    # keeps the estimate bitwise-independent of fleet padding (an XLA
    # matvec re-associates when the contraction length changes)
    path_w = path_weights(graph)
    app_of_inst = (
        np.zeros(graph.n_instances, np.int32) if app_of_inst is None else app_of_inst
    )
    if schedule is None:
        schedule = LinkSchedule.empty(topo.n_links)
    elif schedule.n_links != topo.n_links:
        raise ValueError(
            f"schedule covers {schedule.n_links} links, topology has "
            f"{topo.n_links}")
    ev_link = np.asarray(schedule.ev_link)
    if ev_link.size and (ev_link.min() < 0
                         or ev_link.max() >= topo.n_links):
        # a stale schedule (built for another topology) would otherwise be
        # silently clipped onto the wrong link by the jitted evaluation
        raise ValueError(
            f"schedule event links {ev_link} out of range for "
            f"{topo.n_links} links")
    if load is None:
        load = LoadSchedule.empty(graph.n_instances)
    elif load.n_instances != graph.n_instances:
        raise ValueError(
            f"load schedule covers {load.n_instances} instances, graph has "
            f"{graph.n_instances}")
    loaded = (np.any(np.asarray(load.step_scale) != 1, axis=0)
              | np.any(np.asarray(load.sin_amp) != 0, axis=0))
    not_src = np.flatnonzero(loaded & ~(np.asarray(graph.gen_rate) > 0))
    if not_src.size:
        raise ValueError(
            f"load schedule scales instances {not_src}, which generate "
            f"nothing (gen_rate 0)")
    _validate_sim_inputs(
        "compile_sim",
        finite_nonneg=[("capacities", topo.capacities),
                       ("gen_rate", graph.gen_rate),
                       ("ev_scale", schedule.ev_scale),
                       ("load_scale", load.step_scale)],
        nonneg_inf_ok=[("proc_rate", graph.proc_rate),
                       ("ev_t0", schedule.ev_t0),
                       ("ev_t1", schedule.ev_t1),
                       ("load_t0", load.step_t0),
                       ("load_t1", load.step_t1)])
    for field, a in (("load_amp", load.sin_amp),
                     ("load_omega", load.sin_omega),
                     ("load_phase", load.sin_phase)):
        if not np.all(np.isfinite(a)):
            raise ValueError(f"compile_sim: {field} must be finite")
    F, L = len(flows), topo.n_links
    if reroute is True:
        reroute = RouteSchedule.from_events(topo, flows, schedule)
    if isinstance(reroute, RouteSchedule):
        if reroute.routes.shape[1:] != (F, L):
            raise ValueError(
                f"route schedule is [{reroute.routes.shape[1]} flows, "
                f"{reroute.routes.shape[2]} links]; scenario has "
                f"[{F}, {L}]")
        if reroute.n_states > 1:
            # single shared S_r axis for bank + interval arrays: padded
            # intervals never activate, padded bank states never indexed
            sr = max(reroute.n_states, reroute.n_intervals)
            route_bank = np.zeros((sr, F, L), np.float32)
            route_bank[:reroute.n_states] = reroute.routes
            route_t = np.full((sr,), np.inf, np.float32)
            route_t[:reroute.n_intervals] = reroute.t0
            route_state = np.zeros((sr,), np.int32)
            route_state[:reroute.n_intervals] = reroute.state
        else:
            # one reachable state == static routing: skip by shape
            route_bank = np.zeros((0, F, L), np.float32)
            route_t = np.zeros((0,), np.float32)
            route_state = np.zeros((0,), np.int32)
    else:
        route_bank = np.zeros((0, F, L), np.float32)
        route_t = np.zeros((0,), np.float32)
        route_state = np.zeros((0,), np.int32)
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    return CompiledSim(
        R=f32(R),
        caps=f32(topo.capacities),
        kinds=jnp.asarray(topo.link_kinds),
        has_links=jnp.asarray(R.sum(1) > 0),
        M_in=f32(M_in),
        w_out=f32(graph.w_out),
        p_in=f32(p_in),
        proc_rate=f32(np.minimum(graph.proc_rate, 1e9)),
        selectivity=f32(graph.selectivity),
        gen_rate=f32(graph.gen_rate),
        is_join=jnp.asarray(graph.is_join),
        is_sink=jnp.asarray(graph.is_sink),
        join_dst=jnp.asarray(graph.is_join[graph.dst_of_flow]),
        droppable=jnp.asarray(droppable),
        dst_of_flow=jnp.asarray(graph.dst_of_flow),
        src_of_flow=jnp.asarray(graph.src_of_flow),
        w_of_flow=f32(graph.w_out[graph.src_of_flow,
                                  np.arange(graph.n_flows)]),
        path_w=f32(path_w),
        tuples_per_mb=float(graph.app.tuples_per_mb),
        app_of_flow=jnp.asarray(app_of_inst[graph.dst_of_flow], jnp.int32),
        app_of_inst=jnp.asarray(app_of_inst, jnp.int32),
        n_apps=int(n_apps),
        sin_amp=f32(schedule.sin_amp),
        sin_omega=f32(schedule.sin_omega),
        sin_phase=f32(schedule.sin_phase),
        ev_t0=f32(schedule.ev_t0),
        ev_t1=f32(schedule.ev_t1),
        ev_link=jnp.asarray(schedule.ev_link, jnp.int32),
        ev_scale=f32(schedule.ev_scale),
        route_bank=f32(route_bank),
        route_t=f32(route_t),
        route_state=jnp.asarray(route_state, jnp.int32),
        load_amp=f32(load.sin_amp),
        load_omega=f32(load.sin_omega),
        load_phase=f32(load.sin_phase),
        load_t0=f32(load.step_t0),
        load_t1=f32(load.step_t1),
        load_scale=f32(load.step_scale),
    )


def _route_states_over(sim: CompiledSim, ts: jnp.ndarray) -> jnp.ndarray:
    """Per-tick route-state index [T] — the routing analogue of
    ``_caps_over``: evaluated once per run outside the scan and streamed
    as ``xs``, so selecting the active route state costs one [F, L] gather
    per tick, never a recompile or a ``lax.cond``.

    Piecewise-constant lookup: tick t takes the last interval whose start
    time is ≤ t. Padded interval slots start at +inf (never counted) and
    all-padding rows (a static scenario packed into a rerouting bucket)
    clamp to interval 0, whose bank slot holds that scenario's base R.
    """
    j = jnp.sum(ts[:, None] >= sim.route_t[None, :], axis=1) - 1
    return sim.route_state[jnp.maximum(j, 0)]


def _caps_over(sim: CompiledSim, ts: jnp.ndarray) -> jnp.ndarray:
    """Evaluate the capacity schedule on a tick grid: [T, L].

    Computed once per run *outside* the scan and streamed in as ``xs`` — a
    schedule costs one dynamic slice per tick, not per-tick trig/scatter.
    Sims without sinusoids (S = 0) or events (E = 0) skip those terms by
    shape; a zero-amplitude / never-active schedule multiplies by exactly
    1.0, so the constant-schedule path is bitwise-identical to static caps.
    """
    L = sim.caps.shape[0]
    caps = jnp.broadcast_to(sim.caps[None, :], (ts.shape[0], L))
    if sim.sin_amp.shape[0]:
        wave = jnp.sum(
            sim.sin_amp[None] * jnp.sin(
                sim.sin_omega[None] * ts[:, None, None]
                + sim.sin_phase[None]), axis=1)           # [T, L]
        caps = caps * (1.0 + wave)
    if sim.ev_t0.shape[0]:
        active = (ts[:, None] >= sim.ev_t0[None]) & (
            ts[:, None] < sim.ev_t1[None])                # [T, E]
        mult = jnp.where(active, sim.ev_scale[None], 1.0)
        idx = jnp.clip(sim.ev_link, 0, L - 1)
        ones = jnp.ones((L,), caps.dtype)
        scale = jax.vmap(lambda m: ones.at[idx].multiply(m))(mult)
        caps = caps * scale
    return jnp.maximum(caps, 0.0)


def _load_over(sim: CompiledSim, ts: jnp.ndarray) -> jnp.ndarray:
    """Evaluate the source load schedule on a tick grid: the generation
    rate of every instance at every tick, [T, I].

    Computed once per run outside the scan, like ``_caps_over``, and
    streamed as ``xs``: no trig per tick. Zero-amplitude sinusoids and
    never-starting steps (fleet padding) scale by exactly 1.0, so a
    constant-load sim padded into a loaded bucket generates bitwise its
    own ``gen_rate``.
    """
    with jax.named_scope("load"):
        I = sim.gen_rate.shape[0]
        gen = jnp.broadcast_to(sim.gen_rate[None, :], (ts.shape[0], I))
        if sim.load_amp.shape[0]:
            wave = jnp.sum(
                sim.load_amp[None] * jnp.sin(
                    sim.load_omega[None] * ts[:, None, None]
                    + sim.load_phase[None]), axis=1)          # [T, I]
            gen = gen * (1.0 + wave)
        if sim.load_t0.shape[0]:
            active = (ts[:, None] >= sim.load_t0[None]) & (
                ts[:, None] < sim.load_t1[None])              # [T, E]
            gen = gen * jnp.prod(
                jnp.where(active[:, :, None], sim.load_scale[None], 1.0),
                axis=1)                                       # [T, I]
        return jnp.maximum(gen, 0.0)


def _metrics_epilogue(sink, wait, load, caps_grid, path_w, dt: float,
                      t_event: float, win_s: float = 5.0,
                      pre_s: float = 20.0, frac: float = 0.95,
                      hot_thresh: float = 0.5) -> jnp.ndarray:
    """On-device reduction of one run's trajectories to the
    :data:`CAMPAIGN_METRICS` vector — THE metric definition for both the
    streamed campaign path (where only this ``[n_metrics]`` summary ever
    leaves the device) and the materialized path (``simulate`` /
    ``FleetRunner.run`` attach the same in-program vector to
    ``SimResult.metrics``), so streamed and materialized metrics are one
    computation, not two reimplementations that can drift.

    Mirrors the host-side ``SimResult`` properties (``throughput_tps``,
    ``avg_latency_s``, ``bottleneck_utilization``, ``dip_depth``,
    ``recovery_time_s``) up to float re-association — the host properties
    stay the readable reference; a consistency test pins the two together.
    Runs under the fleet vmap on padded shapes: padded flows wait 0 s with
    zero ``path_w`` weight, padded links carry zero load against huge
    capacity, so padding never moves a metric.

    The batch row count of the compiled program can move the last bits of
    these reductions (on CPU ``sink.sum()``, on a TPU v5e the trajectories
    as well), which is why the fleet engine never splits a bucket across
    devices: equal row counts give bitwise-equal rows.
    """
    T = sink.shape[0]
    warm = T // 4
    rate = sink / dt                                           # [T] MB/s
    lat_t = jnp.matmul(wait, path_w, precision=_HIGHEST)       # [T]
    # bottleneck utilization per SimResult.bottleneck_utilization: mean
    # per-tick utilization against the *scheduled* capacity, averaged over
    # links carrying >= hot_thresh of capacity (all-cold fallback: the
    # near-max links)
    util = (load[warm:] / jnp.maximum(caps_grid[warm:], _EPS)).mean(0)
    hot = util >= hot_thresh
    hot = jnp.where(hot.any(), hot, util >= util.max() * 0.999)
    utilization = (jnp.where(hot, util, 0.0).sum()
                   / jnp.maximum(hot.sum(), 1).astype(util.dtype))
    # transient metrics on the win_s-smoothed throughput (same edge
    # handling as SimResult._smooth_tput: divide by the actual sample
    # count so the trace boundaries don't fake a dip)
    w = max(int(round(win_s / dt)), 1)
    kern = jnp.ones((w,), rate.dtype)
    # per-tick sample count of the window: a constant of the static shape
    count = np.convolve(np.ones(T), np.ones(w), mode="same")
    r = (jnp.convolve(rate, kern, mode="same", precision=_HIGHEST)
         / count.astype(np.float32))
    i = min(int(round(t_event / dt)), T - 1)                   # static
    pre_mean = r[max(0, i - int(round(pre_s / dt))):max(i, 1)].mean()
    post = r[i:]
    post_min = post.min()
    dip = jnp.where(pre_mean > _EPS,
                    jnp.maximum((pre_mean - post_min)
                                / jnp.maximum(pre_mean, _EPS), 0.0), 0.0)
    # settling time, branchless (the host version's dynamic slice
    # `inside[first_out:]` becomes a masked argmax over a static window)
    P = post.shape[0]
    if P < 2:
        recovery = jnp.zeros((), rate.dtype)
    else:
        steady = post[-max(P // 4, 1):].mean()
        inside = (post >= frac * steady) & (post * frac <= steady)
        first_out = jnp.argmax(~inside)
        cand = inside & (jnp.arange(P) >= first_out)
        recovery = jnp.where(
            inside.all(), 0.0,
            jnp.where(cand.any(), jnp.argmax(cand).astype(rate.dtype) * dt,
                      jnp.inf))
    return jnp.stack([
        rate[warm:].mean(),
        r[-1],
        lat_t[warm:].mean(),
        utilization,
        dip,
        recovery.astype(rate.dtype),
        sink.sum(),
    ])


# --------------------------------------------------------------------------
# one simulation tick (shared by all policies)
# --------------------------------------------------------------------------
def _tick(sim: CompiledSim, Qs, Qr, x, dt, qcap, caps_t=None, enforce=True,
          R_t=None):
    """One fluid step against the *current* link capacities ``caps_t``.

    Fused dispatch chain: ``M_in`` and ``w_out`` have exactly one nonzero
    per flow column (the flow's destination / source instance), so the
    back half of the original chain collapses algebraically —
    ``M_in @ (consume·stall[dst]) = (M_in @ consume)·stall`` and
    ``w_out.T @ v = v[src]·w_of_flow`` — replacing two of the per-tick
    [I, F] matmuls with O(F) gathers. The remaining contractions stay as
    matmuls / masked reductions on purpose: under the fleet engine's vmap
    they lower to batched GEMMs and reduces, where segment/scatter forms
    would serialize on CPU backends.

    ``enforce`` gates the per-tick capacity enforcement *per scenario*
    (a python bool for standalone sims, a traced scalar under the fleet
    vmap): a genuinely static scenario batched into a scheduled pack keeps
    its exact static semantics — ``transfer = desired · 1.0``, bitwise the
    static path — instead of taking the enforcement arm on bitwise-equal
    but re-rounded scaled loads. This is what lets brute-force ``x_fixed``
    studies (whose rate vectors are deliberately link-infeasible) share
    buckets with scheduled scenarios.

    ``R_t`` is the tick's active routing matrix when a route bank is
    attached (``None`` — the common case — reads ``sim.R``, leaving the
    static-routing trace untouched). Transfers load the links of the
    *current* routes: the SDN controller has already reprogrammed the
    switches, whatever the policy's stale rate vector was solved against.
    """
    with jax.named_scope("tick"):
        R = sim.R if R_t is None else R_t
        dst, src = sim.dst_of_flow, sim.src_of_flow

        # receiver-window flow control: never overflow the receive buffer
        desired = jnp.minimum(jnp.minimum(Qs, x * dt),
                              jnp.maximum(qcap - Qr, 0.0))
        if caps_t is None or enforce is False:
            # static capacities: the policies' rate vectors are already
            # link-feasible, so the transfer needs no per-tick capacity check
            # (the pre-dynamics semantics — and cost — exactly)
            transfer = desired
        else:
            # the network enforces the *current* capacity: between controller
            # updates a failed/shrunk link moves at most caps_t·dt, whatever
            # the stale rate vector says. Feasible loads scale by exactly 1.0,
            # so a constant schedule reproduces the static path.
            load0 = jnp.matmul(desired, R, precision=_HIGHEST)       # [L] MB
            lscale = jnp.where(load0 > caps_t * dt,
                               jnp.clip(caps_t * dt / jnp.maximum(load0, _EPS),
                                        0.0, 1.0),
                               1.0)
            fscale = jnp.min(jnp.where(R > 0, lscale[None, :], jnp.inf),
                             axis=1)
            fscale = jnp.where(jnp.isfinite(fscale), fscale, 1.0)
            if enforce is not True:
                # traced per-scenario gate: un-enforced rows multiply by
                # exactly 1.0, which is bitwise the static transfer
                fscale = jnp.where(enforce, fscale, 1.0)
            transfer = desired * fscale
        Qs = Qs - transfer
        Qr = Qr + transfer

        # --- processing ---------------------------------------------------
        ratio = Qr / jnp.maximum(sim.p_in, _EPS)                     # [F]
        masked = jnp.where(sim.M_in > 0, ratio[None, :], jnp.inf)    # [I, F]
        join_amt = jnp.min(masked, axis=1)                           # [I]
        join_amt = jnp.where(jnp.isfinite(join_amt), join_amt, 0.0)
        join_amt = jnp.minimum(join_amt, sim.proc_rate * dt)
        consume_join = join_amt[dst] * sim.p_in                      # [F]

        total_in = jnp.matmul(sim.M_in, Qr, precision=_HIGHEST)      # [I]
        amt = jnp.minimum(total_in, sim.proc_rate * dt)
        frac = amt / jnp.maximum(total_in, _EPS)
        consume_any = Qr * frac[dst]

        consume = jnp.where(sim.join_dst, consume_join, consume_any)
        consume = jnp.minimum(consume, Qr)

        # sender-side backpressure (Storm's bounded send buffers): an instance
        # whose outgoing queue is full stalls its processing / generation
        in_i = jnp.matmul(sim.M_in, consume, precision=_HIGHEST)     # [I]
        out_i = sim.selectivity * in_i + sim.gen_rate * dt
        prod = out_i[src] * sim.w_of_flow                            # [F]
        space = jnp.maximum(qcap - Qs, 0.0)
        scale_f = jnp.clip(space / jnp.maximum(prod, _EPS), 0.0, 1.0)
        # droppable (latest-value) streams never backpressure upstream: the app
        # overwrites stale records in its send queue instead of blocking
        stalled = jnp.where((sim.w_out > 0) & ~sim.droppable[None, :],
                            scale_f[None, :], jnp.inf)
        stall_i = jnp.min(stalled, axis=1)                           # [I]
        stall_i = jnp.where(jnp.isfinite(stall_i), stall_i, 1.0)

        consume = consume * stall_i[dst]
        Qr = Qr - consume
        # stale-data discard: droppable join inputs keep only a small working
        # window; bytes beyond it were carried by the network for nothing.
        Qr = jnp.where(sim.droppable, jnp.minimum(Qr, 0.5), Qr)
        in_i = in_i * stall_i        # = M_in @ (consume·stall[dst]), fused
        out_i = sim.selectivity * in_i + sim.gen_rate * dt * stall_i
        Qs = Qs + out_i[src] * sim.w_of_flow   # = w_out.T @ out_i, fused
        # latest-value send queues hold only the freshest working window
        Qs = jnp.where(sim.droppable, jnp.minimum(Qs, 0.5), Qs)

        sink_in = jnp.where(sim.is_sink, in_i, 0.0)
        sink_mb = jnp.sum(sink_in)
        if sim.n_apps == 1:
            # single-app sims (the common case): the per-app split IS the total
            sink_mb_app = sink_mb[None]
        else:
            # small one-hot contraction instead of a segment_sum: under the
            # fleet vmap this is a batched GEMM where a scatter would serialize
            onehot = (sim.app_of_inst[None, :]
                      == jnp.arange(sim.n_apps)[:, None]).astype(sink_in.dtype)
            sink_mb_app = jnp.matmul(onehot, sink_in, precision=_HIGHEST)
        drain = consume / dt                                         # [F] MB/s

        # --- latency estimate (per source→sink path) ----------------------
        # raw per-flow waits only; the path-mean contraction (path_w · wait)
        # happens host-side on the true [F] slice, so the reported latency is
        # bitwise-identical however the fleet engine pads/packs the flow axis
        wait = jnp.minimum(
            Qs / jnp.maximum(x, _EPS) + Qr / jnp.maximum(drain, _EPS), _LAT_CAP
        )

        link_load = jnp.matmul(transfer, R,
                               precision=_HIGHEST) / dt              # [L] MB/s
        return Qs, Qr, transfer, drain, (sink_mb, sink_mb_app, wait, link_load)


# --------------------------------------------------------------------------
# policies
# --------------------------------------------------------------------------
def _tcp_rates(sim: CompiledSim, R, caps_t, Qs, Qr, prod_rate, drain_ewma,
               dt, qcap, order_carry):
    # sender-side demand, clamped by the receiver window (rwnd): a flow whose
    # receive buffer is full only demands its drain rate — real TCP frees the
    # bottleneck for other flows exactly this way.
    send = Qs / dt + prod_rate
    rwnd = jnp.maximum(qcap - Qr, 0.0) / dt + drain_ewma
    demand = jnp.minimum(send, rwnd)
    # fused fixed-trip max-min (demand caps folded into the fill): no
    # lax.while_loop in the per-tick hot path, so the policy batches under
    # vmap/SPMD exactly like appaware's allocator does. The demand-order
    # operand rides the scan carry (``order_carry``): adjacent ticks rarely
    # reorder the demand vector, so the solver only rebuilds its rank
    # machinery on an actual order change — bitwise-identical output either
    # way (see repro.core.tcp.maxmin_fused_step).
    x, order_carry, rebuilt = maxmin_fused_step(
        R, caps_t, demand, order_carry)
    x = jnp.where(sim.has_links, jnp.minimum(x, demand), INTERNAL_RATE)
    return x, order_carry, rebuilt


def _appaware_rates(sim: CompiledSim, R, caps_t, state: FlowState, dt_alloc,
                    backfill_iters=8, solver: str = "sort"):
    x = allocate(sim.program_at(caps_t, R=R), state, dt=dt_alloc,
                 backfill_iters=backfill_iters, solver=solver)
    return jnp.where(sim.has_links, x, INTERNAL_RATE)


@dataclasses.dataclass
class SimResult:
    sink_mb: np.ndarray        # [T]
    sink_mb_app: np.ndarray    # [T, A]
    latency: np.ndarray        # [T]
    link_load: np.ndarray      # [T, L]
    caps: np.ndarray           # [L] base capacities
    kinds: np.ndarray          # [L]
    tuples_per_mb: float
    dt: float
    caps_t: np.ndarray | None = None   # [T, L] per-tick capacities
    # [T] bool — ticks on which the tcp solver's demand-order cache rebuilt
    # its rank operand (all-False for non-tcp policies); observability for
    # the order cache's hit rate, not a correctness input
    order_rebuilds: np.ndarray | None = None
    # [n_metrics] — the in-program CAMPAIGN_METRICS summary computed by the
    # on-device epilogue (`_metrics_epilogue`). MB-based (tuples_per_mb is
    # applied by consumers); the campaign streaming path returns exactly
    # this vector, so "materialized metrics" and "streamed metrics" are by
    # construction one definition
    metrics: np.ndarray | None = None

    def metric(self, name: str) -> float:
        """One entry of the in-program epilogue vector by name (see
        ``CAMPAIGN_METRICS``)."""
        if self.metrics is None:
            raise ValueError("run did not compute the metric epilogue")
        return float(self.metrics[metric_index(name)])

    @property
    def n_order_rebuilds(self) -> int:
        return 0 if self.order_rebuilds is None else int(
            np.sum(self.order_rebuilds))

    def _warm(self, arr):
        return arr[arr.shape[0] // 4:]

    @property
    def caps_grid(self) -> np.ndarray:
        """Per-tick capacities [T, L] (static caps broadcast if no
        schedule ran)."""
        if self.caps_t is not None:
            return self.caps_t
        return np.broadcast_to(self.caps[None, :], self.link_load.shape)

    @property
    def throughput_tps(self) -> float:
        """App throughput: completed tuples/s at the sinks (post-warmup)."""
        return float(self._warm(self.sink_mb).mean() / self.dt * self.tuples_per_mb)

    @property
    def throughput_tps_per_app(self) -> np.ndarray:
        return np.asarray(
            self._warm(self.sink_mb_app).mean(0) / self.dt * self.tuples_per_mb
        )

    @property
    def avg_latency_s(self) -> float:
        return float(self._warm(self.latency).mean())

    def bottleneck_utilization(self, threshold: float = 0.5) -> float:
        """Avg utilization over bottlenecked links — links carrying ≥
        ``threshold`` of their capacity (paper Fig. 12 'average link
        throughput over all bottlenecked links'). Utilization is per-tick
        against the *scheduled* capacity, so a failed link at 10% capacity
        carrying 10% load counts as fully utilized, not idle."""
        load = self._warm(self.link_load)
        caps = self._warm(self.caps_grid)
        util_t = load / np.maximum(caps, _EPS)            # [T', L]
        util = util_t.mean(0)
        hot = util >= threshold
        if not hot.any():
            hot = util >= util.max() * 0.999
        return float(util[hot].mean())

    # ---- transient response (in-run schedules) -----------------------
    def _smooth_tput(self, win_s: float = 5.0) -> np.ndarray:
        """Sink throughput [T] (tuples/s) smoothed over ``win_s`` so the
        per-tick granularity doesn't alias the transient metrics. Edge
        windows divide by the actual sample count (a plain ``mode="same"``
        convolution would average in implicit zeros and fake a dip at the
        trace boundaries)."""
        w = max(int(round(win_s / self.dt)), 1)
        rate = self.sink_mb / self.dt * self.tuples_per_mb
        kern = np.ones(w)
        num = np.convolve(rate, kern, mode="same")
        den = np.convolve(np.ones_like(rate), kern, mode="same")
        return num / den

    def dip_depth(self, t_event: float, pre_s: float = 20.0,
                  win_s: float = 5.0) -> float:
        """Fractional throughput dip after an event at ``t_event``: how far
        the post-event minimum falls below the pre-event mean (0 = no dip,
        1 = complete stall)."""
        r = self._smooth_tput(win_s)
        i = min(int(round(t_event / self.dt)), r.shape[0] - 1)
        pre = r[max(0, i - int(round(pre_s / self.dt))):max(i, 1)]
        pre_mean = float(pre.mean()) if pre.size else 0.0
        if pre_mean <= _EPS:
            return 0.0
        post_min = float(r[i:].min()) if r[i:].size else pre_mean
        return max(0.0, (pre_mean - post_min) / pre_mean)

    def recovery_time_s(self, t_event: float, frac: float = 0.95,
                        win_s: float = 5.0) -> float:
        """Settling time after an event at ``t_event``: how long the
        smoothed throughput takes to first re-enter the ±(1−``frac``) band
        around its post-event steady state (mean over the last quarter of
        the post-event window) *after having left it* — covering both a
        dip-and-recover transient and a monotone decay onto a degraded
        plateau. 0 if it never leaves the band (no transient); ``inf`` if
        it leaves and never settles."""
        r = self._smooth_tput(win_s)
        i = min(int(round(t_event / self.dt)), r.shape[0] - 1)
        post = r[i:]
        if post.size < 2:
            return 0.0
        steady = float(post[-max(post.size // 4, 1):].mean())
        inside = (post >= frac * steady) & (post * frac <= steady)
        if inside.all():
            return 0.0
        first_out = int(np.argmax(~inside))
        ok = inside[first_out:]
        if not ok.any():
            return float("inf")
        return float(first_out + int(np.argmax(ok))) * self.dt


@functools.partial(
    jax.jit,
    static_argnames=("policy", "n_ticks", "dt", "upd_every",
                     "alpha", "n_groups", "solver", "with_metrics",
                     "t_event"),
)
def _run(sim: CompiledSim, policy: str, n_ticks: int, dt: float,
         upd_every: int, x_fixed=None, alpha: float = 0.5, n_groups: int = 8,
         qcap: float = 8.0, solver: str = "sort", enforce=None,
         with_metrics: bool = False, t_event: float = 0.0):
    F = sim.R.shape[0]
    # per-scenario capacity-enforcement gate (see _tick): standalone sims
    # enforce whenever they carry a schedule; the fleet engine passes a
    # traced scalar so static scenarios packed into scheduled buckets keep
    # exact static semantics
    if enforce is None:
        enforce = True
    z = jnp.zeros((F,), jnp.float32)
    # shape-static gate: sims compiled without a schedule (S = 0, E = 0)
    # skip the capacity stream, the per-tick enforcement, and the [T, L]
    # trajectory output entirely — the static path costs what it did
    # before in-run dynamics existed
    dynamic = sim.is_dynamic
    rerouting = sim.is_rerouting
    loaded = sim.is_loaded
    if dynamic or rerouting or loaded:
        ts = jnp.arange(n_ticks, dtype=jnp.float32) * dt
    if dynamic:
        caps_sched = _caps_over(sim, ts)              # [T, L]
    else:
        caps_sched = jnp.zeros((0, sim.caps.shape[0]), jnp.float32)
    # per-tick route-state stream (S_r > 0 only): the scan gathers the
    # active state's routing matrix from the precompiled bank — mid-run
    # rerouting without recompilation or lax.cond
    states_seq = _route_states_over(sim, ts) if rerouting else None
    # per-tick generation rates (S_g > 0 or E_g > 0 only): each tick runs
    # on a copy of the sim whose gen_rate is that tick's row
    gen_seq = _load_over(sim, ts) if loaded else None

    no_rebuild = jnp.zeros((), bool)

    def policy_rates(R_upd, caps_t, Qs, Qr, B, prod_rate, drain_ewma, v_acc,
                     ls, lr, mu, oc):
        """→ (rates, order_carry', rebuilt). Only tcp threads a real order
        carry; the rest pass ``oc`` through untouched (an empty tuple, so
        the scan carry stays policy-minimal — statically gated below)."""
        if policy == "tcp":
            return _tcp_rates(sim, R_upd, caps_t, Qs, Qr, prod_rate,
                              drain_ewma, dt, qcap, oc)
        if policy == "fixed":
            x = jnp.where(sim.has_links, x_fixed, INTERNAL_RATE)
        elif policy == "appaware":
            # the application profiler reports the *useful* receiver backlog
            # B (bytes transferred but not yet joined — stale drops still
            # count as backlog: the paper's memory-overrun signal, Fig. 5)
            st = FlowState(ls_t=ls, lr_t=lr, v=v_acc, ls_t1=Qs, lr_t1=B)
            x = _appaware_rates(sim, R_upd, caps_t, st, dt * upd_every,
                                solver=solver)
        elif policy == "appfair":
            prio = group_by_throughput(mu, n_groups)
            x = strict_priority_alloc(
                R_upd, caps_t, sim.app_of_flow, prio, n_groups=n_groups
            )
            x = jnp.where(sim.has_links, x, INTERNAL_RATE)
        else:
            raise ValueError(policy)
        return x, oc, no_rebuild

    def body(carry, xs):
        tick, caps_t, state_t, gen_t = xs
        (Qs, Qr, B, x, v_acc, ls, lr, prod_rate, drain_ewma, mu,
         mu_acc, oc) = carry
        sim_t = (sim if gen_t is None
                 else dataclasses.replace(sim, gen_rate=gen_t))
        caps_upd = sim.caps if caps_t is None else caps_t
        # active routing matrix: one [F, L] bank gather per tick. The
        # policies re-solve against R(t_upd) at their update ticks, so
        # appaware/tcp shift traffic off failed links as soon as their
        # controller interval fires.
        R_t = None if state_t is None else sim.route_bank[state_t]
        R_upd = sim.R if R_t is None else R_t

        def updated(_):
            mu_new = (ewma_throughput(mu, mu_acc / (dt * upd_every), alpha)
                      if policy == "appfair" else mu)
            x_new, oc_new, reb = policy_rates(
                R_upd, caps_upd, Qs, Qr, B, prod_rate, drain_ewma,
                v_acc, ls, lr, mu_new, oc)
            return (x_new, z, Qs, B, mu_new, jnp.zeros_like(mu_acc),
                    oc_new, reb)

        def kept(_):
            return x, v_acc, ls, lr, mu, mu_acc, oc, no_rebuild

        if upd_every == 1:
            # every-tick policies (tcp/fixed defaults): no lax.cond in the
            # hot loop — the branch dispatch and its fusion barrier go away
            x, v_acc, ls, lr, mu, mu_acc, oc, reb = updated(None)
        else:
            do_upd = (tick % upd_every) == 0
            x, v_acc, ls, lr, mu, mu_acc, oc, reb = jax.lax.cond(
                do_upd, updated, kept, None)

        Qs1, Qr1, transfer, drain, (sink, sink_app, wait, load) = _tick(
            sim_t, Qs, Qr, x, dt, qcap, caps_t=caps_t, enforce=enforce,
            R_t=R_t)
        # per-policy carry pieces are gated *statically*: a policy that
        # never reads prod_rate/B/mu_acc doesn't pay their per-tick ops
        if policy == "tcp":
            t_in = jnp.matmul(sim.M_in, transfer, precision=_HIGHEST)
            out_i = sim.selectivity * t_in + sim_t.gen_rate * dt
            prod_rate = out_i[sim.src_of_flow] * sim.w_of_flow / dt
            drain_ewma = 0.5 * drain_ewma + 0.5 * drain
        if policy == "appaware":
            B = jnp.clip(B + transfer - drain * dt, 0.0, 8.0 * qcap)
            v_acc = v_acc + transfer
        if policy == "appfair":
            mu_acc = mu_acc + sink_app
        return (
            (Qs1, Qr1, B, x, v_acc, ls, lr, prod_rate,
             drain_ewma, mu, mu_acc, oc),
            (sink, sink_app, wait, load, reb),
        )

    mu0 = jnp.zeros((sim.n_apps,), jnp.float32)
    # the demand-order cache only exists on the tcp path: other policies
    # carry an empty pytree, so their scan carries cost exactly what they
    # did before the order cache existed
    oc0 = maxmin_order_init(F) if policy == "tcp" else ()
    carry0 = (z, z, z, z, z, z, z, z, z, mu0, mu0, oc0)
    # None is an empty pytree leaf: static sims stream no capacity xs,
    # static-routing sims no state index, constant-load sims no rate grid
    xs = (jnp.arange(n_ticks), caps_sched if dynamic else None, states_seq,
          gen_seq)
    _, ys = jax.lax.scan(body, carry0, xs)
    if not with_metrics:
        return (*ys, caps_sched)
    # on-device metric epilogue: reduce the trajectories to the
    # CAMPAIGN_METRICS summary *inside the program*, so a streaming caller
    # can fetch [n_metrics] floats and leave the [T, ...] arrays on device
    sink, _sink_app, wait, load, _reb = ys
    caps_grid = (caps_sched if dynamic else
                 jnp.broadcast_to(sim.caps[None, :],
                                  (n_ticks, sim.caps.shape[0])))
    metrics = _metrics_epilogue(sink, wait, load, caps_grid, sim.path_w,
                                dt, t_event)
    return (*ys, caps_sched, metrics)


def result_from_padded_row(sim: CompiledSim, b: int, dt: float,
                           sink, sink_app, wait, load, rebuilds,
                           caps_sched, metrics) -> SimResult:
    """Slice row ``b`` of a padded bucket's (host-side) outputs back to
    ``sim``'s true shapes — the ONE definition of a scenario's
    :class:`SimResult`, shared by the materialized fleet path and the
    streaming campaign collector so they cannot drift apart."""
    F = sim.R.shape[0]
    L, A = sim.caps.shape[0], sim.n_apps
    return SimResult(
        sink_mb=sink[b],
        sink_mb_app=sink_app[b][:, :A],
        # path-mean latency on the true [F] slice: bitwise-independent of
        # bucket padding and pack structure
        latency=wait[b][:, :F] @ np.asarray(sim.path_w),
        link_load=load[b][:, :L],
        caps=np.asarray(sim.caps),
        kinds=np.asarray(sim.kinds),
        tuples_per_mb=sim.tuples_per_mb,
        dt=dt,
        caps_t=caps_sched[b][:, :L] if sim.is_dynamic else None,
        order_rebuilds=rebuilds[b],
        metrics=None if metrics is None else metrics[b],
    )


def smoke_seconds(seconds: float, cap: float = 120.0) -> float:
    """CI short-run mode: ``REPRO_SMOKE=1`` caps run length so the tier-1
    suite finishes in minutes on a CPU runner (same dt, same warmup logic)."""
    if os.environ.get("REPRO_SMOKE", "").strip() not in ("", "0"):
        return min(seconds, cap)
    return seconds


def resolve_upd_every(policy: str, dt: float, upd_every: int | None) -> int:
    if upd_every is None:
        return int(round(5.0 / dt)) if policy in ("appaware", "appfair") else 1
    return upd_every


def simulate(
    sim: CompiledSim,
    policy: str = "tcp",
    seconds: float = 600.0,
    dt: float = 0.5,
    upd_every: int | None = None,
    x_fixed=None,
    alpha: float = 0.5,
    n_groups: int = 8,
    qcap: float = 8.0,
    solver: str = "sort",
    t_event: float = 0.0,
) -> SimResult:
    """Run one experiment (paper §VI: 600 s runs, Δt = 5 s allocator)."""
    n_ticks = int(round(smoke_seconds(seconds) / dt))
    upd_every = resolve_upd_every(policy, dt, upd_every)
    sink, sink_app, wait, load, rebuilds, caps_sched, metrics = _run(
        sim, policy, n_ticks, dt, upd_every,
        x_fixed=None if x_fixed is None else jnp.asarray(x_fixed, jnp.float32),
        alpha=alpha, n_groups=n_groups, qcap=qcap, solver=solver,
        with_metrics=True, t_event=float(t_event),
    )
    return SimResult(
        sink_mb=np.asarray(sink),
        sink_mb_app=np.asarray(sink_app),
        latency=np.asarray(wait) @ np.asarray(sim.path_w),
        link_load=np.asarray(load),
        caps=np.asarray(sim.caps),
        kinds=np.asarray(sim.kinds),
        tuples_per_mb=sim.tuples_per_mb,
        dt=dt,
        caps_t=np.asarray(caps_sched) if sim.is_dynamic else None,
        order_rebuilds=np.asarray(rebuilds),
        metrics=np.asarray(metrics),
    )
