"""App-aware online bandwidth allocation (paper §IV, Algorithm 1).

Pure-JAX, jittable, vectorized over links. Every ``dt`` the allocator maps the
observed :class:`repro.core.flowstate.FlowState` to a rate vector ``x`` [F]:

  1. per bottleneck *uplink* (Fork stage) solve eq. (3)
         min_x max_f w_f / x_f        s.t. Σ_f x_f = C_u,  x ≥ 0
     with w_f = V_f + 2 L_f^s(t+dt) − L_f^s(t). The min-max is attained when
     all transfer times w_f/x_f are equal → closed form x_f = C_u w_f / Σ w.

  2. per bottleneck *downlink* (Join stage) solve eq. (4)
         min_x max_f (L_f^r(t+dt) + x_f dt) / ρ_f     s.t. Σ_f x_f = C_d
     with ρ_f the receiver drain rate. Equalizing the queue-drain time θ
     gives the water-filling solution x_f = max(0, (θ ρ_f − L_f^r)/dt) with
     θ fixed by Σ_f x_f(θ) = C_d. Flows whose join partner is starved
     (small L^r, healthy ρ) get MORE bandwidth — the paper's stall-avoidance.

  3. x_f = min(x_f^u, x_f^d)  (Alg. 1 line 22);

  4. congested *internal* links scale their flows down proportionally and a
     flow takes the min across its links (lines 24–29);

  5. a backfill pass re-distributes leftover capacity proportionally to the
     previous pass's shares (§VI-C, link-utilization experiment).

The batched per-link solvers also exist as a Pallas TPU kernel
(``repro.kernels.waterfill``) — at datacenter scale (10⁴ links × 10³ flows
each interval) this is the allocator's compute hot-spot.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.flowstate import FlowState
from repro.net.topology import LinkKind
from repro.spans import span

_EPS = 1e-9
_INF = jnp.inf

# f32 contractions run at HIGHEST: the chip's default is one bf16 pass,
# which rounds the rates, demands and queue volumes these products carry
_HIGHEST = jax.lax.Precision.HIGHEST

# Trace-time auto-chunk threshold for the sort solver's link axis: above
# 2x this many links, `allocate(block_links=None)` switches to
# `_per_link_rates_chunked` in blocks of this size (the [L, F] solver
# intermediates stop fitting in cache well before datacenter scale).
# Simulator topologies (L <= ~32) always stay on the single-pass form.
ALLOC_BLOCK_LINKS = 256


def solve_uplink(weights: jnp.ndarray, mask: jnp.ndarray, capacity) -> jnp.ndarray:
    """Eq. (3): proportional-to-demand allocation on one uplink.

    weights: [F] demand w_f (≥ 0); mask: [F] flows on this link; capacity: C_u.
    Returns x [F] with x·mask summing to C_u (if any flow is masked).
    """
    w = jnp.maximum(weights, 0.0) * mask
    total = jnp.sum(w)
    n = jnp.sum(mask)
    # all-zero demand: fall back to equal split (still work-conserving)
    w = jnp.where(total > _EPS, w, mask)
    total = jnp.where(total > _EPS, total, jnp.maximum(n, 1.0))
    return capacity * w / total


def solve_downlink(
    backlog: jnp.ndarray,
    rho: jnp.ndarray,
    mask: jnp.ndarray,
    capacity,
    dt: float,
) -> jnp.ndarray:
    """Eq. (4): equalize queue-drain times via exact water-filling (one sort).

    backlog: [F] L_f^r(t+dt); rho: [F] drain rates (>0); mask: [F]; C_d.

    θ solves Σ_f max(0, (θ ρ_f − L_f)/dt) = C. x_f(θ) is piecewise-linear,
    nondecreasing; flows activate at θ_f = L_f/ρ_f. Sorting by θ_f and
    scanning prefixes yields the unique consistent active set.
    """
    F = backlog.shape[0]
    rho = jnp.maximum(rho, _EPS)
    theta_act = jnp.where(mask > 0, backlog / rho, _INF)  # activation points
    order = jnp.argsort(theta_act)
    th_s = theta_act[order]
    rho_s = jnp.where(mask > 0, rho, 0.0)[order]
    L_s = jnp.where(mask > 0, backlog, 0.0)[order]
    cum_rho = jnp.cumsum(rho_s)
    cum_L = jnp.cumsum(L_s)
    # candidate θ for prefix of size k (index k-1)
    theta_k = (capacity * dt + cum_L) / jnp.maximum(cum_rho, _EPS)
    next_th = jnp.concatenate([th_s[1:], jnp.full((1,), _INF)])
    ks = jnp.arange(F)
    n_active = jnp.sum(mask).astype(jnp.int32)
    valid = (
        (theta_k >= th_s)
        & (theta_k <= next_th)
        & (ks < n_active)
        & jnp.isfinite(th_s)
    )
    # the unique valid prefix (fall back to the full active set)
    k_star = jnp.where(jnp.any(valid), jnp.argmax(valid), jnp.maximum(n_active - 1, 0))
    theta = theta_k[k_star]
    x = jnp.maximum(theta * rho - backlog, 0.0) / dt * mask
    # numerical cleanup: renormalize to the capacity exactly
    s = jnp.sum(x)
    x = jnp.where(s > _EPS, x * (capacity / s), x)
    return x


class LinkProgram(NamedTuple):
    """Static routing context for the allocator (from a Topology)."""

    R: jnp.ndarray          # [F, L] binary routing matrix
    capacity: jnp.ndarray   # [L]
    kind: jnp.ndarray       # [L] LinkKind values


def _per_link_rates_vmap(program: LinkProgram, state: FlowState, dt: float):
    """Reference path: vmap the per-link solvers across ALL links; select by
    link kind. One argsort *per link* — kept as the parity oracle for the
    fused solve below (and for the Pallas kernel's CPU cross-check)."""
    w_up = state.uplink_demand()
    rho = state.drain_rate(dt)
    L_r = state.lr_t1

    def one_link(r_col, cap, kind):
        mask = (r_col > 0).astype(w_up.dtype)
        x_u = solve_uplink(w_up, mask, cap)
        x_d = solve_downlink(L_r, rho, mask, cap, dt)
        return jnp.where(kind == int(LinkKind.DOWNLINK), x_d, x_u)

    # [L, F]
    return jax.vmap(one_link, in_axes=(1, 0, 0))(
        program.R, program.capacity, program.kind
    )


def _flow_sort_ctx(state: FlowState, dt: float):
    """Flow-axis preprocessing shared by every link of a solve: the
    per-flow inputs (demand w, backlog L^r, drain ρ) are the same for all
    links — only the on-link mask differs — so the downlink water-filling
    activation order ``θ_f = L_f/ρ_f`` is ONE global permutation, computed
    once (one argsort total, vs one per link in the vmap reference)."""
    rho = jnp.maximum(state.drain_rate(dt), _EPS)
    L_r = state.lr_t1
    theta_act = L_r / rho
    order = jnp.argsort(theta_act)
    return {
        "w_pos": jnp.maximum(state.uplink_demand(), 0.0),
        "rho": rho, "L_r": L_r, "order": order,
        "th_s": theta_act[order], "rho_s": rho[order], "L_s": L_r[order],
    }


def _solve_link_block(mask, cap, kind, ctx, dt: float):
    """Fused eqs. (3)/(4) for one [B_l, F] block of links against the
    shared flow context — the single source of the solver math for both
    the full-axis and the chunked paths.

    Per link, the prefix sums over its masked flows in global θ-order
    equal the prefix sums over its own sorted active set, so masked
    batched cumsums replace per-link sorts; the unique consistent active
    prefix (and the uplink proportional closed form) drop out of one
    [B_l, F] pass."""
    capc = cap[:, None]                                  # [B_l, 1]
    F = mask.shape[1]

    # ---- eq. (3): proportional-to-demand ------------------------------
    wm = ctx["w_pos"][None, :] * mask
    tot = jnp.sum(wm, axis=1, keepdims=True)
    n = jnp.sum(mask, axis=1, keepdims=True)
    wm = jnp.where(tot > _EPS, wm, mask)        # zero demand: equal split
    tot = jnp.where(tot > _EPS, tot, jnp.maximum(n, 1.0))
    x_up = capc * wm / tot

    # ---- eq. (4): batched prefix scans in global θ-order ---------------
    m_s = mask[:, ctx["order"]]                          # [B_l, F]
    cum_rho = jnp.cumsum(ctx["rho_s"][None, :] * m_s, axis=1)
    cum_L = jnp.cumsum(ctx["L_s"][None, :] * m_s, axis=1)
    theta_k = (capc * dt + cum_L) / jnp.maximum(cum_rho, _EPS)
    # active-set selection à la weighted simplex projection (Duchi et al.):
    # the consistent prefix is the LARGEST masked k whose candidate level
    # still covers its own activation point, θ_k ≥ θ̂_(k) — prefixes beyond
    # it would include flows that the candidate level cannot activate
    ks = jnp.arange(F)[None, :]
    ok = (m_s > 0) & (theta_k >= ctx["th_s"][None, :])
    k_star = jnp.max(jnp.where(ok, ks, 0), axis=1)       # [B_l]
    theta = jnp.take_along_axis(theta_k, k_star[:, None], axis=1)
    x_dn = jnp.maximum(theta * ctx["rho"][None, :] - ctx["L_r"][None, :],
                       0.0) / dt * mask
    s = jnp.sum(x_dn, axis=1, keepdims=True)
    x_dn = jnp.where(s > _EPS, x_dn * (capc / s), x_dn)

    is_down = (kind == int(LinkKind.DOWNLINK))[:, None]
    return jnp.where(is_down, x_dn, x_up)


def _min_over_links(rows, mask, kind):
    """Alg. 1 line 22 collapsed: min(x^u, x^d) over a flow's links is the
    min of the per-link rows [L, F] over its non-internal links (each row
    already carries the kind-appropriate solve), so one masked reduction
    replaces the two per-kind passes. mask: [L, F] on-link (> 0); kind:
    [L]. Returns [F], +inf for a flow on no such link."""
    sel = (kind != int(LinkKind.INTERNAL))[:, None] & (mask > 0)
    return jnp.min(jnp.where(sel, rows, _INF), axis=0)


def _per_link_rates(program: LinkProgram, state: FlowState, dt: float):
    """Fused batched [L, F] solve of eqs. (3) and (4) for every link at
    once: one global argsort (:func:`_flow_sort_ctx`) + one
    :func:`_solve_link_block` pass over the full link axis."""
    mask = (program.R.T > 0).astype(jnp.float32)         # [L, F]
    return _solve_link_block(mask, program.capacity, program.kind,
                             _flow_sort_ctx(state, dt), dt)


def _per_link_rates_chunked(program: LinkProgram, state: FlowState,
                            dt: float, block_links: int):
    """Chunked-links variant of the fused solve, with Alg. 1 line 22 folded
    in: the same :func:`_solve_link_block` math, but the link axis is
    processed in ``block_links`` chunks under ``lax.scan`` (sequential),
    and each block is reduced by :func:`_min_over_links` into a running
    per-flow minimum. Returns x [F], the line-22 rates, equal bitwise to
    the combine of the full-axis rows (min is exact in any order).

    Only [block_links, F] solver values exist: no per-link [L, F] rows
    are stacked or read back — at 10⁴ links × 10³ flows that's ~4 MB of
    working set instead of a ~40 MB output written block by block. The
    routing matrix stays full-size as the input. The flow context (one
    global argsort) is shared across chunks, exactly as in the fused form.
    """
    L, F = program.R.shape[1], program.R.shape[0]
    ctx = _flow_sort_ctx(state, dt)

    def chunk(x, args):
        mask, cap, kind = args                      # [blk, F], [blk], [blk]
        rows = _solve_link_block(mask, cap, kind, ctx, dt)
        return jnp.minimum(x, _min_over_links(rows, mask, kind)), None

    blk = max(int(block_links), 1)
    n_chunks = -(-L // blk)
    pad = n_chunks * blk - L
    # padded links: empty mask, INTERNAL kind -> never selected by the min
    maskT = jnp.pad((program.R.T > 0).astype(jnp.float32), ((0, pad), (0, 0)))
    cap_p = jnp.pad(program.capacity, (0, pad))
    kind_p = jnp.pad(program.kind, (0, pad),
                     constant_values=int(LinkKind.INTERNAL))
    x, _ = jax.lax.scan(chunk, jnp.full((F,), _INF, maskT.dtype),
                        (maskT.reshape(n_chunks, blk, F),
                         cap_p.reshape(n_chunks, blk),
                         kind_p.reshape(n_chunks, blk)))
    return x


def _per_link_rates_pallas(program: LinkProgram, state: FlowState, dt: float):
    """Same [L, F] solve through the batched Pallas waterfill kernel
    (``repro.kernels.waterfill``) — bisection on θ instead of the sort.

    The per-flow state ships as [F] vectors (``waterfill_flows``); only the
    on-link mask is [L, F], so no dense per-link broadcasts of w/backlog/ρ
    are materialized. INTERNAL links are fed as uplinks; ``allocate`` never
    reads their rows (it handles internal links by proportional
    scale-down), so only the UPLINK/DOWNLINK selection has to agree with
    the exact solvers.
    """
    from repro.kernels.waterfill.ops import waterfill_flows  # avoids cycle

    mask = (program.R.T > 0).astype(jnp.float32)          # [L, F]
    kind01 = (program.kind == int(LinkKind.DOWNLINK)).astype(jnp.int32)
    # bigger link blocks at scale keep the grid small (10⁴ links / 128 =
    # 79 programs); tiny programs keep the padding overhead low below that.
    # The flow axis walks in 256-lane chunks once F outgrows one chunk, so
    # F = 10³–10⁴ never runs its reductions over one giant lane block.
    L, F = mask.shape
    block_links = 8 if L <= 512 else 128
    block_flows = None if F <= 256 else 256
    return waterfill_flows(
        state.uplink_demand(), state.lr_t1, state.drain_rate(dt),
        mask, program.capacity, kind01, dt=dt, block_links=block_links,
        block_flows=block_flows)


def backfill(x: jnp.ndarray, program: LinkProgram, iters: int = 8,
             damping: float = 0.9) -> jnp.ndarray:
    """§VI-C backfill: hand leftover link capacity to flows proportionally to
    their share from the previous pass, never violating any link.

    A flow's headroom min over its links of ``x_f·resid_l/load_l`` factors as
    ``x_f · min_l(resid_l/load_l)`` (x ≥ 0), so each iteration reduces to one
    [L] residual-ratio vector and one masked min — the [F, L] ``share`` and
    ``gain`` intermediates of the naive form are never materialized.
    """
    R, cap = program.R, program.capacity
    on_link = R > 0
    on_net = jnp.sum(R, axis=1) > 0  # flows that traverse ≥1 link

    def body(_, x):
        load = jnp.matmul(x, R, precision=_HIGHEST)    # [L]
        ratio = jnp.maximum(cap - load, 0.0) / jnp.maximum(load, _EPS)
        r_min = jnp.min(jnp.where(on_link, ratio[None, :], _INF), axis=1)
        inc = jnp.where(on_net & jnp.isfinite(r_min), x * r_min, 0.0)
        return x + damping * inc

    with jax.named_scope("backfill"):
        return jax.lax.fori_loop(0, iters, body, x)


@functools.partial(jax.jit, static_argnames=("dt", "backfill_iters", "solver",
                                             "block_links"))
def allocate(
    program: LinkProgram,
    state: FlowState,
    dt: float = 1.0,
    backfill_iters: int = 8,
    solver: str = "sort",
    block_links: int | None = None,
) -> jnp.ndarray:
    """Algorithm 1, one interval: FlowState -> rate vector x [F] (MB/s).

    solver: "sort" — exact sort-based per-link solves (CPU-friendly);
            "pallas" — the batched bisection waterfill kernel (TPU-friendly;
            interpret mode off-TPU). Both satisfy the same KKT conditions.
    block_links: with the "sort" solver, process links in chunks of this
            size (sequential ``lax.scan``) and fold the line-22 min into
            the loop, so only [block_links, F] solver values exist —
            exact same results, bounded working set at datacenter link
            counts (ignored by "pallas", which tiles internally). ``None``
            (the default) dispatches at trace time on the static link
            count: single-pass below ``2 * ALLOC_BLOCK_LINKS`` links
            (every simulator topology — the fused form's XLA program is
            unchanged there), chunks of ``ALLOC_BLOCK_LINKS`` above it.
            Pass ``0`` to force the single-pass form at any size.
    """
    kind = program.kind
    per_link = None                     # [L, F] rows of the unchunked paths
    with jax.named_scope("per_link"):
        if solver == "sort":
            if block_links is None and program.R.shape[1] > 2 * ALLOC_BLOCK_LINKS:
                block_links = ALLOC_BLOCK_LINKS
            if block_links:                 # line 22 runs inside the loop
                x = _per_link_rates_chunked(program, state, dt,
                                            block_links)           # [F]
            else:
                per_link = _per_link_rates(program, state, dt)     # [L, F]
        elif solver == "pallas":
            per_link = _per_link_rates_pallas(program, state, dt)  # [L, F]
        else:
            raise ValueError(f"unknown solver {solver!r}")
    if per_link is not None:
        x = _min_over_links(per_link, program.R.T, kind)           # line 22
    x = jnp.where(jnp.isfinite(x), x, 0.0)     # flows with no links: handled by caller

    # Internal links: proportional scale-down, min across links (lines 24-29)
    load = jnp.matmul(x, program.R, precision=_HIGHEST)        # [L]
    is_int = kind == int(LinkKind.INTERNAL)
    scale_l = jnp.where(
        is_int & (load > program.capacity),
        program.capacity / jnp.maximum(load, _EPS),
        1.0,
    )
    per_flow_scale = jnp.where(
        (program.R > 0) & is_int[None, :], scale_l[None, :], 1.0
    ).min(axis=1)
    x = x * per_flow_scale

    if backfill_iters:
        x = backfill(x, program, iters=backfill_iters)
    return x


class OnlineAllocator:
    """Alg. 1 driver: wraps a static LinkProgram; call once per Δt."""

    def __init__(self, R, capacity, kind, dt: float = 1.0,
                 backfill_iters: int = 8, solver: str = "sort"):
        self.program = LinkProgram(
            R=jnp.asarray(R, jnp.float32),
            capacity=jnp.asarray(capacity, jnp.float32),
            kind=jnp.asarray(kind, jnp.int32),
        )
        self.dt = float(dt)
        self.backfill_iters = int(backfill_iters)
        self.solver = solver

    def __call__(self, state: FlowState) -> jnp.ndarray:
        # the span covers argument transfer and the enqueue, not the solve
        with span("allocator.launch"):
            return allocate(self.program, state, dt=self.dt,
                            backfill_iters=self.backfill_iters,
                            solver=self.solver)

    @classmethod
    def from_topology(cls, topo, flows, **kw) -> "OnlineAllocator":
        return cls(
            topo.routing_matrix(flows), topo.capacities, topo.link_kinds, **kw
        )
