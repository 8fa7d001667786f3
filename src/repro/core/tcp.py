"""TCP baseline: per-flow max-min fair *rate* allocation (paper §VI-A.3).

The paper's baseline is the default transport of Storm/Heron/Flink — TCP
congestion control, which (idealized) converges to max-min fair rates among
flows sharing bottleneck links.

Three implementations live here:

* :func:`maxmin_fused` — the **hot-path solver**: a fused, fixed-trip-count
  progressive fill with per-flow demand caps folded directly into each
  round. ONE demand-rank matrix (the argsort as a 0/1 GEMM operand) is
  shared by every link; per round each link's exact saturation water level
  (``Σ_f min(d_f, θ) = resid_l``) drops out of batched rank-prefix sums —
  the allocator's weighted-simplex prefix rule (`_solve_link_block`)
  generalized to multi-link coupling. Every *locally minimal* link (no
  cheaper neighbor in the link-conflict graph) freezes per round, so the
  trip count tracks the depth of the strictly-increasing bottleneck-level
  chain, not the link count — and because the trip count is static there
  is **no ``lax.while_loop``**: the solver batches under `vmap`/SPMD
  sharding with zero data-dependent control flow.

  Two *forms* of the per-round water-level evaluation exist behind a
  shape-dependent crossover dispatched at trace time
  (:data:`MAXMIN_CROSSOVER_F`): the **GEMM form** keeps the rank prefixes
  as one ``[F+1, F] @ [F, 2L]`` matmul against the order-only operand
  ``[W; 1]`` (demand folded into the *right* operand — exact in {0, 1}
  arithmetic, so bitwise-identical to the PR-4 stacked ``[2F+2, F]``
  layout), which wins in the op-overhead-bound small-F regime where
  batched sorts serialize on CPU; the **sorted form** replaces the
  O(F²·L) GEMM with one stable argsort + two batched cumsums (O(F·L)),
  which wins once F is large enough that FLOPs beat op overhead. The GEMM
  form additionally chunks its candidate rows in ``block_flows`` blocks
  (mirroring the allocator's ``block_links``) so the [F, L] candidate
  intermediates stay cache-bounded at mid-size F.

* :func:`maxmin_fused_step` / :func:`maxmin_order_init` — the **order-
  cached** variant for per-tick re-solves inside a scan: the rank operand
  is a pure function of the *demand order*, which between adjacent control
  ticks changes rarely, so the carry holds ``(valid, perm, A1)`` and an
  O(F) monotonicity check against the carried permutation decides whether
  the carried operand is still the exact stable order. The rebuild path
  is the same construction as the fresh solve (W from lexicographic
  comparisons), and a kept operand is bitwise-identical to a rebuilt one
  (W is a function of the order alone), so carried and fresh solves agree
  bitwise. The permutation rebuild derives from W's row sums via a
  one-hot contraction — no argsort in the rebuild path, so the carried
  step stays GEMM/elementwise-only under the fleet vmap.

* :func:`maxmin_rates` / :func:`demand_limited_maxmin` — the while-loop
  progressive-filling oracles (same pattern as the allocator's
  `_per_link_rates_vmap`), plus :func:`demand_limited_maxmin_np`, a plain
  numpy sequential reference with unbounded rounds.
  ``demand_limited_maxmin`` is true sequential progressive filling with
  demand caps (per-link levels by bisection — independent math from both
  fused forms); the PR-4 clamp-and-resolve wrapper it replaces froze a
  flow at its demand whenever its *demand-free* max-min share covered the
  demand, which is unsound — demand caps elsewhere can raise competitors'
  rates and pull the flow's final level *below* its demand (seed 5041 of
  the property suite) — so the oracle now passes the KKT certificate
  unconditionally.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-9
_INF = jnp.inf

# f32 contractions run at HIGHEST: the chip's default is one bf16 pass,
# which rounds the rates, demands and queue volumes these products carry
_HIGHEST = jax.lax.Precision.HIGHEST

# Trip count of the hot-path fused fill. Each round freezes EVERY locally
# minimal bottleneck level in parallel, so rounds + 1 (the closing sweep
# resolves one further level) must cover the depth of the strictly-
# increasing bottleneck-level chain in the link-conflict graph — measured
# ≤ 3 across the seed-corpus routing structure, which 2 + sweep covers
# exactly: fleet trajectories are bitwise-identical to the while-loop
# oracle's at this setting (tests/test_maxmin_fused.py::TestCorpusRounds).
# The per-tick policy cost is (rounds + 1) water-level evaluations on a
# kernel-overhead-bound CPU path, so the default deliberately carries no
# slack. Deeper instances stay link-feasible (the sweep assigns
# min(demand, bottleneck level), which provably never oversubscribes a
# link); only the max-min refinement of the tail levels would be
# approximate. Pass ``rounds=None`` for the provably exact shape bound
# min(F, L) + 1 (each round saturates ≥ 1 link or demand-freezes every
# remaining flow).
FILL_ROUNDS = 2

_RTOL = 1e-6   # tie tolerance for water-level comparisons (relative)
_ATOL = 1e-6   # ... and absolute, for levels near zero

# Crossover between the two water-level forms, by (padded) flow count at
# trace time: below it the rank-prefix GEMM form wins (op-overhead-bound
# CPU regime — batched per-link cumsums/gathers serialize), at or above it
# the argsort+cumsum form's O(F·L) beats the GEMM's O(F²·L). Calibrated by
# the ``maxmin_crossover`` rows of ``benchmarks/allocator.py`` (vmap-8,
# the fleet engine's batching shape): 256 is the first grid point where
# the sorted form won in BOTH calibration runs (run-to-run noise on the
# shared container flips the 96–192 band; sorted's margin grows to ~2x by
# F=512) — see BENCH_allocator.json. Every fleet-corpus bucket (F ≤ 28)
# sits well below it, so the fleet path stays on the bitwise-stable GEMM
# form.
MAXMIN_CROSSOVER_F = 256

# GEMM-form candidate rows are processed in chunks of this size once F
# outgrows ``2 * MAXMIN_BLOCK_FLOWS`` (mirroring the allocator's
# ``block_links``): the [F, L] candidate/prefix intermediates of a
# mid-size instance stay cache-bounded while small (fleet-corpus) shapes
# keep the single-pass — and bitwise-unchanged — layout.
MAXMIN_BLOCK_FLOWS = 64

# rounds at or below this unroll as straight-line code (bitwise-identical
# to the fori_loop form; lets XLA fuse the elementwise chains across round
# boundaries instead of walling them behind a while op), above it the
# rolled loop keeps compile time bounded for ``rounds=None`` deep bounds
_UNROLL_ROUNDS = 4


@functools.partial(jax.jit, static_argnames=())
def maxmin_rates(R: jnp.ndarray, capacity: jnp.ndarray,
                 active: jnp.ndarray | None = None) -> jnp.ndarray:
    """Exact max-min fair rates (no demand caps).

    R: [F, L] binary routing; capacity: [L]; active: [F] mask (default all).
    Flows traversing no link get rate +inf (caller clamps to demand).
    """
    F, L = R.shape
    if active is None:
        active = jnp.ones((F,), R.dtype)
    active = active.astype(R.dtype)
    on_net = (jnp.sum(R, axis=1) > 0) & (active > 0)

    def body(carry):
        x, frozen, link_done, _ = carry
        unfrozen = (~frozen) & on_net
        n_l = jnp.sum(R * unfrozen[:, None].astype(R.dtype), axis=0)      # [L]
        used = jnp.sum(R * (x * frozen.astype(R.dtype))[:, None], axis=0)  # [L]
        resid = jnp.maximum(capacity - used, 0.0)
        fair = jnp.where((n_l > 0) & (~link_done), resid / jnp.maximum(n_l, 1.0), _INF)
        share = jnp.min(fair)
        any_left = jnp.isfinite(share)
        # freeze EVERY link attaining the current water level at once
        # (classic progressive filling fills all tightest links together:
        # their unfrozen flows get the same share either way, so one round
        # per *bottleneck level* instead of one per bottleneck link)
        tight = (fair <= share) & any_left                           # [L]
        hit = jnp.any(R * tight[None, :].astype(R.dtype), axis=1) & unfrozen
        x = jnp.where(hit, share, x)
        frozen = frozen | hit
        link_done = link_done | tight
        return x, frozen, link_done, any_left

    x0 = jnp.zeros((F,), R.dtype)
    frozen0 = jnp.zeros((F,), bool)
    done0 = jnp.zeros((L,), bool)
    # while-loop instead of a fixed L-trip fori: each round freezes one
    # water level, and the loop exits as soon as no link has unfrozen
    # flows left — so the trip count tracks the scenario's *real* bottleneck
    # structure (#levels), not the (possibly padded — fleet engine) link
    # count. The body is idempotent once nothing binds.
    x, frozen, _, _ = jax.lax.while_loop(
        lambda c: c[3], body, (x0, frozen0, done0, jnp.array(True)))
    # flows not on any congested link (or off-net): unconstrained
    x = jnp.where(on_net & ~frozen, _INF, x)
    x = jnp.where(on_net, x, jnp.where(active > 0, _INF, 0.0))
    return x


def demand_limited_maxmin(R, capacity, demand, iters: int | None = None):
    """Max-min with per-flow demand caps: sequential progressive filling,
    one bottleneck event per round, per-link saturation levels by
    **bisection** — deliberately independent math from both fused forms,
    so it stays a real oracle.

    Replaces the PR-4 clamp-and-resolve wrapper, whose freeze rule
    ("clamp at demand when the demand-free max-min share covers it") is
    unsound: capping *other* flows at their demands can raise this flow's
    competitors on a shared link and pull its final fair level below its
    own demand, so the premature clamp over-allocates (seed 5041 — the
    wrapper converged to a feasible, work-conserving fixed point that
    fails the KKT certificate). Progressive filling freezes only sated
    flows and global-minimum bottleneck levels, both of which are final
    by the water-filling monotonicity argument, so the fixed point here
    *is* the max-min allocation and the certificate holds unconditionally
    (tests/test_maxmin_fused.py).

    ``iters`` caps the outer rounds (default F + L + 1, the convergence
    bound: every round freezes at least one flow or terminates).
    """
    F, L = R.shape
    R = R.astype(jnp.float32)
    on_net = jnp.sum(R, axis=1) > 0
    d = jnp.where(on_net, jnp.maximum(demand, 0.0), 0.0)
    if iters is None:
        iters = F + L + 1

    def link_theta(m, resid):
        # exact θ_l with Σ_{unfrozen f on l} min(d_f, θ) = resid_l, by 50
        # bisection steps on [0, resid_l] (Σ min(d, θ) is nondecreasing in
        # θ and θ* ≤ resid_l whenever the link can saturate): float32
        # interval width resid·2⁻⁵⁰, far inside the solver tie tolerance
        n_l = jnp.sum(m, axis=0)
        sum_d = jnp.sum(d[:, None] * m, axis=0)
        saturable = (n_l > 0) & (sum_d > resid * (1.0 + _RTOL) + _ATOL)

        def bis(_, lohi):
            # Σ min(d, mid) > resid → the level lies below mid, else above
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            s = jnp.sum(jnp.minimum(d[:, None], mid[None, :]) * m, axis=0)
            over = s > resid
            return jnp.where(over, lo, mid), jnp.where(over, mid, hi)

        lo, hi = jax.lax.fori_loop(
            0, 50, bis, (jnp.zeros_like(resid), jnp.maximum(resid, 0.0)))
        return jnp.where(saturable, 0.5 * (lo + hi), _INF)

    def cond(c):
        _, frozen, _, progressed, rounds = c
        return progressed & jnp.any(~frozen) & (rounds < iters)

    def body(c):
        x, frozen, resid, _, rounds = c
        u = ~frozen
        m = R * u[:, None].astype(R.dtype)
        theta = link_theta(m, resid)
        th_flow = jnp.min(jnp.where(R > 0, theta[None, :], _INF), axis=1)
        # demand-satisfied flows freeze first (their level can only rise);
        # otherwise the single tightest water level (+ ties) is final
        sated = u & (d <= th_flow * (1.0 + _RTOL) + _ATOL)
        lvl = jnp.min(jnp.where(u, th_flow, _INF))
        at_lvl = u & (th_flow <= lvl * (1.0 + _RTOL) + _ATOL)
        newf = jnp.where(jnp.any(sated), sated, at_lvl)
        vals = jnp.minimum(d, th_flow)           # th_flow = inf → demand
        x = jnp.where(newf, vals, x)
        resid = jnp.maximum(resid - jnp.matmul(
            jnp.where(newf, vals, 0.0), R, precision=_HIGHEST), 0.0)
        return x, frozen | newf, resid, jnp.any(newf), rounds + 1

    x0 = jnp.where(on_net, 0.0, jnp.asarray(demand, jnp.float32))
    x, *_ = jax.lax.while_loop(
        cond, body,
        (x0, ~on_net, capacity.astype(jnp.float32), jnp.array(True),
         jnp.asarray(0, jnp.int32)))
    return x


# --------------------------------------------------------------------------
# fused fixed-trip solver (the policy hot path)
# --------------------------------------------------------------------------
def _order_matrix(d):
    """Demand rank order as a 0/1 matrix plus the matching stable-sort
    permutation: ``W[f, g] = [(d_g, g) ≤lex (d_f, f)]`` (ties broken by
    flow index — exactly ``jnp.argsort(d, stable=True)``'s order). The
    permutation derives from W's row sums through a one-hot contraction
    (``rank[f]`` is f's position in the stable order, so scattering
    ``f → rank[f]`` inverts it) instead of an argsort: the order-cache
    rebuild stays GEMM/elementwise-only, which matters under the fleet
    vmap where a per-tick batched sort would serialize on CPU backends."""
    F = d.shape[0]
    idx = jnp.arange(F)
    W = ((d[None, :] < d[:, None])
         | ((d[None, :] == d[:, None])
            & (idx[None, :] <= idx[:, None]))).astype(jnp.float32)
    rank = jnp.sum(W, axis=1).astype(jnp.int32) - 1             # [F]
    perm = jnp.sum(jnp.where(rank[None, :] == idx[:, None],
                             idx[None, :], 0), axis=1)          # [F] int32
    return W, perm


def _order_operand(d):
    """The order-only left GEMM operand ``A1 = [W; 1]`` ([F+1, F]) and the
    stable permutation it encodes. A1 is a pure function of the demand
    *order*: two demand vectors with the same stable order produce
    bitwise-identical operands, which is what makes the order cache's
    kept-vs-rebuilt branches interchangeable."""
    F = d.shape[0]
    W, perm = _order_matrix(d)
    A1 = jnp.concatenate([W, jnp.ones((1, F), jnp.float32)], axis=0)
    return A1, perm


def _theta_from_parts(m_or_ms, n_l, sum_d, cum_n, cum_d, resid):
    """Shared tail of every water-level form: candidate chord roots →
    max-selection → saturability gate (see :func:`_link_levels`)."""
    denom = n_l[None, :] - cum_n
    theta_k = (resid[None, :] - cum_d) / jnp.maximum(denom, 0.5)
    cand = jnp.where((m_or_ms > 0) & (denom > 0.5), theta_k, -_INF)
    theta = jnp.maximum(jnp.max(cand, axis=0),
                        resid / jnp.maximum(n_l, 1.0))
    saturable = (n_l > 0) & (sum_d > resid * (1.0 + _RTOL) + _ATOL)
    return jnp.where(saturable, theta, _INF)


def _link_levels(A1, d, m, resid):
    """Exact demand-capped saturation level θ_l per link: the unique θ with
    ``Σ_{unfrozen f on l} min(d_f, θ) = resid_l`` (+inf if the link cannot
    saturate: no unfrozen flows, or their total demand fits in resid).

    GEMM form, no sorting: ``A1 = [W; 1]`` where ``W[f, g] = [d_g ≤ d_f]``
    (ties by index) is the demand order as a 0/1 matrix — order-only, so
    the order cache can carry it across ticks — and the demand weighting
    rides in the *right* operand: ``P = A1 @ [m | d·m]`` ([F+1, 2L])
    yields every per-link quantity the prefix rule needs (rank prefixes of
    counts and demands, plus their totals) in one shared matmul per round
    in *original* flow order. W and m are {0, 1}-valued, so folding d
    right is exact: each product term equals the PR-4 stacked
    ``[W; 1; W·d; d] @ m`` layout's term bitwise (verified property-wise;
    the fleet path relies on it). Selection needs no validity filter: the
    candidate level for the prefix capped at flow f is the root of the
    chord ``Σ_{d_g ≤ d_f} d_g + (#rest)·θ``, which upper-bounds
    ``Σ min(d, θ)`` pointwise, so every candidate root lower-bounds the
    true θ and the consistent prefix attains it — θ is simply the MAX over
    candidates (incl. the nothing-capped chord ``resid/n``). ``m`` [F, L]
    is the routing mask restricted to unfrozen flows. Returns θ [L].
    """
    F, L = m.shape
    P = jnp.matmul(A1, jnp.concatenate([m, d[:, None] * m], axis=1),
                   precision=_HIGHEST)                        # [F+1, 2L]
    return _theta_from_parts(m, P[F, :L], P[F, L:], P[:F, :L], P[:F, L:],
                             resid)


def _link_levels_blocked(A1, d, m, resid, block_flows: int):
    """GEMM form with the candidate rows processed in ``block_flows``
    chunks under ``lax.map`` (mirroring the allocator's ``block_links``):
    the [F, 2L] prefix / [F, L] candidate intermediates are capped at
    [block, ·] while only the rank operand and routing mask stay
    full-size. The per-chunk maxima combine by ``max`` — exact and
    associative — and each chunk's GEMM rows contract identically to the
    single-pass form, so chunking changes wall-clock working set, not
    semantics (parity-tested at ≤1e-5; the fleet corpus never takes this
    path — it activates only above ``2 * MAXMIN_BLOCK_FLOWS`` flows)."""
    F, L = m.shape
    rhs = jnp.concatenate([m, d[:, None] * m], axis=1)        # [F, 2L]
    tot = jnp.matmul(A1[F], rhs, precision=_HIGHEST)          # [2L]
    n_l, sum_d = tot[:L], tot[L:]
    blk = max(int(block_flows), 1)
    nb = -(-F // blk)
    pad = nb * blk - F
    # padded rows: zero rank rows and zero mask → candidates -inf, inert
    Ap = jnp.pad(A1[:F], ((0, pad), (0, 0)))
    mp = jnp.pad(m, ((0, pad), (0, 0)))

    def chunk(args):
        Ac, mc = args                       # [blk, F], [blk, L]
        Pc = jnp.matmul(Ac, rhs, precision=_HIGHEST)  # [blk, 2L]
        denom = n_l[None, :] - Pc[:, :L]
        theta_k = (resid[None, :] - Pc[:, L:]) / jnp.maximum(denom, 0.5)
        cand = jnp.where((mc > 0) & (denom > 0.5), theta_k, -_INF)
        return jnp.max(cand, axis=0)        # [L]

    cmax = jax.lax.map(chunk, (Ap.reshape(nb, blk, F),
                               mp.reshape(nb, blk, L)))
    theta = jnp.maximum(jnp.max(cmax, axis=0),
                        resid / jnp.maximum(n_l, 1.0))
    saturable = (n_l > 0) & (sum_d > resid * (1.0 + _RTOL) + _ATOL)
    return jnp.where(saturable, theta, _INF)


def _link_levels_sorted(perm, d_s, m, resid):
    """Sorted (argsort + cumsum) form of the same water level: gather the
    mask rows into stable demand order once, then the rank prefixes are
    two batched cumsums — O(F·L) against the GEMM form's O(F²·L), which
    wins once F clears :data:`MAXMIN_CROSSOVER_F` (below it the batched
    gathers/cumsums serialize on CPU and lose to the one GEMM). The max
    over candidates is order-independent, so no un-sort is needed."""
    m_s = m[perm]                                             # [F, L]
    cum_n = jnp.cumsum(m_s, axis=0)
    cum_d = jnp.cumsum(d_s[:, None] * m_s, axis=0)
    return _theta_from_parts(m_s, cum_n[-1], cum_d[-1], cum_n, cum_d, resid)


def _fill(R, on_net, d, levels, capacity, rounds: int):
    """The progressive fill itself, generic over the water-level form.

    Per round: compute every link's exact demand-capped water level θ_l,
    then freeze every link that is *locally minimal* — θ_l ≤ θ_m for every
    link m sharing an unfrozen flow — at its level, its flows at
    ``min(d_f, θ_l)``, plus every flow whose demand is covered by all of
    its links (``d_f ≤ min_l θ_l``). Water levels are monotone
    nondecreasing across rounds, so locally minimal freezing is confluent
    with classic sequential progressive filling: the rounds needed equal
    the depth of the increasing bottleneck-level chain. A closing sweep
    assigns any still-unfrozen flow ``min(d_f, min_l θ_l)``, which never
    oversubscribes a link (Σ_f min(d_f, θ_flow) ≤ Σ_f min(d_f, θ_l) =
    resid_l), so truncated runs stay feasible. Small round counts unroll
    (bitwise-identical to the rolled loop; XLA then fuses the elementwise
    chains across round boundaries instead of walling them behind a while
    op — the op-overhead-bound fleet regime's main saving)."""
    def body(carry):
        x, frozen, resid = carry
        u = (~frozen) & on_net
        m = R * u[:, None].astype(R.dtype)                    # [F, L]
        theta = levels(m, resid)                              # [L]
        # per-flow bottleneck level: tightest link on the flow's route
        th_flow = jnp.min(jnp.where(R > 0, theta[None, :], _INF), axis=1)
        # locally minimal links: no unfrozen flow of theirs sees a tighter
        # link elsewhere (th_flow ≤ θ_l always, so this is a tie test)
        nbr = jnp.min(jnp.where(m > 0, th_flow[:, None], _INF), axis=0)
        freeze_l = jnp.isfinite(theta) & (
            theta <= nbr * (1.0 + _RTOL) + _ATOL)
        hit = (jnp.sum(R * freeze_l[None, :].astype(R.dtype), axis=1)
               > 0) & u
        sated = u & (d <= th_flow * (1.0 + _RTOL) + _ATOL)
        newf = hit | sated
        vals = jnp.minimum(d, th_flow)        # th_flow=inf → demand
        x = jnp.where(newf, vals, x)
        resid = jnp.maximum(resid - jnp.matmul(
            jnp.where(newf, vals, 0.0), R, precision=_HIGHEST), 0.0)
        return x, frozen | newf, resid

    carry = (jnp.zeros((R.shape[0],), jnp.float32), ~on_net,
             capacity.astype(jnp.float32))
    if rounds <= _UNROLL_ROUNDS:
        for _ in range(rounds):
            carry = body(carry)
    else:
        carry = jax.lax.fori_loop(0, rounds, lambda _, c: body(c), carry)
    x, frozen, resid = carry
    # closing sweep: any leftover flow rides its current bottleneck level —
    # always link-feasible, exact when the loop already converged
    m = R * ((~frozen) & on_net)[:, None].astype(R.dtype)
    theta = levels(m, resid)
    th_flow = jnp.min(jnp.where(R > 0, theta[None, :], _INF), axis=1)
    return jnp.where(frozen, x, jnp.minimum(d, th_flow))


def _resolve_form(F: int, form: str | None) -> str:
    if form is None:
        return "sorted" if F >= MAXMIN_CROSSOVER_F else "gemm"
    if form not in ("gemm", "sorted"):
        raise ValueError(f"unknown maxmin form {form!r}")
    return form


def _resolve_block_flows(F: int, form: str, block_flows: int | None):
    if form != "gemm":
        return None
    if block_flows is None:
        return MAXMIN_BLOCK_FLOWS if F > 2 * MAXMIN_BLOCK_FLOWS else None
    return int(block_flows) if block_flows > 0 else None


def _levels_fn(form: str, d, A1, perm, block_flows):
    """Bind the chosen water-level form over its order machinery."""
    if form == "gemm":
        if block_flows is not None:
            return lambda m, resid: _link_levels_blocked(
                A1, d, m, resid, block_flows)
        return lambda m, resid: _link_levels(A1, d, m, resid)
    d_s = d[perm]
    return lambda m, resid: _link_levels_sorted(perm, d_s, m, resid)


@functools.partial(jax.jit,
                   static_argnames=("rounds", "form", "block_flows"))
def maxmin_fused(R: jnp.ndarray, capacity: jnp.ndarray, demand: jnp.ndarray,
                 rounds: int | None = FILL_ROUNDS,
                 form: str | None = None,
                 block_flows: int | None = None) -> jnp.ndarray:
    """Demand-limited max-min fair rates as a fused fixed-trip program.

    R: [F, L] binary routing; capacity: [L]; demand: [F] per-flow caps.
    Flows traversing no link get their demand (unconstrained), matching
    :func:`demand_limited_maxmin`. ``rounds=None`` selects the provably
    exact shape bound min(F, L) + 1; the default ``FILL_ROUNDS`` is exact
    whenever the bottleneck-level chain is no deeper (always, on the seed
    corpus) and link-feasible regardless.

    ``form`` picks the water-level evaluation: ``"gemm"`` (rank-prefix
    GEMM against the order-only operand), ``"sorted"`` (stable argsort +
    batched cumsums), or ``None`` — the default — for the trace-time
    crossover on the (padded) flow count against
    :data:`MAXMIN_CROSSOVER_F`. The choice is a python-level branch on a
    static shape, so it can never retrigger compilation at run time and
    is constant per fleet bucket. ``block_flows`` chunks the GEMM form's
    candidate rows (``None`` = auto: single-pass below
    ``2 * MAXMIN_BLOCK_FLOWS`` flows).
    """
    F, L = R.shape
    if rounds is None:
        rounds = min(F, L) + 1
    form = _resolve_form(F, form)
    block_flows = _resolve_block_flows(F, form, block_flows)
    R = R.astype(jnp.float32)
    on_net = jnp.sum(R, axis=1) > 0
    d = jnp.where(on_net, jnp.maximum(demand, 0.0), 0.0)
    if form == "gemm":
        A1, perm = _order_operand(d)
    else:
        A1 = None
        perm = jnp.argsort(d, stable=True)
    levels = _levels_fn(form, d, A1, perm, block_flows)
    x = _fill(R, on_net, d, levels, capacity, rounds)
    return jnp.where(on_net, x, demand)


# --------------------------------------------------------------------------
# order-cached per-tick stepping (the in-scan hot path)
# --------------------------------------------------------------------------
def maxmin_order_init(F: int, form: str | None = None):
    """Initial (invalid) order-cache carry for a scan over per-tick
    solves: ``(valid, perm, A1)``. The first step always rebuilds (and
    counts as one rebuild — the perf gate's static-demand invariant is
    exactly one rebuild per trajectory). The carried operand's shape
    follows the form the crossover will pick for this F: the sorted form
    carries no rank matrix (A1 is [0, F]), the GEMM form carries the full
    [F+1, F] operand."""
    form = _resolve_form(F, form)
    rows = F + 1 if form == "gemm" else 0
    return (jnp.zeros((), bool), jnp.arange(F, dtype=jnp.int32),
            jnp.zeros((rows, F), jnp.float32))


def maxmin_fused_step(R: jnp.ndarray, capacity: jnp.ndarray,
                      demand: jnp.ndarray, carry,
                      rounds: int | None = FILL_ROUNDS,
                      form: str | None = None,
                      block_flows: int | None = None):
    """One order-cached solve: :func:`maxmin_fused` semantics (bitwise),
    amortizing the demand-order machinery across ticks.

    ``carry`` is ``(valid, perm, A1)`` from :func:`maxmin_order_init` or a
    previous step. An O(F) monotonicity check of the current (clamped)
    demands against the carried permutation — ``(d[perm], perm)`` must be
    strictly increasing in lexicographic order, which characterizes perm
    as *the* stable sort of d — decides whether the carried operand still
    encodes the exact order; only on a change is it rebuilt, by the same
    construction the fresh solver uses. Kept and rebuilt operands are
    bitwise-identical whenever the check passes (A1 is a function of the
    order alone), so the solve output never depends on the cache's hit
    pattern. Under the fleet vmap the keep/rebuild ``lax.cond`` lowers to
    a select (both arms execute per batch member); the savings there come
    from the order-only operand and the unrolled fill, while the
    *sequential* scan path takes the branch for real. Returns
    ``(x, carry', rebuilt)`` with ``rebuilt`` a bool scalar (one per
    batch member under vmap) for rebuild-count accounting.

    Not jitted itself: it is scan-body machinery, traced inside its
    caller (``repro.streams.simulator._run``).
    """
    with jax.named_scope("maxmin"):
        F, L = R.shape
        if rounds is None:
            rounds = min(F, L) + 1
        form = _resolve_form(F, form)
        block_flows = _resolve_block_flows(F, form, block_flows)
        R = R.astype(jnp.float32)
        on_net = jnp.sum(R, axis=1) > 0
        d = jnp.where(on_net, jnp.maximum(demand, 0.0), 0.0)

        valid0, perm0, A1_0 = carry
        dp = d[perm0]
        if F > 1:
            mono = jnp.all((dp[:-1] < dp[1:])
                           | ((dp[:-1] == dp[1:]) & (perm0[:-1] < perm0[1:])))
        else:
            mono = jnp.array(True)
        ok = valid0 & mono

        def rebuild(_):
            if form == "gemm":
                A1, perm = _order_operand(d)
            else:
                _, perm = _order_matrix(d)
                A1 = jnp.zeros((0, F), jnp.float32)
            return perm, A1

        def keep(_):
            return perm0, A1_0

        perm, A1 = jax.lax.cond(ok, keep, rebuild, None)
        levels = _levels_fn(form, d, A1, perm, block_flows)
        x = _fill(R, on_net, d, levels, capacity, rounds)
        x = jnp.where(on_net, x, demand)
        return x, (jnp.ones((), bool), perm, A1), ~ok


def demand_limited_maxmin_np(R, capacity, demand):
    """Plain-numpy sequential progressive filling with demand caps —
    unbounded rounds, one bottleneck event at a time. The slow, obviously-
    correct reference the fused solver (and the while-loop oracles) are
    property-tested against."""
    R = np.asarray(R, np.float64)
    resid = np.asarray(capacity, np.float64).copy()
    d = np.asarray(demand, np.float64)
    F, L = R.shape
    on_net = R.sum(1) > 0
    x = np.where(on_net, 0.0, d)
    frozen = ~on_net
    d = np.where(on_net, np.maximum(d, 0.0), 0.0)
    for _ in range(F + L + 1):
        u = ~frozen
        if not u.any():
            break
        # exact saturation level per link (sort the link's own demands)
        theta = np.full(L, np.inf)
        for link in range(L):
            f = u & (R[:, link] > 0)
            n = int(f.sum())
            if n == 0 or d[f].sum() <= resid[link] + 1e-12:
                continue  # link cannot saturate: no level
            ds = np.sort(d[f])
            capped = 0.0
            for k in range(n):
                t = (resid[link] - capped) / (n - k)
                if t <= ds[k] + 1e-15:   # guaranteed for some k: Σd > resid
                    theta[link] = t
                    break
                capped += ds[k]
        th_flow = np.where(
            on_net, np.min(np.where(R > 0, theta[None, :], np.inf), 1), np.inf
        )
        lvl = np.inf if not u.any() else np.nanmin(th_flow[u])
        # freeze demand-satisfied flows first, else the single tightest level
        sated = u & (d <= th_flow + 1e-12)
        if sated.any():
            newf = sated
        else:
            newf = u & (th_flow <= lvl * (1 + 1e-12))
        vals = np.minimum(d, th_flow)
        x = np.where(newf, vals, x)
        resid = np.maximum(resid - (vals * newf) @ R, 0.0)
        frozen |= newf
    return x


def assert_maxmin_certificate(R, cap, d, x, tol: float = 1e-4) -> None:
    """KKT certificate of demand-limited max-min optimality, raising
    ``AssertionError`` on the first violation:

      * feasible: no link is oversubscribed and 0 ≤ x_f ≤ d_f;
      * off-net flows get exactly their demand (unconstrained);
      * every on-net flow is either demand-capped, or crosses a saturated
        link where no flow has a greater rate (its bottleneck).
    """
    R = np.asarray(R, np.float64)
    cap = np.asarray(cap, np.float64)
    d = np.asarray(d, np.float64)
    x = np.asarray(x, np.float64)
    load = x @ R
    scale = max(float(cap.max(initial=1.0)), 1.0)
    if not np.all(load <= cap + tol * scale):
        raise AssertionError(f"link oversubscribed by {(load - cap).max()}")
    if not np.all(x >= -tol):
        raise AssertionError(f"negative rate {x.min()}")
    on_net = R.sum(1) > 0
    np.testing.assert_allclose(x[~on_net], d[~on_net], atol=tol)
    if not np.all(x[on_net] <= d[on_net]
                  + tol * np.maximum(d[on_net], 1.0)):
        raise AssertionError("rate above demand")
    saturated = load >= cap - tol * np.maximum(cap, 1.0)
    for f in np.nonzero(on_net)[0]:
        if x[f] >= d[f] - tol * max(d[f], 1.0):
            continue  # demand-capped
        links = np.nonzero((R[f] > 0) & saturated)[0]
        if not links.size:
            raise AssertionError(
                f"flow {f}: below demand but no saturated link")
        # bottleneck: some saturated link where f's rate is maximal
        if not any(x[f] >= x[R[:, link] > 0].max() - tol * max(1.0, x.max())
                   for link in links):
            raise AssertionError(
                f"flow {f}: rate {x[f]} not maximal on any saturated link")
