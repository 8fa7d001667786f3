"""Allocator hot-path micro-benchmark: per-interval ``allocate`` cost at
datacenter scale (ROADMAP: 10⁴ links × 10³ flows).

Alg. 1 re-solves every Δt, so the per-interval solve is the controller's
steady-state cost. Three paths over the same random LinkProgram/FlowState:

  * ``sort``   — the fused batched solve (`allocator._per_link_rates`):
                 ONE global argsort over flows + masked batched cumsums;
  * ``vmap``   — the pre-fusion reference (`_per_link_rates_vmap`):
                 one argsort *per link* under `jax.vmap` (kept as the
                 parity oracle; benchmarked here to track the fusion win);
  * ``pallas`` — the bisection waterfill kernel (TPU target; interpret
                 mode off-TPU, so CPU numbers measure the kernel's control
                 flow, not TPU performance).

Sizes: {10², 10³, 10⁴} links × 10³ flows. ``REPRO_SMOKE=1`` (CI) caps the
sweep at 10³ links and skips the interpret-mode pallas point beyond 10²
(unrolling a 10³-link grid through the interpreter is compile-bound).

    PYTHONPATH=src python benchmarks/allocator.py
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit_us
from repro.compile_cache import setup_compile_cache
from repro.core.allocator import (
    LinkProgram,
    _per_link_rates,
    _per_link_rates_vmap,
    allocate,
)
from repro.core.flowstate import FlowState

N_FLOWS = 1_000
LINK_SIZES = (100, 1_000, 10_000)
SMOKE = os.environ.get("REPRO_SMOKE", "").strip() not in ("", "0")
DT = 5.0


def random_problem(n_links: int, n_flows: int = 1_000, seed: int = 0,
                   links_per_flow: int = 4) -> tuple[LinkProgram, FlowState]:
    """Seeded controller instance at fabric scale: each flow crosses
    ``links_per_flow`` random links, link kinds split 40/40/20
    uplink/downlink/internal like a fat-tree, capacities U(1, 50) MB/s and
    every FlowState field U(0, 10)."""
    rng = np.random.default_rng(seed)
    R = np.zeros((n_flows, n_links), np.float32)
    for f in range(n_flows):
        R[f, rng.choice(n_links, size=min(links_per_flow, n_links),
                        replace=False)] = 1.0
    kind = rng.choice([0, 1, 2], size=n_links,
                      p=[0.4, 0.4, 0.2]).astype(np.int32)
    prog = LinkProgram(
        R=jnp.asarray(R),
        capacity=jnp.asarray(rng.uniform(1.0, 50.0, n_links), jnp.float32),
        kind=jnp.asarray(kind),
    )
    st = FlowState(*[jnp.asarray(rng.uniform(0, 10, n_flows), jnp.float32)
                     for _ in range(5)])
    return prog, st


@functools.partial(jax.jit, static_argnames=("dt",))
def _vmap_rates(program, state, dt):
    return _per_link_rates_vmap(program, state, dt)


@functools.partial(jax.jit, static_argnames=("dt",))
def _fused_rates(program, state, dt):
    return _per_link_rates(program, state, dt)


def run() -> list[dict]:
    rows = []
    sizes = [s for s in LINK_SIZES if not (SMOKE and s > 1_000)]
    for L in sizes:
        prog, st = random_problem(L, N_FLOWS)
        iters = max(2, min(10, 20_000 // L))

        us_sort = timeit_us(
            lambda: jax.block_until_ready(
                allocate(prog, st, dt=DT, solver="sort")), iters)
        us_fused = timeit_us(
            lambda: jax.block_until_ready(_fused_rates(prog, st, DT)), iters)
        us_vmap = timeit_us(
            lambda: jax.block_until_ready(_vmap_rates(prog, st, DT)), iters)
        # chunked-links variant: bounded [block, F] working set — the
        # memory-capped path for datacenter link counts
        blk = min(L, 256)
        us_chunk = timeit_us(
            lambda: jax.block_until_ready(
                allocate(prog, st, dt=DT, solver="sort", block_links=blk)),
            iters)
        row = {
            "name": f"alloc_L{L}",
            "us_per_call": us_sort,
            "n_links": L,
            "n_flows": N_FLOWS,
            "backend": jax.default_backend(),
            "allocate_sort_us": round(us_sort, 1),
            "allocate_chunked_us": round(us_chunk, 1),
            "block_links": blk,
            "per_link_fused_us": round(us_fused, 1),
            "per_link_vmap_us": round(us_vmap, 1),
            "fused_over_vmap": round(us_vmap / max(us_fused, 1e-9), 2),
        }
        # interpret-mode pallas walks the grid in python: keep CI (smoke)
        # to the small grid, measure every size in full runs / on TPU
        if jax.default_backend() == "tpu" or not SMOKE or L <= 100:
            us_pal = timeit_us(
                lambda: jax.block_until_ready(
                    allocate(prog, st, dt=DT, solver="pallas")),
                max(2, iters // 2))
            row["allocate_pallas_us"] = round(us_pal, 1)
        rows.append(row)
    return rows


def _mk_maxmin(F: int, L: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    R = np.zeros((F, L), np.float32)
    for f in range(F):
        R[f, rng.choice(L, size=min(3, L), replace=False)] = 1.0
    cap = rng.uniform(1.0, 20.0, L).astype(np.float32)
    d = rng.uniform(0.0, 10.0, F).astype(np.float32)
    return jnp.asarray(R), jnp.asarray(cap), jnp.asarray(d)


def run_maxmin() -> list[dict]:
    """Max-min solver micro-bench: the fused fixed-trip fill
    (`maxmin_fused`, the tcp/appfair hot path) vs the retained while-loop
    progressive-filling oracle (`demand_limited_maxmin`), single-instance
    and under an 8-wide `vmap` (the fleet engine's shape) — the while
    loop's data-dependent trip count runs at the batch max under vmap,
    which is exactly what the fixed-trip rewrite removes."""
    from repro.core.tcp import demand_limited_maxmin, maxmin_fused

    fused = jax.jit(maxmin_fused)
    loop = jax.jit(demand_limited_maxmin)
    vfused = jax.jit(jax.vmap(maxmin_fused, in_axes=(0, 0, 0)))
    vloop = jax.jit(jax.vmap(demand_limited_maxmin, in_axes=(0, 0, 0)))
    rows = []
    for F, L in ((64, 24), (512, 64)):
        if SMOKE and F > 64:
            continue
        R, cap, d = _mk_maxmin(F, L)
        Rb, capb, db = (jnp.stack([a] * 8) for a in (R, cap, d))
        us_f = timeit_us(lambda: jax.block_until_ready(fused(R, cap, d)), 20)
        us_l = timeit_us(lambda: jax.block_until_ready(loop(R, cap, d)), 20)
        us_vf = timeit_us(
            lambda: jax.block_until_ready(vfused(Rb, capb, db)), 20)
        us_vl = timeit_us(
            lambda: jax.block_until_ready(vloop(Rb, capb, db)), 20)
        rows.append({
            "name": f"maxmin_F{F}_L{L}",
            "us_per_call": us_f,
            "backend": jax.default_backend(),
            "fused_us": round(us_f, 1),
            "while_oracle_us": round(us_l, 1),
            "fused_vmap8_us": round(us_vf, 1),
            "while_vmap8_us": round(us_vl, 1),
            "fused_over_while": round(us_l / max(us_f, 1e-9), 2),
            "fused_over_while_vmap8": round(us_vl / max(us_vf, 1e-9), 2),
        })
    return rows


def run_crossover() -> list[dict]:
    """Calibration rows for ``MAXMIN_CROSSOVER_F`` — the trace-time
    dispatch between the rank-prefix GEMM form (O(F²·L), order-cacheable,
    one matmul per round) and the argsort+cumsum form (O(F·L), batched
    gathers/scans) of the fused solver's water-level evaluation. Both
    forms are timed at a grid of flow counts straddling the constant,
    single-instance and vmap-8 (the fleet engine's batching shape, where
    per-member sorts serialize on CPU and the GEMM form's advantage is
    largest). The shipped constant must sit inside the measured crossover
    band of the vmap-8 column: the solver's only batched consumer is the
    fleet engine."""
    from repro.core.tcp import MAXMIN_CROSSOVER_F, maxmin_fused

    grid = (32, 96, 192, 256, 384, 512)
    if SMOKE:
        grid = (32, 96)
    rows = []
    for F in grid:
        L = max(16, F // 8)
        R, cap, d = _mk_maxmin(F, L, seed=1)
        Rb, capb, db = (jnp.stack([a] * 8) for a in (R, cap, d))
        row = {"name": f"maxmin_crossover_F{F}", "n_flows": F, "n_links": L,
               "backend": jax.default_backend(),
               "crossover_f": MAXMIN_CROSSOVER_F}
        for form in ("gemm", "sorted"):
            one = jax.jit(functools.partial(maxmin_fused, form=form))
            vm = jax.jit(jax.vmap(functools.partial(maxmin_fused, form=form),
                                  in_axes=(0, 0, 0)))
            row[f"{form}_us"] = round(timeit_us(
                lambda: jax.block_until_ready(one(R, cap, d)), 20), 1)
            row[f"{form}_vmap8_us"] = round(timeit_us(
                lambda: jax.block_until_ready(vm(Rb, capb, db)), 20), 1)
        row["us_per_call"] = row["gemm_vmap8_us"]
        row["gemm_over_sorted_vmap8"] = round(
            row["gemm_vmap8_us"] / max(row["sorted_vmap8_us"], 1e-9), 3)
        rows.append(row)
    return rows


def main() -> None:
    emit(run() + run_maxmin() + run_crossover(), "allocator")


if __name__ == "__main__":
    setup_compile_cache()
    main()
