"""Fleet engine benchmark: packed single-dispatch `simulate_many`
(`FleetRunner`) vs a sequential `simulate` loop over the same scenarios.

The sequential loop pays one XLA compile per distinct [F, L, I] shape plus
per-scenario dispatch; the packed path compiles ONE fused executable per
policy (every shape bucket's vmap-over-scan inside the same program) and a
warm fleet run is exactly one kernel dispatch. Reports end-to-end
wall-clock for the cold path (first call, compiles included — the
realistic "run a fresh study" cost) and the steady-state warm path, plus
the runner's dispatch/bucket stats so the single-dispatch property is
recorded next to the timing it buys. Warm timings are the **median of
WARM_REPS repeat calls, with the sequential and batched reps
interleaved**: post-compile calls are tens of milliseconds, where
single-shot wall-clock on a shared CI core is noise-dominated and
container drift between separate timing blocks would bias the ratio.

The `fleet_dispatch_floor` row measures the same no-solver "fixed" run at
1, 2 and 4 kernel dispatches. The 1- and 4-dispatch points share one
identical 4-bucket plan (the packed executable vs per-bucket dispatch of
the same buckets — same compute, only the launch count changes), so
`(t_4 - t_1) / 3` isolates per-dispatch overhead; the 2-dispatch point is
a *merged* 2-bucket plan whose larger covers add padded compute, recorded
as the intermediate operating point rather than a fit input. This keeps
the overhead the packing amortizes measured and tracked across PRs, and
gives the planner's `TICK_OVERHEAD_FLOPS` calibration (see
`repro.streams.fleet`) a checked-in measurement trail.

With ``JAX_PLATFORMS=cpu`` the process forces XLA host devices (one per
core, at least 4 and at most 8) — set BEFORE jax initializes, hence the
env fiddling above the imports — so the sharded paths have devices to
spread over: `run` places whole buckets on them, `run_campaign` its
chunks. On an accelerator the runner uses its real devices.

    PYTHONPATH=src python benchmarks/fleet.py
"""
from __future__ import annotations

import os
import sys
import time

# host devices only exist to be forced on the CPU platform, and only
# before jax initializes
if ("jax" not in sys.modules
        and os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + str(max(4, min(os.cpu_count() or 1, 8))))

import jax
import numpy as np

from benchmarks.common import emit
from repro.compile_cache import setup_compile_cache
from repro.streams import (
    FleetRunner,
    bench_fleet,
    campaign_fleet,
    compile_fleet,
    link_failure_sweep,
    simulate,
    simulate_many,
    time_varying_sweep,
)
from repro.streams.fleet import (
    TICK_OVERHEAD_FLOPS_CPU,
    _default_runner,
    calibrate_backend,
)

SECONDS = 60.0
DT = 0.5
WARM_REPS = 5


def _wall(fn):
    t0 = time.time()
    out = fn()
    return time.time() - t0, out


def _wall_median(fn, reps: int):
    ts, out = [], None
    for _ in range(reps):
        t, out = _wall(fn)
        ts.append(t)
    return float(np.median(ts)), out


def run(policy: str = "appaware", seconds: float = SECONDS) -> list[dict]:
    sims = compile_fleet(bench_fleet(seed=0))

    def sequential():
        return [simulate(s, policy, seconds=seconds, dt=DT) for s in sims]

    def batched():
        return simulate_many(sims, policy, seconds=seconds, dt=DT)

    # cold: includes compilation — what one pays for a fresh parameter study
    t_seq_cold, _ = _wall(sequential)
    t_bat_cold, _ = _wall(batched)
    # warm: compile caches hot, pure execution. Sequential and batched
    # reps are INTERLEAVED so slow container drift (a shared CI core
    # speeding up or down between blocks) cancels out of the ratio instead
    # of biasing it; each side still reports its median over WARM_REPS.
    seq_ts, bat_ts, seq, bat = [], [], None, None
    for _ in range(WARM_REPS):
        t, seq = _wall(sequential)
        seq_ts.append(t)
        t, bat = _wall(batched)
        bat_ts.append(t)
    t_seq_warm = float(np.median(seq_ts))
    t_bat_warm = float(np.median(bat_ts))
    stats = _default_runner().last_stats

    # sanity: batched results match the sequential loop
    worst = max(
        abs(a.throughput_tps - b.throughput_tps)
        for a, b in zip(seq, bat)
    )

    return [{
        "name": f"fleet_{policy}",
        "us_per_call": t_bat_warm * 1e6,
        "n_scenarios": len(sims),
        "backend": jax.default_backend(),
        "seq_cold_s": round(t_seq_cold, 2),
        "batch_cold_s": round(t_bat_cold, 2),
        "speedup_cold": round(t_seq_cold / t_bat_cold, 2),
        "seq_warm_s": round(t_seq_warm, 3),
        "batch_warm_s": round(t_bat_warm, 3),
        "speedup_warm": round(t_seq_warm / t_bat_warm, 2),
        "warm_ms_per_scenario": round(t_bat_warm * 1e3 / len(sims), 3),
        "n_dispatches": stats["n_dispatches"],
        "n_shards": stats["n_shards"],
        "n_buckets": stats["n_buckets"],
        "max_tps_diff": f"{worst:.2e}",
        # tcp only: demand-order cache rebuilds across the whole fleet
        # (queue-driven demands reorder freely, so this is an observable,
        # not a gate — the gated invariant is the static-demand row)
        "order_rebuilds": stats.get("order_rebuilds", 0),
    }]


def run_order_cache(n_ticks: int = 64) -> list[dict]:
    """Order-cache invariant row (gated by perf_gate): scanning the
    order-cached solver (`maxmin_fused_step`) over every corpus scenario's
    routing/capacities with a CONSTANT demand vector must rebuild the rank
    operand exactly once per scenario — the tick-0 cold start. More than
    one rebuild means the monotonicity check is spuriously invalidating a
    carried order; zero means the cold start isn't counted. The real tcp
    fleet's queue-driven demands reorder freely (their rebuild count is
    reported in the ``fleet_tcp`` row as an observable), so the invariant
    is pinned on static demands where the ground truth is exact."""
    import jax.numpy as jnp

    from repro.core.tcp import maxmin_fused_step, maxmin_order_init

    sims = compile_fleet(bench_fleet(seed=0))
    rng = np.random.default_rng(3)
    per = []
    for s in sims:
        R = jnp.asarray(s.R)
        cap = jnp.asarray(s.caps)
        F = int(R.shape[0])
        d = jnp.asarray(rng.uniform(
            0.0, 2.0 * float(np.asarray(s.caps).max()), F), jnp.float32)

        def step(carry, _):
            _, carry, reb = maxmin_fused_step(R, cap, d, carry)
            return carry, reb

        _, rebs = jax.lax.scan(step, maxmin_order_init(F), None,
                               length=n_ticks)
        per.append(int(np.sum(np.asarray(rebs))))
    # no us_per_call: this is an invariant/observable row, not a timing —
    # common.emit prints "-" and rejects fake 0.0 timings outright
    return [{
        "name": "fleet_order_cache",
        "n_scenarios": len(sims),
        "backend": jax.default_backend(),
        "ticks_per_scenario": n_ticks,
        "static_demand_rebuilds_total": int(sum(per)),
        "static_demand_rebuilds_max": int(max(per)),
        "static_demand_rebuilds_min": int(min(per)),
        "rebuilds_per_scenario_expected": 1,
    }]


def run_dispatch_floor(seconds: float = SECONDS) -> list[dict]:
    """No-solver "fixed" corpus run at 1, 2 and 4 kernel dispatches.

    The 1- and 4-dispatch points run the *same* flop-only 4-bucket plan
    padded the same way, so their difference isolates per-dispatch
    overhead with identical compute: ``per_dispatch_overhead_s =
    (t_4 - t_1) / 3``. The 2-dispatch point is a merged 2-bucket plan —
    its larger covers add padded compute, so it is the intermediate
    *operating* point, not a fit input. The separate ``packed_default_s``
    point is the overhead-aware planner's own choice for this fleet (it
    collapses cheap-tick fleets below the bucket cap), i.e. what
    `simulate_many` actually pays."""
    sims = compile_fleet(bench_fleet(seed=0))
    xf = [np.full(s.R.shape[0], 0.5, np.float32) for s in sims]

    def timed(runner):
        def call():
            return runner.run(sims, "fixed", seconds=seconds, dt=DT,
                              x_fixed=xf, shard=False)
        call()  # compile
        t, _ = _wall_median(call, WARM_REPS)
        return t, runner.last_stats

    t1, s1 = timed(FleetRunner(fused=True, max_buckets=4, tick_overhead=0.0))
    t2, s2 = timed(FleetRunner(fused=False, max_buckets=2,
                               tick_overhead=0.0))
    t4, s4 = timed(FleetRunner(fused=False, max_buckets=4,
                               tick_overhead=0.0))
    tp, sp = timed(FleetRunner())   # overhead-aware default, packed
    assert (s1["n_dispatches"], s2["n_dispatches"], s4["n_dispatches"]) \
        == (1, 2, 4)
    return [{
        "name": "fleet_dispatch_floor",
        "us_per_call": t1 * 1e6,
        "n_scenarios": len(sims),
        "backend": jax.default_backend(),
        "dispatch_1_s": round(t1, 4),
        "dispatch_2_s": round(t2, 4),
        "dispatch_4_s": round(t4, 4),
        "per_dispatch_overhead_s": round((t4 - t1) / 3, 4),
        "packed_default_s": round(tp, 4),
        "packed_default_buckets": sp["n_buckets"],
        # measured per-backend calibration (what the planner and
        # `chunk_rows="auto"` actually use); the old hardcoded guess
        # stays recorded as the REPRO_CALIBRATE=0 fallback
        "planner_tick_overhead_flops": calibrate_backend(
        ).tick_overhead_flops,
        "planner_tick_overhead_fallback": TICK_OVERHEAD_FLOPS_CPU,
    }]


def run_dynamics(policy: str = "tcp", seconds: float = SECONDS) -> list[dict]:
    """Scheduled-caps machinery cost vs static: the *identical* scenarios
    once with no schedule and once with a constant (no-op) schedule. A
    constant schedule produces bitwise-identical trajectories but takes
    the full dynamic path — [T, L] capacity stream into the scan plus
    per-tick enforcement — so the ratio isolates exactly what in-run
    dynamics cost, with zero workload difference (a real failure schedule
    would also change queue dynamics and the max-min solver's
    data-dependent trip counts, conflating workload with machinery)."""
    import dataclasses

    from repro.net import LinkSchedule

    scens = (link_failure_sweep(n=4, seed=7, in_run=True)
             + time_varying_sweep(n_phases=4, seed=7, in_run=True))
    static = compile_fleet(
        [dataclasses.replace(s, schedule=None) for s in scens])
    sched = compile_fleet(
        [dataclasses.replace(s,
                             schedule=LinkSchedule.constant(s.topo.n_links))
         for s in scens])

    def run_static():
        return simulate_many(static, policy, seconds=seconds, dt=DT)

    def run_sched():
        return simulate_many(sched, policy, seconds=seconds, dt=DT)

    run_static(), run_sched()  # compile both paths
    t_static, _ = _wall_median(run_static, WARM_REPS)
    t_sched, _ = _wall_median(run_sched, WARM_REPS)
    return [{
        "name": f"fleet_dynamics_{policy}",
        "us_per_call": t_sched * 1e6,
        "n_scenarios": len(sched),
        "backend": jax.default_backend(),
        "static_warm_s": round(t_static, 3),
        "scheduled_warm_s": round(t_sched, 3),
        "sched_overhead": round(t_sched / max(t_static, 1e-9), 2),
    }]


def run_reroute(policy: str = "appaware",
                seconds: float = SECONDS) -> list[dict]:
    """Mid-run rerouting machinery cost: the *identical* failure-scheduled
    scenarios once with capacity-only dynamics (the schedule degrades
    links, routes stay fixed) and once with the precompiled route bank
    (same schedule, plus the per-tick state stream and the in-scan
    ``route_bank`` gather). The workload difference is real — rerouted
    flows move different bytes — but the *machinery* being priced is the
    banked-gather path itself: the ratio must stay near 1, because the
    whole design point of precompiling ``[S_r, F, L]`` and streaming a
    per-tick int32 state index is that mid-run rerouting costs one gather,
    not a recompile or a ``lax.cond``."""
    import dataclasses

    scens = link_failure_sweep(n=8, seed=7, reroute=True)
    sched = compile_fleet(
        [dataclasses.replace(s, reroute=False) for s in scens])
    rer = compile_fleet(scens)
    assert all(s.is_rerouting for s in rer)

    def run_sched():
        return simulate_many(sched, policy, seconds=seconds, dt=DT)

    def run_rer():
        return simulate_many(rer, policy, seconds=seconds, dt=DT)

    run_sched(), run_rer()  # compile both paths
    # interleaved warm reps (see `run`): container drift cancels out of
    # the ratio instead of biasing it
    sched_ts, rer_ts = [], []
    for _ in range(WARM_REPS):
        t, _ = _wall(run_sched)
        sched_ts.append(t)
        t, _ = _wall(run_rer)
        rer_ts.append(t)
    t_sched = float(np.median(sched_ts))
    t_rer = float(np.median(rer_ts))
    n_states = max(int(np.asarray(s.route_bank).shape[0]) for s in rer)
    return [{
        "name": f"fleet_reroute_{policy}",
        "us_per_call": t_rer * 1e6,
        "n_scenarios": len(rer),
        "backend": jax.default_backend(),
        "sched_warm_s": round(t_sched, 3),
        "reroute_warm_s": round(t_rer, 3),
        # ~1: the banked gather is in-scan arithmetic, not a mode switch
        "reroute_overhead": round(t_rer / max(t_sched, 1e-9), 2),
        "max_route_states": n_states,
    }]


def run_campaign_bench(policy: str = "tcp", n: int = 256,
                       seconds: float = SECONDS,
                       chunk_rows: int = 64) -> list[dict]:
    """Streaming campaign vs materialized fleet on the same corpus.

    ``run_campaign`` pays per-chunk staging + dispatch + a [rows, 7]
    metric fetch; ``run`` pays one staged dispatch + full-trajectory
    transfer but amortizes staging across warm calls. The gate floor
    asserts streaming throughput ≥ 0.9× materialized — the bounded-memory
    mode must not cost more than the staging it re-does (the overlap with
    in-flight device compute is what pays for it; ``overlap_fraction``
    records how much staging wall-time was hidden). Warm reps are
    interleaved so container drift cancels out of the ratio (see `run`),
    and each side takes its best-of (min, à la timeit) — the run-to-run
    spread on a shared container is one-sided noise that a median over a
    handful of reps does not reject."""
    sims = compile_fleet(campaign_fleet(n, seed=0))
    runner = FleetRunner()

    def materialized():
        return runner.run(sims, policy, seconds=seconds, dt=DT)

    def streaming():
        return runner.run_campaign(sims, policy, seconds=seconds, dt=DT,
                                   chunk_rows=chunk_rows)

    materialized(), streaming()  # compile both paths
    mat_ts, str_ts, stats = [], [], None
    for _ in range(WARM_REPS):
        t, _ = _wall(materialized)
        mat_ts.append(t)
        t, _ = _wall(streaming)
        str_ts.append(t)
        stats = dict(runner.last_stats)
    t_mat = float(np.min(mat_ts))
    t_str = float(np.min(str_ts))
    cal = stats["calibration"]
    return [{
        "name": "fleet_campaign",
        "us_per_call": t_str * 1e6,
        "n_scenarios": n,
        "backend": jax.default_backend(),
        "materialized_warm_s": round(t_mat, 3),
        "streaming_warm_s": round(t_str, 3),
        # >= 1: streaming is at least as fast as materializing everything
        "stream_vs_materialized": round(t_mat / t_str, 2),
        "scenarios_per_s": round(n / t_str, 1),
        "chunk_rows": stats["chunk_rows"],
        "n_chunks": stats["n_chunks"],
        "n_streams": stats["n_streams"],
        "peak_staged_rows": stats["peak_staged_rows"],
        "peak_staged_bytes": stats["peak_staged_bytes"],
        "overlap_fraction": round(stats["overlap_fraction"], 3),
        # three-stage pipeline split: H2D copy time, how much of it the
        # dispatch thread re-paid as waiting, and the resulting overlap
        "transfer_s": round(stats["transfer_s"], 3),
        "transfer_wait_s": round(stats["transfer_wait_s"], 3),
        "transfer_overlap": round(stats["transfer_overlap"], 3),
        # backend calibration behind chunk_rows="auto"
        "calib_dispatch_us": round(cal["dispatch_us"], 2),
        "calib_sync_us": round(cal["sync_us"], 2),
        "calib_tick_overhead_flops": round(cal["tick_overhead_flops"], 0),
        "calib_proxy_mflops": round(cal["proxy_mflops"], 0),
        "calib_clamped": cal["clamped"],
    }]


def run_campaign_auto(policy: str = "tcp", n: int = 256,
                      seconds: float = SECONDS) -> list[dict]:
    """`chunk_rows="auto"` vs a measured chunk-size sweep.

    Streams the same corpus at a grid of fixed chunk sizes plus "auto",
    and reports where auto's pick lands against the measured optimum. On
    CPU the warm curve is a broad plateau (per-dispatch overhead is tens
    of µs against tens-of-ms chunks), so the gateable claim is membership
    in the plateau — auto within ``plateau_tol`` of the best measured
    point — not an exact argmin match on a noisy shared core."""
    sims = compile_fleet(campaign_fleet(n, seed=0))
    runner = FleetRunner()
    grid = [16, 32, 64, 128]
    reps = max(2, WARM_REPS - 2)

    def stream(rows):
        def call():
            return runner.run_campaign(sims, policy, seconds=seconds,
                                       dt=DT, chunk_rows=rows)
        call()  # compile
        t, _ = _wall_median(call, reps)
        return t

    sweep = {rows: stream(rows) for rows in grid}
    t_auto = stream("auto")
    stats = dict(runner.last_stats)
    best_rows = min(sweep, key=sweep.get)
    t_best = sweep[best_rows]
    return [{
        "name": "fleet_campaign_auto",
        "us_per_call": t_auto * 1e6,
        "n_scenarios": n,
        "backend": jax.default_backend(),
        "auto_target_rows": stats["target_chunk_rows"],
        "auto_warm_s": round(t_auto, 3),
        "sweep_warm_s": {str(k): round(v, 3) for k, v in sweep.items()},
        "sweep_best_rows": best_rows,
        "sweep_best_s": round(t_best, 3),
        # <= plateau tolerance: auto picked within the measured plateau
        "auto_vs_best": round(t_auto / t_best, 3),
    }]


def run_campaign_resilience(policy: str = "tcp", n: int = 256,
                            seconds: float = SECONDS,
                            chunk_rows: int = 64) -> list[dict]:
    """Fault-free overhead of the resilience guards.

    The guarded side runs the campaign at its defaults — finite-check on
    every [rows, n_metrics] slab, the transfer watchdog armed, plus a
    checkpoint append (slab write + fsync'd manifest line) per chunk into
    a fresh directory per rep (a reused directory would resume instead of
    measure). The bare side switches every guard off. Reps are
    INTERLEAVED so container drift cancels out of the ratio, best-of
    (min) per side; the gate ceiling asserts guarded ≤ 1.05× bare in full
    mode — the resilience layer must be effectively free when nothing
    fails, since it is always on by default."""
    import shutil
    import tempfile

    sims = compile_fleet(campaign_fleet(n, seed=0))
    runner = FleetRunner()
    tmp = tempfile.mkdtemp(prefix="bench_resilience_ckpt_")
    n_ck = [0]

    def guarded():
        n_ck[0] += 1
        return runner.run_campaign(
            sims, policy, seconds=seconds, dt=DT, chunk_rows=chunk_rows,
            checkpoint=os.path.join(tmp, f"ck{n_ck[0]}"))

    def bare():
        return runner.run_campaign(
            sims, policy, seconds=seconds, dt=DT, chunk_rows=chunk_rows,
            finite_check=False, transfer_timeout_s=None)

    try:
        g0, b0 = guarded(), bare()  # compile (shared executables)
        assert np.array_equal(g0.metrics, b0.metrics)  # guards are inert
        assert not g0.failures
        g_ts, b_ts, stats = [], [], None
        for _ in range(WARM_REPS):
            t, _ = _wall(guarded)
            g_ts.append(t)
            stats = dict(runner.last_stats)
            t, _ = _wall(bare)
            b_ts.append(t)
        t_g = float(np.min(g_ts))
        t_b = float(np.min(b_ts))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return [{
        "name": "fleet_campaign_resilience",
        "us_per_call": t_g * 1e6,
        "n_scenarios": n,
        "backend": jax.default_backend(),
        "guarded_warm_s": round(t_g, 3),
        "bare_warm_s": round(t_b, 3),
        # ~1: finite-check + checkpoint append + watchdog are free when
        # nothing fails (gate ceiling: <= 1.05 full mode)
        "guard_overhead": round(t_g / t_b, 3),
        "n_chunks": stats["n_chunks"],
        "n_quarantined": stats["n_quarantined"],
        "n_retries": stats["n_retries"],
    }]


def run_campaign_scaling(policy: str = "tcp", n: int = 256,
                         seconds: float = SECONDS) -> list[dict]:
    """Sharded chunk stream over this process's devices vs one device,
    both timed in this process (no child: on an accelerator the parent
    already holds the devices). With forced host devices on one CPU core
    the gateable number is a *not-much-worse* bound — sharding must not
    serialize or duplicate work; real scaling is a chip measurement.
    Metrics parity across device counts is asserted bitwise in
    tests/test_multidevice.py; this row tracks the wall-clock."""
    sims = compile_fleet(campaign_fleet(n, seed=0))
    runner = FleetRunner()
    walls, stats = {}, {}
    for shard in (False, True):
        def stream():
            return runner.run_campaign(sims, policy, seconds=seconds,
                                       dt=DT, shard=shard)
        stream()  # compile
        walls[shard], _ = _wall_median(stream, max(2, WARM_REPS - 2))
        stats[shard] = dict(runner.last_stats)
    st = stats[True]
    return [{
        "name": "fleet_campaign_scaling",
        "us_per_call": walls[True] * 1e6,
        "n_scenarios": n,
        "backend": jax.default_backend(),
        "host_cores": os.cpu_count(),
        "n_devices": jax.local_device_count(),
        "n_streams": st["n_streams"],
        "warm_1dev_s": round(walls[False], 3),
        "warm_ndev_s": round(walls[True], 3),
        # >= floor: sharding on shared host cores must stay within a
        # constant factor of the single-stream run (not serialize or
        # duplicate work); > 1 means a real parallel win
        "scaling_efficiency": round(walls[False] / walls[True], 3),
        "transfer_overlap_ndev": round(st["transfer_overlap"], 3),
        "overlap_fraction_ndev": round(st["overlap_fraction"], 3),
    }]


def main() -> None:
    rows = []
    for policy in ("tcp", "appaware"):
        rows += run(policy)
    rows += run_dispatch_floor()
    rows += run_dynamics("tcp")
    rows += run_reroute()
    rows += run_order_cache()
    rows += run_campaign_bench()
    rows += run_campaign_auto()
    rows += run_campaign_resilience()
    rows += run_campaign_scaling()
    emit(rows, "fleet")


if __name__ == "__main__":
    setup_compile_cache()
    main()
