#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU chip.

    python chip_smoke.py                 # one chip: phases 1-3 below
    python chip_smoke.py --chips 4       # four chips: sharded paths only

One chip, in one process:

1. controller solve at fabric scale: ``allocate`` with the sort solver
   and with the compiled Pallas waterfill kernel on a seeded
   10 000-link, 1 000-flow program; parity and link feasibility;
2. streaming campaign: ``FleetRunner.run_campaign`` over
   ``campaign_fleet(2048)`` at the paper's 600 s horizon for tcp,
   appaware and appfair, with no retry, no quarantine, no NaN and no
   recompile after the first chunk of a bucket;
3. numerics: 16 sampled scenarios per policy re-run with ``simulate`` on
   the host CPU device of the same process, against the chip's campaign
   rows; tcp max-min solves on the chip against the numpy progressive
   fill and its KKT certificate;
4. row count: ``bench_fleet()`` through ``FleetRunner.run`` against
   ``run_campaign`` with each bucket in one chunk of the same rows
   (bitwise: a bucket's program does not depend on the other buckets of
   the run, which is what the sharded ``run`` relies on) and with chunks
   of at most 3 rows (within the reference tolerances; how far from
   bitwise a different batch row count lands is reported).

``--chips 4`` runs only the sharded paths and their one-chip references:
``run_campaign(shard=True)`` against ``shard=False`` and
``FleetRunner.run(shard=True)`` against unsharded on ``bench_fleet()``:
metrics bitwise, trajectories bitwise, and the run's ``total_sink_mb``
within a few ULP.

Data comes from ``--seed``. Wall times are printed as informational. The
last line of stdout is ``{"ok": true, "device": {...}}`` and is printed
only when every check passed; any failure exits non-zero. The script
refuses to run without a TPU and with ``REPRO_SMOKE`` set (which caps the
simulated horizon).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
import zlib

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the host CPU device serves as the in-process reference; keep it
# reachable when the platform list was narrowed to the accelerator
_plats = os.environ.get("JAX_PLATFORMS", "").strip()
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

N_CAMPAIGN = 2048
SECONDS = 600.0
POLICIES = ("tcp", "appaware", "appfair")
N_SAMPLE = 16
RTOL = 1e-3          # throughput, latency, utilization, sink volume
DIP_ATOL = 0.05
RECOVERY_ATOL_S = 3.0
SINK_ULPS = 4        # sharded run's total_sink_mb against unsharded


class Checks:
    """Collects check outcomes so one run reports every failure."""

    def __init__(self):
        self.failed: list[str] = []
        self.report: dict = {}

    def check(self, ok: bool, what: str) -> bool:
        print(f"  [{'pass' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok

    def phase(self, name: str, fn, *args) -> None:
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            fn(self, *args)
        except Exception:  # noqa: BLE001 — a crashed phase is a failure
            traceback.print_exc()
            self.failed.append(f"{name}: raised")
        dt = time.perf_counter() - t0
        self.report.setdefault("phase_wall_s", {})[name] = dt
        print(f"  {name}: {dt:.3f} s wall (chip, informational)", flush=True)


def _watch_compile_cache() -> dict:
    """Counts the persistent compilation cache's lookups, hits and writes
    from JAX's monitoring events; a second run on the same cache shows
    its executables reused as hits."""
    from jax import monitoring
    pre = "/jax/compilation_cache/"
    names = {pre + "compile_requests_use_cache": "lookups",
             pre + "cache_hits": "hits", pre + "cache_misses": "writes"}
    seen = {"lookups": 0, "hits": 0, "writes": 0, "saved_s": 0.0}

    def on_event(event, **_):
        if event in names:
            seen[names[event]] += 1

    def on_duration(event, secs, **_):
        if event == pre + "compile_time_saved_sec":
            seen["saved_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def _median_s(fn, reps: int = 5) -> float:
    import jax
    import numpy as np
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# ------------------------------------------------------------- phase 1
def phase_controller(c: Checks, seed: int) -> None:
    import jax
    import numpy as np
    from benchmarks.allocator import random_problem
    from repro.core.allocator import allocate

    dt = 5.0
    prog, st = random_problem(10_000, 1_000, seed=seed, links_per_flow=4)
    text = allocate.lower(prog, st, dt=dt, solver="pallas").as_text()
    c.check("tpu_custom_call" in text,
            "pallas allocate lowers to a compiled tpu_custom_call")
    xs = np.asarray(allocate(prog, st, dt=dt, solver="sort"), np.float64)
    xp = np.asarray(allocate(prog, st, dt=dt, solver="pallas"), np.float64)
    R = np.asarray(prog.R, np.float64)
    cap = np.asarray(prog.capacity, np.float64)
    err = np.abs(xs - xp) - (2e-3 + 2e-3 * np.abs(xp))
    c.check(bool(np.all(err <= 0)),
            f"sort and pallas agree within rtol/atol 2e-3 at L=10000, "
            f"F=1000 (max |diff| {np.abs(xs - xp).max():.3g})")
    for name, x in (("sort", xs), ("pallas", xp)):
        over = (x @ R) / cap
        c.check(bool(np.all(x >= 0) and np.all(over <= 1 + 1e-3)),
                f"{name}: rates feasible, max load/cap {over.max():.6f}")
    for solver in ("sort", "pallas"):
        t = _median_s(lambda: allocate(prog, st, dt=dt, solver=solver))
        c.report[f"allocate_{solver}_s"] = t
        print(f"  allocate solver={solver}: {t * 1e3:.3f} ms per solve "
              f"(chip, informational)")


# ------------------------------------------------------------- phase 2
def phase_campaign(c: Checks, seed: int, sims, out: dict) -> None:
    import numpy as np
    from repro.streams import FleetRunner
    from repro.streams.fleet import calibrate_backend
    from repro.streams.simulator import metric_index

    cal = calibrate_backend()
    c.report["calibration"] = dataclasses.asdict(cal)
    print(f"  calibration: {cal}")
    rec = metric_index("recovery_time_s")
    for policy in POLICIES:
        runner = FleetRunner()
        t0 = time.perf_counter()
        cr = runner.run_campaign(sims, policy, seconds=SECONDS)
        wall = time.perf_counter() - t0
        st = runner.last_stats
        out[policy] = (runner, cr)
        keys = ("n_chunks", "n_buckets", "rows", "n_streams",
                "chunks_per_device", "stage_s", "transfer_s",
                "dispatch_s", "block_s", "wall_s")
        m = cr.metrics
        # digest of the metric matrix: equal digests in two processes
        # mean the campaign reproduced bit for bit
        crc = zlib.crc32(np.ascontiguousarray(m).tobytes())
        c.report[f"campaign_{policy}"] = {k: st[k] for k in keys}
        c.report[f"campaign_{policy}"]["metrics_crc32"] = crc
        print(f"  {policy}: {len(sims)} scenarios, {st['n_chunks']} chunks "
              f"over {st['n_buckets']} buckets (rows {st['rows']}), "
              f"metrics crc32 {crc:08x}, {wall:.3f} s wall "
              f"(chip, informational)")
        others = np.delete(m, rec, axis=1)
        c.check(not cr.failures and st["n_retries"] == 0
                and st["n_quarantined"] == 0,
                f"{policy}: {len(cr.failures)} failures, "
                f"{st['n_retries']} retries, {st['n_quarantined']} "
                f"quarantined")
        c.check(not np.isnan(m).any() and np.isfinite(others).all(),
                f"{policy}: metrics free of NaN, +inf only in recovery")
        n_exec = runner.compile_cache_size()
        c.check(n_exec <= st["n_buckets"],
                f"{policy}: {n_exec} compiles for {st['n_buckets']} "
                f"buckets (none after a bucket's first chunk)")


# ------------------------------------------------------------- phase 3
def _sample(plan, n: int, rng) -> list[int]:
    """``n`` scenario indices spread round-robin over the buckets."""
    pools = [list(rng.permutation(idxs)) for idxs, _ in plan]
    out: list[int] = []
    while len(out) < n and any(pools):
        for p in pools:
            if p and len(out) < n:
                out.append(int(p.pop()))
    return sorted(out)


def _row_mismatches(chip, ref) -> list[str]:
    import numpy as np
    from repro.streams.simulator import CAMPAIGN_METRICS
    bad = []
    for k, name in enumerate(CAMPAIGN_METRICS):
        a, b = float(chip[k]), float(ref[k])
        if name == "dip_depth":
            ok = abs(a - b) <= DIP_ATOL
        elif name == "recovery_time_s":
            ok = a == b or abs(a - b) <= RECOVERY_ATOL_S
        else:
            ok = bool(np.isclose(a, b, rtol=RTOL, atol=1e-6))
        if not ok:
            bad.append(f"{name} chip {a!r} cpu {b!r}")
    return bad


def phase_numerics(c: Checks, seed: int, sims, campaigns: dict) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.tcp import (
        assert_maxmin_certificate,
        demand_limited_maxmin_np,
        maxmin_fused,
    )
    from repro.streams import simulate

    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(seed)
    for policy in POLICIES:
        runner, cr = campaigns[policy]
        idx = _sample(runner.plan(sims, policy), N_SAMPLE, rng)
        worst: list[str] = []
        with jax.default_device(cpu):
            for i in idx:
                sim = jax.device_put(sims[i], cpu)
                ref = simulate(sim, policy, seconds=SECONDS).metrics
                bad = _row_mismatches(cr.metrics[i], ref)
                if bad:
                    worst.append(f"scenario {i}: " + "; ".join(bad))
        for w in worst[:4]:
            print(f"    {w}")
        c.report[f"reference_{policy}"] = {"sampled": idx,
                                           "mismatches": worst}
        c.check(not worst,
                f"{policy}: {len(idx) - len(worst)}/{len(idx)} sampled "
                f"scenarios match the CPU simulate reference")

    # tcp max-min solves: corpus routing with seeded demands, plus
    # random instances on both sides of the GEMM/sorted crossover
    cases = []
    runner, _ = campaigns["tcp"]
    for i in _sample(runner.plan(sims, "tcp"), 4, rng):
        R = np.asarray(sims[i].R, np.float32)
        cap = np.asarray(sims[i].caps, np.float32)
        d = rng.uniform(0.0, 2.0 * cap.max(), R.shape[0]).astype(np.float32)
        cases.append((f"scenario {i}", R, cap, d))
    for F, L in ((64, 24), (512, 64)):
        R = np.zeros((F, L), np.float32)
        for f in range(F):
            R[f, rng.choice(L, size=3, replace=False)] = 1.0
        cap = rng.uniform(1.0, 20.0, L).astype(np.float32)
        d = rng.uniform(0.0, 10.0, F).astype(np.float32)
        cases.append((f"random F={F} L={L}", R, cap, d))
    for name, R, cap, d in cases:
        x = np.asarray(maxmin_fused(jnp.asarray(R), jnp.asarray(cap),
                                    jnp.asarray(d), rounds=None))
        ref = demand_limited_maxmin_np(R, cap, d)
        scale = max(float(cap.max()), 1.0)
        diff = float(np.abs(x - ref).max())
        c.check(bool(np.allclose(x, ref, rtol=1e-4, atol=1e-4 * scale)),
                f"maxmin_fused {name} matches the numpy fill "
                f"(max |diff| {diff:.3g})")
        try:
            assert_maxmin_certificate(R, cap, d, x)
            ok, why = True, ""
        except AssertionError as e:
            ok, why = False, f": {e}"
        c.check(ok, f"maxmin_fused {name} passes the KKT certificate{why}")


# ------------------------------------------------------------- phase 4
TRAJ_FIELDS = ("sink_mb", "sink_mb_app", "latency", "link_load", "caps_t",
               "order_rebuilds")


def _ulps(a, b):
    import numpy as np
    ai = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    bi = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return np.abs(ai - bi)


def _compare_runs(ref, got) -> tuple[dict, list[str]]:
    """Per field: [scenarios differing, max ULP]; and the scenarios whose
    metric rows miss the reference tolerances."""
    import numpy as np
    diff: dict[str, list] = {}
    bad = []
    for i, (r0, r1) in enumerate(zip(ref, got)):
        for f in TRAJ_FIELDS + ("metrics",):
            x, y = getattr(r0, f), getattr(r1, f)
            if x is None or np.array_equal(x, y):
                continue
            d = diff.setdefault(f, [0, 0])
            d[0] += 1
            if f != "order_rebuilds":
                fin = np.isfinite(x) & np.isfinite(y)
                d[1] = max(d[1], int(_ulps(x[fin], y[fin]).max(initial=0)))
        m = _row_mismatches(got[i].metrics, r0.metrics)
        if m:
            bad.append(f"scenario {i}: " + "; ".join(m))
    return diff, bad


def phase_row_count(c: Checks, seed: int) -> None:
    from repro.streams import FleetRunner, bench_fleet, compile_fleet

    sims = compile_fleet(bench_fleet(seed=seed))
    for policy in ("tcp", "appaware"):
        runner = FleetRunner()
        ref = runner.run(sims, policy, seconds=SECONDS, shard=False)
        rows = runner.last_stats["rows"]
        biggest = max(len(idxs) for idxs, _ in runner.plan(sims, policy))
        for label, chunk in (("whole buckets", biggest),
                             ("chunks of <= 3 rows", 3)):
            cr = runner.run_campaign(sims, policy, seconds=SECONDS,
                                     chunk_rows=chunk, shard=False,
                                     retain_trajectories=True)
            diff, bad = _compare_runs(ref, cr.results)
            chunk_rows = runner.last_stats["rows"]
            c.report[f"row_count_{policy}_{chunk}"] = {
                "run_rows": rows, "chunk_rows": chunk_rows,
                "differing": diff, "mismatches": bad}
            print(f"  {policy}, run rows {rows} vs campaign {label} "
                  f"(rows {chunk_rows}): differing field: "
                  f"[scenarios, max ULP] {diff}")
            for w in bad[:4]:
                print(f"    {w}")
            if chunk == biggest:
                c.check(not diff, f"{policy}: each bucket as one campaign "
                        f"chunk is bitwise equal to run")
            else:
                c.check(not bad, f"{policy}: smaller chunks match run within "
                        f"the reference tolerances "
                        f"({len(sims) - len(bad)}/{len(sims)})")


# ------------------------------------------------------------ 4 chips
def phase_sharded_campaign(c: Checks, seed: int) -> None:
    import numpy as np
    from repro.streams import FleetRunner, campaign_fleet, compile_fleet

    sims = compile_fleet(campaign_fleet(N_CAMPAIGN, seed=seed))
    crs = {}
    for shard in (False, True):
        runner = FleetRunner()
        t0 = time.perf_counter()
        crs[shard] = runner.run_campaign(sims, "tcp", seconds=SECONDS,
                                         shard=shard)
        st = runner.last_stats
        on = st["chunks_per_device"]
        c.report[f"campaign_shard_{shard}"] = {
            "chunks_per_device": on, "n_streams": st["n_streams"],
            "wall_s": time.perf_counter() - t0}
        print(f"  shard={shard}: {st['n_streams']} streams, chunks per "
              f"device {on}, {time.perf_counter() - t0:.3f} s wall "
              f"(chip, informational)")
        c.check(not crs[shard].failures and st["n_retries"] == 0,
                f"shard={shard}: no failures, no retries")
        if shard:
            c.check(len(on) == 4, f"sharded outputs landed on {len(on)} "
                    f"devices")
    a, b = crs[False].metrics, crs[True].metrics
    c.check(a.shape == b.shape and bool(np.all(
        a.view(np.uint32) == b.view(np.uint32))),
        "sharded campaign metrics bitwise equal to one chip")


def phase_sharded_run(c: Checks, seed: int) -> None:
    """Sharded ``run`` (whole buckets spread over the chips) against
    unsharded: trajectories bitwise, metrics bitwise but for
    ``total_sink_mb`` within ``SINK_ULPS``."""
    from repro.streams import FleetRunner, bench_fleet, compile_fleet
    from repro.streams.simulator import CAMPAIGN_METRICS, metric_index

    sink = metric_index("total_sink_mb")
    sims = compile_fleet(bench_fleet(seed=seed))
    for policy in ("tcp", "appaware"):
        outs = {}
        for shard in (False, True):
            runner = FleetRunner()
            outs[shard] = runner.run(sims, policy, seconds=SECONDS,
                                     shard=shard)
            st = runner.last_stats
            if shard:
                n_used = min(4, st["n_buckets"])
                print(f"  {policy}: bucket rows {st['rows']} on devices "
                      f"{st['bucket_devices']}")
                c.check(st["n_shards"] == n_used
                        and len(set(st["bucket_devices"])) == n_used,
                        f"{policy}: {st['n_buckets']} buckets spread over "
                        f"{st['n_shards']} devices")
        diff, _ = _compare_runs(outs[False], outs[True])
        loose = {f: d for f, d in diff.items() if f != "metrics"}
        c.report[f"run_shard_{policy}"] = {"differing": diff}
        print(f"  {policy}: differing field: [scenarios, max ULP] {diff}")
        c.check(not loose, f"{policy}: sharded run trajectories bitwise "
                f"equal to unsharded")
        worst = 0
        cols = set()
        for r0, r1 in zip(outs[False], outs[True]):
            u = _ulps(r0.metrics, r1.metrics)
            cols |= {CAMPAIGN_METRICS[k] for k in u.nonzero()[0]}
            worst = max(worst, int(u[sink]))
        c.check(cols <= {"total_sink_mb"} and worst <= SINK_ULPS,
                f"{policy}: sharded run metrics bitwise but total_sink_mb, "
                f"which is within {worst} <= {SINK_ULPS} ULP "
                f"(columns differing: {sorted(cols)})")


# --------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--report", default=None,
                    help="write the detailed check report as JSON here")
    args = ap.parse_args()
    if os.environ.get("REPRO_SMOKE", "").strip() not in ("", "0"):
        print("chip_smoke: REPRO_SMOKE caps the simulated horizon; unset "
              "it", file=sys.stderr)
        return 2

    import jax

    from repro.compile_cache import setup_compile_cache

    cache = setup_compile_cache()
    cache_seen = _watch_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (default device is {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    c = Checks()
    if args.chips == 4:
        c.phase("sharded campaign", phase_sharded_campaign, args.seed)
        c.phase("sharded fleet run", phase_sharded_run, args.seed)
    else:
        from repro.streams import campaign_fleet, compile_fleet
        c.phase("1 controller solve", phase_controller, args.seed)
        sims = compile_fleet(campaign_fleet(N_CAMPAIGN, seed=args.seed))
        campaigns: dict = {}
        c.phase("2 streaming campaign", phase_campaign, args.seed, sims,
                campaigns)
        if len(campaigns) == len(POLICIES):
            c.phase("3 numerics", phase_numerics, args.seed, sims,
                    campaigns)
        else:
            c.failed.append("3 numerics: skipped, campaign incomplete")
        c.phase("4 row count", phase_row_count, args.seed)
    c.report["compile_cache"] = cache_seen
    print(f"compile cache {cache}: {cache_seen['lookups']} lookups, "
          f"{cache_seen['hits']} hits, {cache_seen['writes']} written, "
          f"{cache_seen['saved_s']:.3f} s compile saved (informational)")
    c.report["failed"] = c.failed
    if args.report:
        os.makedirs(os.path.dirname(os.path.abspath(args.report)),
                    exist_ok=True)
        with open(args.report, "w") as f:
            json.dump(c.report, f, indent=1, default=repr)
    if c.failed:
        print(f"chip_smoke: {len(c.failed)} check(s) failed:",
              file=sys.stderr)
        for w in c.failed:
            print(f"  {w}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
