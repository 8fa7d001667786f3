"""CI perf gate: fail the job when the fleet warm path regresses.

Parses ``BENCH_fleet.json`` (written by ``benchmarks/fleet.py``) and
checks, per policy:

* ``speedup_warm`` against a checked-in floor,
* ``n_dispatches == n_shards`` — the packed runtime's structural
  invariant: a warm fleet run is ONE fused executable per device it uses
  (``n_shards``, 1 unless the run spreads its buckets over forced host
  devices). A solver or runner change that silently falls back to
  per-bucket dispatch fails the gate even if the wall-clock happens to
  look fine on the runner that day, and
* the ``fleet_order_cache`` row — the order-cached max-min solver must
  rebuild its demand-rank operand exactly ONCE per scenario on the
  static-demand corpus scan (the tick-0 cold start). More than one means
  the O(F) order check is spuriously invalidating carried state (the
  order cache silently degrades to rebuild-every-tick); zero means the
  cold start stopped being counted, and
* the ``fleet_campaign`` row — the streaming campaign mode
  (``FleetRunner.run_campaign``) must keep its throughput within the
  floor of the materialized path on the same corpus
  (``stream_vs_materialized``: chunk staging re-done per call has to be
  paid for by its overlap with in-flight device compute), its host
  staging bounded (``peak_staged_rows`` ≤ 3 × ``chunk_rows`` ×
  ``n_streams`` — the three rotating slots per device stream, one per
  pipeline stage; more means the bounded-memory property silently broke
  and a 10⁴-scenario campaign would materialize after all), and its H2D
  prefetch overlapped (``transfer_overlap`` above the floor — 0 means
  the dispatch thread re-paid every copy, i.e. the transfer worker
  stopped prefetching), and
* the ``fleet_campaign_resilience`` row — the always-on fault-tolerance
  guards (finite-check per metric slab, transfer watchdog, checkpoint
  append) must be effectively free on the fault-free path
  (``guard_overhead`` ≤ the ceiling; and the fault-free A/B must report
  zero retries/quarantines — anything else means the guards misfire
  without faults), and
* the ``fleet_campaign_scaling`` row — the chunk stream sharded over
  the forced host devices must stay within a constant factor of the
  1-device run (``scaling_efficiency``; on a small CI container the
  streams share its cores, so the floor only catches sharding that
  serializes or duplicates work — real scaling is a chip measurement).

Missing input files are a hard, *loud* failure: benchmark snapshots are
checked into the repo (see ``.gitignore`` history — they used to be
ignored, which made "the gate passed" indistinguishable from "the gate
read nothing"), so an absent ``BENCH_*.json`` means the bench step was
skipped or its artifact lost, and the gate says exactly that instead of
raising a bare traceback.

On failure (and success) the gate prints the full measured-vs-floor table,
so a red CI job shows every margin at a glance instead of a bare assert.

Two modes:

* **smoke** (``REPRO_SMOKE=1``, the CI runner): floors are deliberately
  conservative — the shared CI runner's wall-clock is noisy and the
  sequential baseline there is itself fast, so the gate only catches real
  regressions (e.g. a change that re-serializes the batch), not
  scheduling jitter.
* **full** (REPRO_SMOKE unset): asserts the ROADMAP target for the
  measured-and-re-scoped warm-path item.

    PYTHONPATH=src:. python benchmarks/perf_gate.py [path/to/BENCH_fleet.json]
"""
from __future__ import annotations

import json
import os
import sys

# speedup_warm is strongly container-class dependent: the quiet 2-core
# container of PR 5 measured tcp 2.43 / appaware 2.67, while the loaded
# 1-core container that produced the committed BENCH_fleet.json measures
# 1.16 / 1.16 for the SAME code — op-dispatch contention slows the
# batched and sequential sides almost equally, so the ratio compresses
# toward 1 long before anything is actually wrong (interleaved A/B
# old-vs-new solver on that container: neutral on both sides, see
# ROADMAP item 1). Floors are therefore set to catch structural
# regressions — a batch path that re-serializes drops to <= 1.0 on ANY
# container — not to re-assert the quiet-container headline, which only
# the quiet-container BENCH refresh can do.
SMOKE_FLOORS = {"fleet_tcp": 1.05, "fleet_appaware": 1.05}
# Full-mode floors: a guard band under the weakest container class we
# have measured (1.16/1.16, loaded 1-core).
FULL_FLOORS = {"fleet_tcp": 1.1, "fleet_appaware": 1.1}

# Streaming-vs-materialized throughput floors (ratio of warm wall-clocks,
# same corpus, interleaved reps): ISSUE-7 target is >= 0.9x in full mode;
# smoke keeps a wider band for the noisy shared CI runner.
CAMPAIGN_SMOKE_FLOOR = 0.8
CAMPAIGN_FULL_FLOOR = 0.9

# H2D prefetch overlap floors: the fraction of copy time the dispatch
# thread did NOT re-pay as waiting. The loaded 1-core container measures
# ~0.5-0.9 depending on chunk compute; the floor only asserts the
# transfer worker still prefetches at all (0 = every copy waited on).
TRANSFER_OVERLAP_SMOKE_FLOOR = 0.05
TRANSFER_OVERLAP_FULL_FLOOR = 0.2

# Mid-run rerouting machinery ceilings (t_reroute / t_sched on the same
# failure-scheduled corpus): the precompiled route bank turns mid-run
# rerouting into one in-scan gather, so the warm ratio sits at ~1.0
# (measured 0.95 on the loaded 1-core container). The ceiling catches a
# change that reintroduces a per-state recompile or a lax.cond mode
# switch — either shows up as a multiple, not a few percent.
REROUTE_SMOKE_CEIL = 2.0
REROUTE_FULL_CEIL = 1.5

# Resilience guard ceilings (t_guarded / t_bare, interleaved best-of on
# the same corpus): the always-on fault-tolerance layer — finite-check on
# every metric slab, the transfer watchdog, a checkpoint append per chunk
# — must be effectively free on the fault-free path. ISSUE-10 target is
# <= 1.05x in full mode; smoke keeps a wider band because the fsync'd
# checkpoint appends meet a noisy shared-runner filesystem.
RESILIENCE_SMOKE_CEIL = 1.25
RESILIENCE_FULL_CEIL = 1.05

# forced-host-device scaling floors (t_1dev / t_ndev): on a 1-core
# container the streams share the core, so anything >= ~0.6 means
# the shard neither serialized nor duplicated work; multi-core targets
# (> 1) belong to the wide-backend ROADMAP item, not this gate.
SCALING_SMOKE_FLOOR = 0.5
SCALING_FULL_FLOOR = 0.6

# Companion snapshots that must exist alongside the gate's own input —
# their absence means the bench job silently skipped a section.
COMPANION_FILES = ("BENCH_allocator.json", "BENCH_overhead.json")


def _load(path: str):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def check(path: str) -> int:
    rows = _load(path)
    if rows is None:
        print(f"perf gate FAILED:\n  {path}: benchmark snapshot missing — "
              f"run `PYTHONPATH=src:. python benchmarks/fleet.py` (or "
              f"restore the committed BENCH_fleet.json); a missing input "
              f"is a gate failure, never a silent pass")
        return 1
    try:
        # one mode definition shared with the benches (common.smoke_mode);
        # falls back to the same env check when run without PYTHONPATH=src
        # (benchmarks.common imports repro at module level)
        from benchmarks.common import smoke_mode
        smoke = smoke_mode()
    except ImportError:
        smoke = os.environ.get("REPRO_SMOKE", "").strip() not in ("", "0")
    floors = SMOKE_FLOORS if smoke else FULL_FLOORS
    by_name = {r.get("name"): r for r in rows}
    table, failures = [], []
    for name, floor in floors.items():
        row = by_name.get(name)
        if row is None:
            failures.append(f"{name}: missing from {path}")
            table.append((name, "missing", f"{floor:.2f}", "-", "MISSING"))
            continue
        got = float(row.get("speedup_warm", 0.0))
        disp = row.get("n_dispatches")
        n_sh = row.get("n_shards", 1)
        ok_speed = got >= floor
        ok_disp = disp == n_sh
        status = "ok" if (ok_speed and ok_disp) else "REGRESSED"
        table.append((name, f"{got:.2f}", f"{floor:.2f}",
                      f"{disp}", status))
        if not ok_speed:
            failures.append(
                f"{name}: speedup_warm {got:.2f} < floor {floor:.2f}")
        if not ok_disp:
            failures.append(
                f"{name}: n_dispatches {disp} != {n_sh} devices used "
                f"(packed runtime fell back to per-bucket dispatch)")
    # order-cache structural invariant: exactly one rebuild per scenario
    # on the static-demand corpus scan
    oc = by_name.get("fleet_order_cache")
    if oc is None:
        failures.append(f"fleet_order_cache: missing from {path}")
        table.append(("fleet_order_cache", "missing", "1/scenario", "-",
                      "MISSING"))
    else:
        lo = int(oc.get("static_demand_rebuilds_min", -1))
        hi = int(oc.get("static_demand_rebuilds_max", -1))
        ok = lo == 1 and hi == 1
        table.append(("fleet_order_cache", f"rebuilds {lo}..{hi}",
                      "1/scenario", "-", "ok" if ok else "REGRESSED"))
        if not ok:
            failures.append(
                f"fleet_order_cache: static-demand rebuilds per scenario "
                f"in [{lo}, {hi}], expected exactly 1 (order cache "
                f"{'over-invalidates' if hi > 1 else 'lost its cold-start count'})")
    # streaming campaign mode: throughput floor + bounded host staging +
    # H2D prefetch overlap
    cp = by_name.get("fleet_campaign")
    cfloor = CAMPAIGN_SMOKE_FLOOR if smoke else CAMPAIGN_FULL_FLOOR
    tfloor = (TRANSFER_OVERLAP_SMOKE_FLOOR if smoke
              else TRANSFER_OVERLAP_FULL_FLOOR)
    if cp is None:
        failures.append(f"fleet_campaign: missing from {path}")
        table.append(("fleet_campaign", "missing", f"{cfloor:.2f}", "-",
                      "MISSING"))
    else:
        ratio = float(cp.get("stream_vs_materialized", 0.0))
        peak = int(cp.get("peak_staged_rows", -1))
        crows = int(cp.get("chunk_rows", 0))
        streams = max(int(cp.get("n_streams", 1)), 1)
        tover = float(cp.get("transfer_overlap", -1.0))
        bound = 3 * crows * streams
        ok_ratio = ratio >= cfloor
        ok_peak = 0 <= peak <= bound
        ok_tover = tover >= tfloor
        status = ("ok" if (ok_ratio and ok_peak and ok_tover)
                  else "REGRESSED")
        table.append(("fleet_campaign", f"{ratio:.2f}", f"{cfloor:.2f}",
                      f"peak {peak}/{bound}", status))
        if not ok_ratio:
            failures.append(
                f"fleet_campaign: stream_vs_materialized {ratio:.2f} < "
                f"floor {cfloor:.2f} (streaming mode lost its overlap)")
        if not ok_peak:
            failures.append(
                f"fleet_campaign: peak_staged_rows {peak} > 3 x chunk_rows "
                f"{crows} x n_streams {streams} — host staging is no "
                f"longer bounded by the per-stream rotating slots")
        if not ok_tover:
            failures.append(
                f"fleet_campaign: transfer_overlap {tover:.2f} < floor "
                f"{tfloor:.2f} (H2D prefetch no longer overlaps — the "
                f"dispatch thread re-pays every copy)")
    # mid-run rerouting: the banked in-scan gather must stay cheap
    rr = by_name.get("fleet_reroute_appaware")
    rceil = REROUTE_SMOKE_CEIL if smoke else REROUTE_FULL_CEIL
    if rr is None:
        failures.append(f"fleet_reroute_appaware: missing from {path}")
        table.append(("fleet_reroute_appaware", "missing",
                      f"<= {rceil:.2f}", "-", "MISSING"))
    else:
        over = float(rr.get("reroute_overhead", float("inf")))
        ok = over <= rceil
        table.append(("fleet_reroute_appaware", f"{over:.2f}",
                      f"<= {rceil:.2f}",
                      f"{rr.get('max_route_states')} states",
                      "ok" if ok else "REGRESSED"))
        if not ok:
            failures.append(
                f"fleet_reroute_appaware: reroute_overhead {over:.2f} > "
                f"ceiling {rceil:.2f} — the route bank stopped being a "
                f"cheap in-scan gather (per-state recompile or cond "
                f"mode switch reintroduced)")
    # resilience guards free when nothing fails: guarded/bare <= ceiling,
    # and the fault-free A/B must have quarantined or retried nothing
    rs = by_name.get("fleet_campaign_resilience")
    gceil = RESILIENCE_SMOKE_CEIL if smoke else RESILIENCE_FULL_CEIL
    if rs is None:
        failures.append(f"fleet_campaign_resilience: missing from {path}")
        table.append(("fleet_campaign_resilience", "missing",
                      f"{gceil:.2f}", "-", "MISSING"))
    else:
        over = float(rs.get("guard_overhead", float("inf")))
        clean = (rs.get("n_quarantined") == 0 and rs.get("n_retries") == 0)
        status = "ok" if (over <= gceil and clean) else "REGRESSED"
        table.append(("fleet_campaign_resilience", f"{over:.2f}",
                      f"<= {gceil:.2f}", "-", status))
        if over > gceil:
            failures.append(
                f"fleet_campaign_resilience: guard_overhead {over:.2f} > "
                f"ceiling {gceil:.2f} — the fault-free path is paying for "
                f"the resilience layer")
        if not clean:
            failures.append(
                f"fleet_campaign_resilience: fault-free A/B reported "
                f"retries/quarantines "
                f"({rs.get('n_retries')}/{rs.get('n_quarantined')}) — the "
                f"guards are misfiring without faults")
    # sharded chunk stream over the forced host devices: within a
    # constant factor of the 1-device run
    sc = by_name.get("fleet_campaign_scaling")
    sfloor = SCALING_SMOKE_FLOOR if smoke else SCALING_FULL_FLOOR
    if sc is None:
        failures.append(f"fleet_campaign_scaling: missing from {path}")
        table.append(("fleet_campaign_scaling", "missing", f"{sfloor:.2f}",
                      "-", "MISSING"))
    else:
        eff = float(sc.get("scaling_efficiency", 0.0))
        ndev = sc.get("n_devices")
        ok_eff = eff >= sfloor
        ok_dev = (ndev or 0) > 1
        status = "ok" if (ok_eff and ok_dev) else "REGRESSED"
        table.append(("fleet_campaign_scaling", f"{eff:.2f}",
                      f"{sfloor:.2f}", f"{ndev} dev", status))
        if not ok_eff:
            failures.append(
                f"fleet_campaign_scaling: scaling_efficiency "
                f"{eff:.2f} < floor {sfloor:.2f} (sharded stream "
                f"serialized or duplicated work)")
        if not ok_dev:
            failures.append(
                f"fleet_campaign_scaling: measured on {ndev} device(s) — "
                f"the host-device XLA flag was not applied")
    # companion snapshots exist (content is informational — calibration
    # rows — but absence means the bench job dropped a section)
    bench_dir = os.path.dirname(os.path.abspath(path)) or "."
    for fname in COMPANION_FILES:
        fpath = os.path.join(bench_dir, fname)
        if not os.path.exists(fpath):
            failures.append(
                f"{fname}: companion benchmark snapshot missing from "
                f"{bench_dir} — run `PYTHONPATH=src:. python "
                f"benchmarks/allocator.py`")
    header = ("bench", "measured", "floor", "dispatches", "status")
    widths = [max(len(str(r[i])) for r in [header] + table)
              for i in range(len(header))]
    for r in [header] + table:
        print("  ".join(str(v).ljust(w) for v, w in zip(r, widths)))
    if failures:
        print("perf gate FAILED:\n  " + "\n  ".join(failures))
        return 1
    print(f"perf gate passed ({'smoke' if smoke else 'full'} floors)")
    return 0


if __name__ == "__main__":
    path = sys.argv[1] if len(sys.argv) > 1 else "BENCH_fleet.json"
    sys.exit(check(path))
