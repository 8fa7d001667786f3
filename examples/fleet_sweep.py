"""Fleet simulation demo: a capacity × workload parameter sweep in ONE
fused kernel dispatch per policy.

Builds the paper's §VI grid — {TT, TI} × {10, 15, 20 Mbps} × {single-hop,
multi-hop} — as 12 scenarios and runs TCP and App-aware across the whole
grid through a persistent `FleetRunner`: every shape bucket's
vmap-over-scan lives inside one jitted executable, so a warm sweep is a
single kernel launch per policy. The second (warm) sweep shows what a
repeat study costs once the executables are cached — the runner's
`last_stats` reports the dispatch count and bucket structure behind each
number. Compare `stream_allocator_demo.py`, which walks the same grid
with 12 separate compile+run cycles per policy.

    PYTHONPATH=src python examples/fleet_sweep.py
"""
from __future__ import annotations

import time

from repro.compile_cache import setup_compile_cache
from repro.streams import FleetRunner, capacity_sweep, compile_fleet

SECONDS = 600.0


def main() -> None:
    setup_compile_cache()
    scenarios = capacity_sweep(multihop=False) + capacity_sweep(multihop=True)
    sims = compile_fleet(scenarios)
    runner = FleetRunner()
    print(f"fleet: {len(sims)} scenarios "
          f"(one fused executable per policy)\n")

    t0 = time.time()
    tcp = runner.run(sims, "tcp", seconds=SECONDS)
    tcp_stats = dict(runner.last_stats)
    aa = runner.run(sims, "appaware", seconds=SECONDS)
    aa_stats = dict(runner.last_stats)
    cold = time.time() - t0

    # warm repeat: executables cached, staging reused — a parameter
    # re-study pays pure execution
    t0 = time.time()
    runner.run(sims, "tcp", seconds=SECONDS)
    runner.run(sims, "appaware", seconds=SECONDS)
    warm = time.time() - t0

    print(f"{'scenario':28s} {'tcp t/s':>9s} {'appaware t/s':>13s} {'Δ%':>7s}")
    for sc, r_tcp, r_aa in zip(scenarios, tcp, aa):
        gain = (r_aa.throughput_tps / max(r_tcp.throughput_tps, 1e-9) - 1) * 100
        print(f"{sc.name:28s} {r_tcp.throughput_tps:9.1f} "
              f"{r_aa.throughput_tps:13.1f} {gain:+6.1f}%")
    print(f"\nwhole sweep (both policies, {SECONDS:.0f}s runs): "
          f"{cold:.1f}s cold (compiles included), {warm:.2f}s warm repeat")
    for name, st in (("tcp", tcp_stats), ("appaware", aa_stats)):
        print(f"  {name}: {st['n_dispatches']} kernel dispatch(es), "
              f"{st['n_buckets']} shape bucket(s) in one executable, "
              f"padded rows {st['rows']}")
    per_scen = warm / 2 / len(sims) * 1e3
    print(f"  warm cost: {per_scen:.1f} ms/scenario/policy")


if __name__ == "__main__":
    main()
