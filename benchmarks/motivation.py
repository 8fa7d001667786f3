"""Fig. 3 (motivation): three placements of a 4-operator chain; TCP vs the
best fixed bandwidth allocation found by brute-force search. Paper: BA beats
TCP by 17% / 47% / 33% for TP1/TP2/TP3 — placement alone is not enough."""
from __future__ import annotations

import numpy as np

from benchmarks.common import DT, emit
from repro.compile_cache import setup_compile_cache
from repro.net import big_switch
from repro.streams import compile_sim, motivation_chain, parallelize, simulate

# three placements over 3 machines (instances: src, opA, opB, sink)
PLACEMENTS = {
    "TP1": np.array([0, 1, 2, 1]),   # chain spread; src->A & B->sink disjoint
    "TP2": np.array([0, 1, 0, 2]),   # src+opB co-located -> shared uplink m0
    "TP3": np.array([0, 0, 1, 2]),   # src+opA co-located; A->B & B->sink mix
}
CAP = 1.25
SECONDS = 300.0


def brute_force_best(sim, n_flows: int, grid: int = 7) -> float:
    """Grid-search fixed rate vectors over the flows (the paper's costly
    exhaustive search; small topology makes it feasible)."""
    best = 0.0
    ws = np.linspace(0.1, 1.0, grid)
    from itertools import product
    for w in product(ws, repeat=n_flows):
        x = np.asarray(w, np.float32) * CAP
        r = simulate(sim, "fixed", seconds=SECONDS, dt=DT, x_fixed=x)
        best = max(best, r.throughput_tps)
    return best


def run(fast: bool = True) -> list[dict]:
    rows = []
    g = parallelize(motivation_chain(), seed=0)
    topo = big_switch(3, CAP)
    for name, place in PLACEMENTS.items():
        sim = compile_sim(g, topo, place)
        tcp = simulate(sim, "tcp", seconds=SECONDS, dt=DT)
        grid = 5 if fast else 9
        best = brute_force_best(sim, g.n_flows, grid=grid)
        # the online allocator should recover most of the brute-force gain
        aa = simulate(sim, "appaware", seconds=SECONDS, dt=DT)
        rows.append({
            "name": f"fig3_motivation_{name}",
            "tcp_tps": round(tcp.throughput_tps, 1),
            "bruteforce_tps": round(best, 1),
            "appaware_tps": round(aa.throughput_tps, 1),
            "ba_gain_pct": round((best / max(tcp.throughput_tps, 1e-9) - 1)
                                 * 100, 1),
        })
    return rows


def main() -> None:
    emit(run(), "fig3")


if __name__ == "__main__":
    setup_compile_cache()
    main()
