"""§VI-D: optimizer/enforcer overhead. Paper: ~6 ms per allocation on their
testbed scale; controller→switch updates 0.1–10 ms. We time (a) the full
Alg. 1 allocation on the paper-scale problem, (b) the batched Pallas
waterfill at datacenter scale (10⁴ links full mode; shrunk under
REPRO_SMOKE so the CI leg finishes in seconds — the row records which),
(c) the TCP max-min baseline, and (d) the campaign runtime's backend
calibration (dispatch/sync/tick overhead — the measurements behind
``chunk_rows="auto"``), emitted to ``BENCH_overhead.json`` like every
other bench so CI uploads the trajectory and ``perf_gate`` can demand the
snapshot exists.

    PYTHONPATH=src:. python benchmarks/overhead.py
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, smoke_mode, timeit_us
from repro.compile_cache import setup_compile_cache
from repro.core import FlowState, OnlineAllocator, maxmin_rates
from repro.kernels.waterfill.ops import waterfill
from repro.net import fat_tree
from repro.streams import parallelize, round_robin, trending_topics
from repro.streams.fleet import calibrate_backend


def run() -> list[dict]:
    rows = []
    rng = np.random.default_rng(0)
    smoke = smoke_mode()

    # (a) paper-scale: TT app on the fat-tree testbed
    g = parallelize(trending_topics(), seed=0)
    topo = fat_tree()
    flows = g.flow_pairs(round_robin(g, topo.n_machines))
    alloc = OnlineAllocator.from_topology(topo, flows)
    F = len(flows)
    st = FlowState(*[jnp.asarray(rng.uniform(0, 10, F), jnp.float32)
                     for _ in range(5)])
    us = timeit_us(lambda: jax.block_until_ready(alloc(st)),
                   iters=3 if smoke else 10)
    rows.append({"name": "overhead_alg1_paper_scale", "us_per_call": us,
                 "flows": F, "links": topo.n_links,
                 "paper_ms": 6.0, "ours_ms": round(us / 1e3, 3)})

    # (b) datacenter scale, Pallas kernel: 8192 links x 256 flows in full
    # mode; smoke shrinks the grid so the interpret-mode CPU run fits a
    # CI leg (the mode is recorded — the two scales are not comparable)
    L, Fk = (512, 64) if smoke else (8192, 256)
    w = jnp.asarray(rng.uniform(0, 20, (L, Fk)), jnp.float32)
    bl = jnp.asarray(rng.uniform(0, 30, (L, Fk)), jnp.float32)
    rho = jnp.asarray(rng.uniform(0.1, 10, (L, Fk)), jnp.float32)
    mask = jnp.asarray(rng.random((L, Fk)) < 0.5, jnp.float32)
    cap = jnp.asarray(rng.uniform(1, 50, L), jnp.float32)
    kind = jnp.asarray(rng.integers(0, 2, L), jnp.int32)
    us = timeit_us(
        lambda: jax.block_until_ready(
            waterfill(w, bl, rho, mask, cap, kind)),
        iters=2 if smoke else 3)
    rows.append({"name": f"overhead_waterfill_kernel_{L}x{Fk}",
                 "us_per_call": us,
                 "links": L, "flows_per_link": Fk,
                 "smoke": smoke,
                 "note": "interpret-mode on CPU; TPU compiled is the target"})

    # (c) TCP max-min on the same paper-scale problem
    R = jnp.asarray(topo.routing_matrix(flows), jnp.float32)
    caps = jnp.asarray(topo.capacities, jnp.float32)
    us = timeit_us(lambda: jax.block_until_ready(maxmin_rates(R, caps)),
                   iters=3 if smoke else 10)
    rows.append({"name": "overhead_tcp_maxmin", "us_per_call": us})

    # (d) campaign backend calibration: per-dispatch / sync / per-tick
    # overhead as measured by the `chunk_rows="auto"` machinery — the
    # same numbers run_campaign records in last_stats["calibration"]
    cal = calibrate_backend()
    rows.append({"name": "overhead_backend_calibration",
                 "us_per_call": cal.dispatch_us,
                 **dataclasses.asdict(cal)})
    return rows


def main() -> None:
    emit(run(), "overhead")


if __name__ == "__main__":
    setup_compile_cache()
    main()
