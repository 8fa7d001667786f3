"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import json
import os
import time

from repro.net import LinkKind, big_switch, fat_tree
from repro.streams import compile_sim, parallelize, round_robin, simulate

CAPS = {"10Mbps": 1.25, "15Mbps": 1.875, "20Mbps": 2.5}
SECONDS = 600.0
DT = 0.5


def run_pair(app_fn, topo, seconds=SECONDS, seed=0, **sim_kw):
    """Run TCP vs App-aware on one app/topology; returns (tcp, appaware)."""
    g = parallelize(app_fn(), seed=seed)
    sim = compile_sim(g, topo, round_robin(g, topo.n_machines))
    tcp = simulate(sim, "tcp", seconds=seconds, dt=DT, **sim_kw)
    aa = simulate(sim, "appaware", seconds=seconds, dt=DT, **sim_kw)
    return tcp, aa


def singlehop_topo(cap: float):
    """10-machine cluster, 8 workers, bottleneck at machine up/downlinks."""
    return big_switch(8, cap)


def multihop_topo(cap: float):
    """Fat-tree testbed (Fig. 2) with throttled internal links (§VI-A.1)."""
    return fat_tree(up=12.5).set_capacity(LinkKind.INTERNAL, cap)


def smoke_mode() -> bool:
    """True when REPRO_SMOKE is set (the CI runner): benchmarks shrink
    their problem sizes / iteration counts, and perf_gate applies its
    conservative smoke floors. One definition so a bench and the gate
    can never disagree about which mode a run was in."""
    return os.environ.get("REPRO_SMOKE", "").strip() not in ("", "0")


_JSON_ROWS: dict[str, list[dict]] = {}


# repo root: BENCH_*.json always lands here (full *and* smoke mode, any
# CWD) so the per-PR perf trajectory is never silently empty; override
# with BENCH_DIR for scratch runs
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def emit(rows: list[dict], name: str) -> None:
    """CSV to stdout: name,us_per_call,derived-metrics...

    Every section also accumulates into ``BENCH_<name>.json`` (in
    ``BENCH_DIR``, default the repo root) so CI can upload the per-PR perf
    trajectory as a workflow artifact.

    ``us_per_call`` is *optional* — rows that carry no timing (pure
    invariant/observable rows like ``fleet_order_cache``) simply omit the
    field and print ``-`` in its column. A row that DOES carry it must
    carry a real measurement: zero or negative timings are rejected here
    so a broken timer can't silently land as a plausible-looking 0.0 in
    the committed JSON again."""
    for r in rows:
        us = r.get("us_per_call")
        if us is not None and not float(us) > 0.0:
            raise ValueError(
                f"row {r.get('name', name)!r}: us_per_call={us!r} is not a "
                f"positive timing — omit the field for non-timing rows")
        derived = ";".join(f"{k}={v}" for k, v in r.items()
                           if k not in ("name", "us_per_call"))
        col = f"{float(us):.2f}" if us is not None else "-"
        print(f"{r.get('name', name)},{col},{derived}")
    _JSON_ROWS.setdefault(name, []).extend(rows)
    path = os.path.join(os.environ.get("BENCH_DIR", _REPO_ROOT),
                        f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(_JSON_ROWS[name], f, indent=1, default=str)


def timeit_us(fn, iters: int = 10) -> float:
    fn()  # compile
    t0 = time.time()
    for _ in range(iters):
        fn()
    return (time.time() - t0) / iters * 1e6
