#!/usr/bin/env python3
"""Run one benchmark cell on the CPU at a small size, optionally with a
fault planted in its timed path, and print the run's result line.

    python3 bench/tests/fault_run.py <cell> <fault> [--seed N]

Faults (``bench/tests/faults/<kind>.py``): ``none``; ``state_unchanged``
(a step returns its state: the simulator's tick moves nothing, or the
allocator's backfill returns its input); ``half_batch`` and
``quarter_batch`` (the last half or quarter of the answers left out,
filled with the mean of the rest); ``one_bucket`` and ``one_chunk`` (the
rows of one bucket of the campaign's plan, or of one of its chunks, each
written to its neighbour's place); ``answer_altered`` (an answer changed
where it is produced). The chip check of the harness is skipped;
everything else of a run is driven as on the chip.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


# cells whose files are here though BENCHMARK.json does not hold them,
# each with the cell whose metrics it would report
CANDIDATES = (({"name": "testbed.controller", "config": "storm-testbed-8",
                "traffic": "controller-testbed", "chips": 1},
               "fattree.controller"),)


def benchmark() -> dict:
    """``BENCHMARK.json`` with the ``CANDIDATES`` added, each reporting
    the metrics of its named cell."""
    from benchlib import spec

    bench = spec.load_benchmark(ROOT)
    for w, like in CANDIDATES:
        bench["workloads"].append(dict(w))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(w["name"])
    return bench


def faults(kind: str, root: str = ROOT):
    """The fault module of traffic kind ``kind``:
    ``bench/tests/faults/<kind>.py``, with ``SMALL`` (the run's size
    overrides), ``FAULTS`` (what the kind's cells can have) and
    ``plant(fault, hooks)``."""
    from benchlib import spec

    return spec.load_file(os.path.join(root, "bench", "tests", "faults",
                                       f"{kind}.py"),
                          f"the faults of traffic kind {kind!r}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("fault")
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    a = ap.parse_args()
    import run
    from benchlib import spec

    bench = benchmark()
    mod = faults(spec.resolve(a.cell, ROOT, bench).traffic["kind"])
    hooks = {"require_chip": False, "benchmark": bench, **mod.SMALL}
    mod.plant(a.fault, hooks)
    return run.main(["--workload", a.cell, "--seed", str(a.seed),
                     "--seconds", "0.6"], hooks=hooks, t_start=T0)


if __name__ == "__main__":
    sys.exit(main())
