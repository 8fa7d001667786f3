#!/usr/bin/env python3
"""The control of a cell's correctness check: the plain reference, put in
the program's place and computed one precision step below the
configuration's float32 at HIGHEST (float32 elementwise, every
contraction in three bf16 passes, the TPU's ``high``), compared by the
cell's own numbers against the float64 reference.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

Prints one JSON line per seed with each number compared beside its limit;
a sound check reads above the limit on every seed. Runs on the host (the
reference and its control are numpy); the scenarios are built and
compiled through the program as in a run. Each kind of traffic's control
is the ``control(cell, seed)`` of its loop (``bench/loops/<kind>.py``).
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    from benchlib import spec
    cell = spec.resolve(a.workload)
    loop = spec.load_loop(cell.traffic["kind"])
    failed_all = True
    for seed in (int(s) for s in a.seeds.split(",")):
        checks = loop.control(cell, seed)
        failed = not all(c["ok"] for c in checks.values())
        failed_all &= failed
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "control_failed": failed,
                          "checks": checks}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
