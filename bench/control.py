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
compiled through the program as in a run.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def campaign_control(cell, seed: int) -> dict:
    """The control's rows of the cell's own sample: one scenario of every
    chunk of the plan this machine's planner makes for the corpus."""
    from repro.streams import FleetRunner

    from benchlib import campaign, deploy, reference
    policy = cell.traffic["policy"]
    kw = campaign.settings(cell.config, policy)
    corpus = deploy.testbed_corpus(cell.config, seed)
    plan = FleetRunner().plan(
        [deploy.program_scenario(sc).compile() for sc in corpus], policy)
    chosen = [corpus[i] for i in campaign.sample(plan, kw["chunk_rows"], seed)]
    n_ticks = int(round(kw["seconds"] / kw["dt"]))
    rows = [reference.simulate_ref(
                reference.testbed_arrays(sc.graph, sc.placement,
                                         sc.n_machines, sc.cap, sc.events,
                                         sc.diurnal),
                policy, n_ticks, kw["dt"], kw["upd_every"], kw["qcap"],
                reference.Arith("high"))[None]
            for sc in chosen]
    checks, _ = campaign.compare(chosen, rows, policy, kw,
                                 cell.traffic["limits"])
    return checks


def controller_control(cell, seed: int) -> dict:
    from benchlib import controller, deploy, reference
    cfg, tr = cell.config, cell.traffic
    fab = deploy.fabric(cfg, seed)
    states = deploy.flow_states(cfg, fab, int(tr["n_states"]),
                                int(tr["warm_intervals"]))
    dt = float(cfg["controller_interval_s"])
    iters = int(cfg["backfill_iters"])
    answers = [reference.allocate_ref(fab.R, fab.cap, fab.kind, st, dt,
                                      reference.Arith("high"),
                                      backfill_iters=iters)
               for st in states]
    checks, _ = controller.compare(fab, states, answers, dt, iters,
                                   tr["limits"])
    return checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    from benchlib import spec
    cell = spec.resolve(a.workload)
    fn = {"campaign": campaign_control,
          "controller": controller_control}[cell.traffic["kind"]]
    failed_all = True
    for seed in (int(s) for s in a.seeds.split(",")):
        checks = fn(cell, seed)
        failed = not all(c["ok"] for c in checks.values())
        failed_all &= failed
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "control_failed": failed,
                          "checks": checks}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
