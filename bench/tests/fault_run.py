#!/usr/bin/env python3
"""Run one benchmark cell on the CPU at a small size, optionally with a
fault planted in its timed path, and print the run's result line.

    python3 bench/tests/fault_run.py <cell> <fault> [--seed N]

Faults: ``none``; ``state_unchanged`` (a step returns its state: the
simulator's tick moves nothing, or the allocator's backfill returns its
input); ``half_batch`` and ``quarter_batch`` (the last half or quarter of
the answers left out, filled with the mean of the rest); ``one_bucket``
and ``one_chunk`` (the rows of one bucket of the campaign's plan, or of
one of its chunks, each written to its neighbour's place);
``answer_altered`` (an answer changed where it is produced). The chip
check of the harness is skipped; everything else of a run is driven as
on the chip.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

SMALL = {"campaign": {"config": {"n_scenarios": 12, "horizon_s": 60.0,
                                  "chunk_rows": 2}},
         "controller": {"traffic": {"n_states": 2}}}


def _campaign_faults(fault: str, hooks: dict) -> None:
    import jax.numpy as jnp

    from repro.streams import simulator

    if fault == "state_unchanged":
        def tick(sim, Qs, Qr, x, dt, qcap, caps_t=None, enforce=True,
                 R_t=None):
            z = jnp.zeros_like(Qs)
            L = sim.R.shape[1]
            return Qs, Qr, z, z, (jnp.zeros(()), jnp.zeros((1,)), z,
                                  jnp.zeros((L,)))
        simulator._tick = tick
    elif fault == "answer_altered":
        epilogue = simulator._metrics_epilogue

        def altered(*a, **k):
            m = epilogue(*a, **k)
            return m.at[0].multiply(1.0 + 1e-4)
        simulator._metrics_epilogue = altered
    elif fault in ROW_FAULTS:
        def wrap(run_campaign):
            def run(sims, policy, **k):
                cr = run_campaign(sims, policy, **k)
                plan = run_campaign.__self__.plan(sims, policy)
                break_rows(cr.metrics, fault, plan, k["chunk_rows"])
                return cr
            return run
        hooks["wrap_campaign"] = wrap
    elif fault != "none":
        raise ValueError(fault)


ROW_FAULTS = ("half_batch", "quarter_batch", "one_bucket", "one_chunk")


def break_rows(m, fault: str, plan, chunk_rows: int) -> None:
    """Plant one of ``ROW_FAULTS`` in a campaign's metric slab ([n, 7])."""
    from benchlib import campaign

    n = m.shape[0]
    if fault in ("half_batch", "quarter_batch"):
        lo = n // 2 if fault == "half_batch" else n - n // 4
        m[lo:] = m[:lo].mean(axis=0)
        return
    rows = (plan[-1][0] if fault == "one_bucket"
            else campaign.chunks(plan, chunk_rows)[-1])
    rows = np.asarray(rows)
    m[rows] = m[np.roll(rows, 1)]


def _controller_faults(fault: str, hooks: dict) -> None:
    from repro.core import allocator

    if fault == "state_unchanged":
        allocator.backfill = lambda x, program, iters=8, damping=0.9: x
    elif fault in ("half_batch", "answer_altered"):
        def wrap(solve):
            def run(state):
                x = np.array(solve(state))
                if fault == "half_batch":
                    x[x.shape[0] // 2:] = x[:x.shape[0] // 2].mean()
                else:
                    x[np.argmax(x)] *= 1.0 + 1e-3
                return x
            return run
        hooks["wrap_solve"] = wrap
    elif fault != "none":
        raise ValueError(fault)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("fault")
    ap.add_argument("--seed", type=int, default=3_000_000_019)
    a = ap.parse_args()
    import run
    from benchlib import spec

    kind = spec.resolve(a.cell, ROOT).traffic["kind"]
    hooks = {"require_chip": False, **SMALL[kind]}
    (_campaign_faults if kind == "campaign" else _controller_faults)(
        a.fault, hooks)
    return run.main(["--workload", a.cell, "--seed", str(a.seed),
                     "--seconds", "0.6"], hooks=hooks, t_start=T0)


if __name__ == "__main__":
    sys.exit(main())
