"""Runs campaign cells as ``bench/loops/campaign.py`` does, and checks a
wider sample: ``PER_CHUNK`` scenarios of every chunk of the plan, where
that loop takes one.

Its two numbers are that loop's (``row_gap_max``, ``row_gap_median``),
computed over the wider sample. A cell needs it where float32 rounding
alone takes a few scenarios' widest gap as far as the precision step
down does, so that the median over one scenario a chunk cannot tell the
two apart on every seed; over more scenarios it can.

What it calls of the cell's deployment module: what the campaign loop
calls.
"""
from __future__ import annotations

import numpy as np

from benchlib import spec

campaign = spec.load_loop("campaign")
LAYERS = campaign.LAYERS
# scenarios checked of every chunk: with 16 chunks, the median over 64
# kept float32 and the control apart on every seed tried; over 16 it did not
PER_CHUNK = 4


def sample(plan, chunk_rows: int, seed: int,
           per_chunk: int = PER_CHUNK) -> list[int]:
    """``per_chunk`` distinct scenarios of every chunk (all of a smaller
    one), drawn from the seed."""
    rng = np.random.default_rng([seed, 3])
    out = []
    for c in campaign.chunks(plan, chunk_rows):
        out += rng.choice(c, min(per_chunk, len(c)), replace=False).tolist()
    return sorted(int(i) for i in out)


def run(cell, args, t_start: float, devices, hooks: dict) -> tuple:
    """The campaign loop's run, its window's metric rows kept (through the
    ``wrap_campaign`` hook, inside any wrapper the hooks already hold),
    checked over the wider sample."""
    seen = []          # (runner, sims, metrics) of every campaign run
    inner = hooks.get("wrap_campaign", lambda f: f)

    def wrap(run_campaign):
        wrapped = inner(run_campaign)

        def keep(sims, policy, **kw):
            cr = wrapped(sims, policy, **kw)
            seen.append((run_campaign.__self__, sims, cr.metrics))
            return cr
        return keep

    e2e, ctx, _, dev, n_done, _ = campaign.run(
        cell, args, t_start, devices, dict(hooks, wrap_campaign=wrap))
    runner, sims, _ = seen[0]
    policy = cell.traffic["policy"]
    kw = campaign.settings(cell.config, policy)
    idx = sample(runner.plan(sims, policy), kw["chunk_rows"], args.seed)
    corpus = cell.deployment.corpus(cell.config, args.seed)
    window = [m for _, _, m in seen[1:]]          # the warm-up left out
    checks, n_bad = campaign.compare(
        cell.deployment, [corpus[i] for i in idx],
        [np.stack([m[i] for m in window]) for i in idx], policy, kw,
        cell.traffic["limits"])
    return e2e, ctx, checks, dev, n_done, n_bad


def control(cell, seed: int) -> dict:
    """The checks of the control (``campaign.control``) over this loop's
    wider sample."""
    from repro.streams import FleetRunner

    dep = cell.deployment
    policy = cell.traffic["policy"]
    kw = campaign.settings(cell.config, policy)
    corpus = dep.corpus(cell.config, seed)
    plan = FleetRunner().plan(
        [dep.program_scenario(sc).compile() for sc in corpus], policy)
    chosen = [corpus[i] for i in sample(plan, kw["chunk_rows"], seed)]
    rows = [dep.reference_row(sc, policy, kw, "high")[None]
            for sc in chosen]
    checks, _ = campaign.compare(dep, chosen, rows, policy, kw,
                                 cell.traffic["limits"])
    return checks
