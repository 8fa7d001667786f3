"""Runs campaign cells: a closed loop of whole streaming campaigns
(``FleetRunner.run_campaign``) over the configuration's compiled corpus.

The window ends when the campaign running at ``--seconds`` completes;
the rate is every scenario completed over the time from the window's
start to that end.

What it calls of the cell's deployment module: ``corpus(cfg, seed)``, a
list of scenarios, each with a ``name``; ``program_scenario(sc)``, the
program's ``Scenario`` of one; ``reference_row(sc, policy, kw,
precision="exact")``, the plain reference's metric row of one
([7], ``reference.METRICS``), with ``kw`` from :func:`settings` and
``precision`` "exact" or "high" (``reference.Arith``)."""
from __future__ import annotations

import dataclasses

import numpy as np

from benchlib import reference
from benchlib.harness import (CompileWatch, GuardError, TracedWindow,
                              check, device_record, log, now, span)

LAYERS = ("campaign pipeline", "scan program", "device")
SPANS = ("stage_s", "transfer_s", "transfer_wait_s", "dispatch_s", "block_s")


def _guard(st: dict, cr) -> None:
    """A campaign whose numbers would measure something else: not every
    planned chunk dispatched, a chunk resumed, retried or quarantined."""
    bad = []
    if st["status"] != "ok":
        bad.append(f"status {st['status']} ({st['error']})")
    if st["n_dispatches"] < st["n_chunks"]:
        bad.append(f"{st['n_dispatches']} dispatches for {st['n_chunks']} "
                   f"planned chunks")
    if st["n_chunks_resumed"]:
        bad.append(f"{st['n_chunks_resumed']} chunks resumed")
    if st["n_retries"] or st["n_quarantined"] or cr.failures:
        bad.append(f"{st['n_retries']} retries, {st['n_quarantined']} "
                   f"quarantined")
    if bad:
        raise GuardError("campaign: " + "; ".join(bad))


def settings(cfg: dict, policy: str) -> dict:
    dt = float(cfg["dt_s"])
    upd = (int(round(float(cfg["controller_interval_s"]) / dt))
           if policy in ("appaware", "appfair") else 1)
    # every cell runs on one chip: its chunks go to one device
    return {"seconds": float(cfg["horizon_s"]), "dt": dt, "upd_every": upd,
            "qcap": float(cfg["qcap_mb"]),
            "chunk_rows": int(cfg["chunk_rows"]), "shard": False}


def run(cell, args, t_start: float, devices, hooks: dict) -> tuple:
    from repro.streams import FleetRunner

    cfg, tr, dep = cell.config, cell.traffic, cell.deployment
    policy = tr["policy"]
    kw = settings(cfg, policy)
    corpus = dep.corpus(cfg, args.seed)
    sims = [dep.program_scenario(sc).compile() for sc in corpus]
    runner = FleetRunner()
    campaign = hooks.get("wrap_campaign", lambda f: f)(runner.run_campaign)
    plan = runner.plan(sims, policy)
    log(f"campaign: {len(sims)} scenarios, policy {policy}, "
        f"{kw['seconds']:g} s horizon, dt {kw['dt']} s, chunk_rows "
        f"{kw['chunk_rows']} on {len(devices)} device(s); plan "
        f"{[(len(i), dataclasses.astuple(s)) for i, s in plan]}"
        f"; tick_overhead {runner.tick_overhead!r}")
    warm = campaign(sims, policy, **kw)     # compiles, first staging
    st = runner.last_stats
    _guard(st, warm)
    log(f"warm-up campaign: {st['wall_s']:.3f} s, rows {st['rows']}, "
        f"{st['n_chunks']} chunks, per device {st['chunks_per_device']}, "
        f"stage {st['stage_s']:.3f} s; calibration {st['calibration']}")
    watch = CompileWatch()
    compiles0, execs0 = watch.count, runner.compile_cache_size()
    setup_s = now() - t_start

    results = []
    spans = dict.fromkeys(SPANS, 0.0)
    n_chunks = 0
    # a traced run traces the window's first campaign only: collecting the
    # device trace of one campaign takes about two minutes on a TPU v5e,
    # so a trace of the whole window would not end within a run's time
    traced = TracedWindow(args.trace)
    w0 = now()
    while True:
        with span("bench.campaign", traced.on):
            cr = campaign(sims, policy, **kw)
        traced.stop()
        st = runner.last_stats
        _guard(st, cr)
        results.append(cr.metrics)
        for k in SPANS:
            spans[k] += st[k]
        n_chunks += st["n_chunks"]
        if now() - w0 >= args.seconds:
            break
    w1 = now()
    if watch.count != compiles0 or runner.compile_cache_size() != execs0:
        raise GuardError(f"{watch.count - compiles0} compilation(s) inside "
                         f"the window")
    trace = traced.reduce() if args.trace else None
    dev = device_record(devices, trace)

    n_done = len(results) * len(sims)
    e2e = {"setup_s": setup_s, "campaign_scen_per_s": n_done / (w1 - w0)}
    log(f"window: {len(results)} campaigns, {n_done} scenarios in "
        f"{w1 - w0:.3f} s; spans {spans}, {n_chunks} chunks")
    ctx = {"trace": trace, "pipeline": dict(spans, n_chunks=n_chunks),
           "n_scenarios": n_done, "n_traced": len(sims),
           "device_kind": devices[0].device_kind}

    idx = sample(plan, kw["chunk_rows"], args.seed)
    checks, n_bad = compare(dep, [corpus[i] for i in idx],
                            [np.stack([m[i] for m in results]) for i in idx],
                            policy, kw, tr["limits"])
    return e2e, ctx, checks, dev, n_done, n_bad


def control(cell, seed: int) -> dict:
    """The checks of the control: the plain reference at ``high`` put in
    the program's place for the cell's own sample, one scenario of every
    chunk of the plan this machine's planner makes for the corpus."""
    from repro.streams import FleetRunner

    dep = cell.deployment
    policy = cell.traffic["policy"]
    kw = settings(cell.config, policy)
    corpus = dep.corpus(cell.config, seed)
    plan = FleetRunner().plan(
        [dep.program_scenario(sc).compile() for sc in corpus], policy)
    chosen = [corpus[i] for i in sample(plan, kw["chunk_rows"], seed)]
    rows = [dep.reference_row(sc, policy, kw, "high")[None]
            for sc in chosen]
    checks, _ = compare(dep, chosen, rows, policy, kw, cell.traffic["limits"])
    return checks


def chunks(plan, chunk_rows: int) -> list[list[int]]:
    """The chunks a campaign dispatches: each bucket of the plan split
    into ceil(members / chunk_rows) near-equal runs of its members."""
    out = []
    for idxs, _shape in plan:
        n = -(-len(idxs) // chunk_rows)
        per = -(-len(idxs) // n)
        out += [list(idxs[lo:lo + per]) for lo in range(0, len(idxs), per)]
    return out


def sample(plan, chunk_rows: int, seed: int) -> list[int]:
    """One scenario of every chunk, drawn from the seed: every bucket,
    every chunk and, over seeds, every row position of a chunk."""
    rng = np.random.default_rng([seed, 2])
    return sorted(int(rng.choice(c)) for c in chunks(plan, chunk_rows))


# columns compared as a gap relative to the reference's value; the dip
# depth, a share in [0, 1], as an absolute gap; the settling time as its
# absolute gap over the horizon
_REL = ("avg_tput_mb_s", "final_tput_mb_s", "avg_latency_s", "utilization",
        "total_sink_mb")
_REL_FLOOR = 1e-6
_DIP = reference.METRICS.index("dip_depth")
_REC = reference.METRICS.index("recovery_time_s")


def column_gaps(rows: np.ndarray, ref: np.ndarray, horizon_s: float
                ) -> np.ndarray:
    """Gap of every metric of each row ([n, 7]) from the reference row; a
    broken entry (NaN, or an infinity other than a settling time that
    never comes) reads inf."""
    rows = np.asarray(rows, np.float64)
    g = np.empty(rows.shape)
    cols = [reference.METRICS.index(c) for c in _REL]
    g[:, cols] = (np.abs(rows[:, cols] - ref[cols])
                  / np.maximum(np.abs(ref[cols]), _REL_FLOOR))
    g[:, _DIP] = np.abs(rows[:, _DIP] - ref[_DIP])
    both_inf = np.isinf(rows[:, _REC]) & np.isinf(ref[_REC])
    g[:, _REC] = np.where(both_inf, 0.0,
                          np.abs(rows[:, _REC] - ref[_REC]) / horizon_s)
    bad = ~np.isfinite(rows)
    bad[:, _REC] = np.isnan(rows[:, _REC])
    return np.where(bad | np.isnan(g), np.inf, g)


def row_gap(rows: np.ndarray, ref: np.ndarray, horizon_s: float
            ) -> np.ndarray:
    """Gap of each metric row from the reference row: its widest column."""
    return column_gaps(rows, ref, horizon_s).max(axis=1)


def compare(dep, scenarios: list, rows: list, policy: str, kw: dict,
            limits: dict):
    """The sampled scenarios' rows from every window campaign against the
    reference run of each scenario (the deployment ``dep``'s
    ``reference_row``), built from the scenario's own parameters. Two
    numbers: the widest gap of any sampled row in any campaign, which a
    broken, misplaced or altered row moves; and the median over the
    sampled scenarios of each one's widest gap, which a precision step
    down moves (float32 rounding alone already takes a few scenarios'
    widest gap as far as the step down does)."""
    worst, cols = [], []
    for sc, r in zip(scenarios, rows):
        g = column_gaps(r, dep.reference_row(sc, policy, kw), kw["seconds"])
        worst.append(float(g.max()))
        cols.append(g.max(axis=0))
    worst = np.asarray(worst)
    n_bad = int(np.sum(~np.isfinite(worst)))
    j = int(np.argmax(worst))
    log(f"compared {len(rows)} sampled scenarios x {len(rows[0])} "
        f"campaign(s) against the reference: widest gap per scenario "
        f"{np.array2string(worst, precision=3)}; widest in "
        f"{scenarios[j].name}, by column "
        f"{dict(zip(reference.METRICS, cols[j].tolist()))}")
    return {"row_gap_max": check(float(worst.max()),
                                 limits["row_gap_max"]),
            "row_gap_median": check(float(np.median(worst)),
                                    limits["row_gap_median"])}, n_bad
