"""The ``nexmark_campaign`` deployment (NEXmark Q5 under SINE or SQUARE
input on the paper's testbed): the program agrees with the deployment's
plain reference, load schedule included, on seeded scenarios under tcp
and appaware; the reference's per-tick copy is ``reference.simulate_ref``
when the load is constant; and a program that ignores the schedule fails
the cell's row-gap limits."""
import json
import time

import jax
import numpy as np
import pytest

import run
from benchlib import reference, spec
from fault_run import faults

SEED = 2**35 + 11
NEX = spec.load_deployment("nexmark_campaign")
TESTBED = spec.load_deployment("testbed_campaign")
campaign = spec.load_loop("campaign")
CELL = spec.resolve("nexmark.q5-appaware")


def _cfg(**kw):
    return dict(CELL.config, **kw)


def test_corpus_1024_scenarios_seeded_jitter_fixed_sizes():
    cfg = CELL.config
    a = NEX.corpus(cfg, SEED)
    assert len(a) == 1024
    assert [sc.shape for sc in a[:4]] == ["SINE", "SQUARE"] * 2
    assert [sc.cap for sc in a[:6]] == [1.25, 1.25, 1.875, 1.875, 2.5, 2.5]
    assert {(sc.graph.n_instances, sc.graph.n_flows) for sc in a} == {
        (26, 137)}
    ld = cfg["load"]
    r = np.array([sc.ratio for sc in a])
    p = np.array([sc.period_s for sc in a])
    assert ld["next_over_first"][0] <= r.min() and r.max() <= \
        ld["next_over_first"][1]
    assert ld["period_s"][0] <= p.min() and p.max() <= ld["period_s"][1]
    for sc in a:
        first = ld["first_events_per_s"] * 126e-6
        base = first * (1 + sc.ratio) / 2 if sc.shape == "SINE" else first
        np.testing.assert_allclose(sc.graph.gen_rate.sum(), base)
        if sc.shape == "SQUARE":
            assert len(sc.steps) == NEX.step_slots(cfg) == 11
            on = [st for st in sc.steps if st[0] < cfg["horizon_s"]]
            assert on and all(st == (np.inf, np.inf)
                              for st in sc.steps[len(on):])
            assert all(t1 - t0 <= sc.period_s / 2 + 1e-9 for t0, t1 in on)
            assert all(b[0] - a_[0] == pytest.approx(sc.period_s)
                       for a_, b in zip(on[1:], on[2:]))
        else:
            assert sc.steps == []
    b = NEX.corpus(_cfg(n_scenarios=8), SEED)
    c = NEX.corpus(_cfg(n_scenarios=8), 7)
    assert [x.ratio for x in b] == [x.ratio for x in a[:8]]
    assert [x.ratio for x in b] != [x.ratio for x in c]


def test_square_steps_alternate_half_periods():
    steps = NEX.square_steps(100.0, 0.0, 600.0)
    assert steps == [(50.0 + 100.0 * k, 100.0 + 100.0 * k) for k in range(6)]
    steps = NEX.square_steps(100.0, np.pi, 250.0)       # half a period on
    assert steps == [(0.0, 50.0), (100.0, 150.0), (200.0, 250.0)]


def test_campaign_plan_is_fixed_by_the_configuration():
    """Every SQUARE scenario has the same step slots, so the 1024
    scenarios make two shapes, two buckets of 512 and 16 chunks of 64
    rows, whatever the seed draws."""
    from repro.streams import FleetRunner

    for seed in (SEED, 7):
        sims = [NEX.program_scenario(sc).compile()
                for sc in NEX.corpus(CELL.config, seed)]
        plan = FleetRunner().plan(sims, "appaware")
        assert sorted((len(i), s.n_load_sins, s.n_load_steps)
                      for i, s in plan) == [(512, 0, 11), (512, 1, 0)]
        assert len(campaign.chunks(plan, CELL.config["chunk_rows"])) == 16


def test_wide_sample_takes_its_share_of_every_chunk():
    """The cell's loop checks ``PER_CHUNK`` distinct scenarios of every
    chunk, all of a smaller one, the same for the same seed."""
    wide = spec.load_loop(CELL.traffic["kind"])
    plan = [(list(range(0, 20, 2)), None), (list(range(1, 7, 2)), None)]
    chunks = campaign.chunks(plan, 4)      # 4, 4 and 2 rows; then 3
    got = wide.sample(plan, 4, SEED, 2)
    assert got == wide.sample(plan, 4, SEED, 2) != wide.sample(plan, 4, 7, 2)
    assert len(got) == len(set(got)) == 2 * len(chunks)
    assert all(len(set(got) & set(c)) == 2 for c in chunks)
    assert wide.sample(plan, 4, SEED, 5) == sorted(i for c in chunks
                                                   for i in c)
    assert len(wide.sample(plan, 4, SEED)) == sum(
        min(wide.PER_CHUNK, len(c)) for c in chunks)


def test_program_scenario_carries_the_load():
    """The program's compiled rate grid is the reference's own grid,
    built from the scenario's parameters."""
    import jax.numpy as jnp

    from repro.streams.simulator import _load_over

    ts = np.arange(1200, dtype=np.float32) * np.float32(0.5)
    for sc in NEX.corpus(_cfg(n_scenarios=6), SEED):
        sim = NEX.program_scenario(sc).compile()
        assert sim.is_loaded and not sim.is_dynamic
        s = reference.testbed_arrays(sc.graph, sc.placement, sc.n_machines,
                                     sc.cap)
        want = NEX.gen_grid(sc, s, ts, reference.Arith("exact"))
        got = np.asarray(_load_over(sim, jnp.asarray(ts)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=0)


@pytest.mark.parametrize("policy", ["tcp", "appaware"])
def test_program_matches_the_deployments_reference(policy):
    """Eight seeded scenarios, SINE and SQUARE at every capacity, 90 s,
    through the program's campaign path: every row within the cell's
    row-gap limits of the reference's run of the same scenario."""
    from repro.streams import FleetRunner

    cfg = _cfg(n_scenarios=8, horizon_s=90.0)
    kw = campaign.settings(cfg, policy)
    corpus = NEX.corpus(cfg, SEED)
    assert {sc.shape for sc in corpus} == {"SINE", "SQUARE"}
    sims = [NEX.program_scenario(sc).compile() for sc in corpus]
    cr = FleetRunner().run_campaign(sims, policy, **kw)
    gaps = np.array([
        campaign.row_gap(cr.metrics[i][None],
                         NEX.reference_row(sc, policy, kw), kw["seconds"])[0]
        for i, sc in enumerate(corpus)])
    lim = CELL.traffic["limits"]
    assert gaps.max() < lim["row_gap_max"], gaps
    assert np.median(gaps) < lim["row_gap_median"], gaps


@pytest.mark.parametrize("policy", ["tcp", "appaware"])
@pytest.mark.parametrize("precision", ["exact", "high"])
def test_reference_copy_is_the_original_at_constant_load(policy, precision):
    """With every tick's generation rate the constant one, the
    deployment's ``simulate_ref`` is ``reference.simulate_ref`` bitwise,
    on testbed scenarios static, failing and cycling."""
    cfg = dict(spec.resolve("testbed.campaign-tcp").config, n_scenarios=18)
    ar = reference.Arith(precision)
    n, dt = 40, 0.5
    for sc in TESTBED.corpus(cfg, SEED)[::4]:
        s = reference.testbed_arrays(sc.graph, sc.placement, sc.n_machines,
                                     sc.cap, sc.events, sc.diurnal)
        gen = np.broadcast_to(ar.f(s["gen_rate"]), (n, len(s["gen_rate"])))
        a = NEX.simulate_ref(s, gen, policy, n, dt, 10, 8.0, ar)
        b = reference.simulate_ref(s, policy, n, dt, 10, 8.0, ar)
        np.testing.assert_array_equal(a, b)


@pytest.fixture
def ignored_load(monkeypatch):
    """The program with its load schedule ignored: every tick generates
    the constant ``gen_rate``."""
    import jax.numpy as jnp

    from repro.streams import simulator

    def constant(sim, ts):
        return jnp.broadcast_to(sim.gen_rate[None, :],
                                (ts.shape[0], sim.gen_rate.shape[0]))

    monkeypatch.setattr(simulator, "_load_over", constant)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_ignoring_the_load_fails_the_limits(ignored_load, monkeypatch,
                                            capsys):
    monkeypatch.setattr(run, "setup_jax", lambda root: jax)
    hooks = {"require_chip": False, **faults("campaign").SMALL}
    rc = run.main(["--workload", "nexmark.q5-appaware", "--seed",
                   str(SEED), "--seconds", "0.5"], hooks=hooks,
                  t_start=time.perf_counter())
    out, err = capsys.readouterr()
    assert rc == 0, err[-3000:]
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is False
    for ch in res["checks"].values():
        assert ch["value"] > ch["limit"], res["checks"]
