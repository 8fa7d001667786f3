"""Fault-tolerant campaign tests: deterministic fault injection, chunk
retry + quarantine, bisection, checkpoint/resume, teardown correctness,
and compile-boundary input validation.

The bitwise contract throughout: every recovery path re-runs scenarios
through the SAME per-bucket executable at the SAME padded row count as
the pipeline path, and vmap rows are independent — so every row the
resilience layer touches must come out byte-identical to the fault-free
campaign. (Fault-free campaign ≡ materialized run over the 4-policy
256-scenario suite is already pinned by
tests/test_campaign.py::TestStreamingParity with the guards at their
defaults, i.e. with the resilience layer enabled.)
"""
import dataclasses
import os

import numpy as np
import pytest

from repro.net.topology import LinkSchedule
from repro.streams import (
    FailureRecord,
    FaultAbort,
    FaultPlan,
    FaultSpec,
    FleetRunner,
    InjectedFault,
    campaign_fleet,
    compile_fleet,
)

SECONDS = 6.0
DT = 0.5
CHUNK = 8
FAST = dict(retry_backoff_s=0.001, retry_backoff_cap_s=0.01)


@pytest.fixture(scope="module")
def corpus():
    """48 scenarios, mixed shapes/static/scheduled → several chunks per
    bucket at chunk_rows=8."""
    return compile_fleet(campaign_fleet(48, seed=0))


@pytest.fixture(scope="module")
def runner():
    """One shared runner: every test hits the same compiled executables
    (identical campaign parameters), so recovery re-runs are provably the
    same programs the pipeline dispatched."""
    return FleetRunner()


@pytest.fixture(scope="module")
def oracle(runner, corpus):
    """Fault-free campaign metrics — the bitwise reference."""
    cr = runner.run_campaign(corpus, "tcp", seconds=SECONDS, dt=DT,
                             chunk_rows=CHUNK)
    assert runner.last_stats["status"] == "ok"
    assert runner.last_stats["n_chunks"] >= 4
    return cr.metrics.copy()


def _campaign(runner, corpus, **kw):
    return runner.run_campaign(corpus, "tcp", seconds=SECONDS, dt=DT,
                               chunk_rows=CHUNK, **kw)


class TestFaultPlan:
    def test_spec_validation(self):
        with pytest.raises(ValueError, match="unknown fault stage"):
            FaultSpec("h2d")
        with pytest.raises(ValueError, match="times"):
            FaultSpec("pack", times=0)
        with pytest.raises(ValueError, match="hang_s"):
            FaultSpec("dispatch", hang_s=1.0)

    def test_fire_consumes_and_logs(self):
        fp = FaultPlan([FaultSpec("dispatch", chunk=3, times=2)])
        fp.fire("dispatch", 0)          # wrong chunk: no-op
        fp.fire("pack", 3)              # wrong stage: no-op
        for _ in range(2):
            with pytest.raises(InjectedFault):
                fp.fire("dispatch", 3)
        fp.fire("dispatch", 3)          # spent: no-op
        assert fp.log == [("dispatch", 3, "raise")] * 2
        assert fp.n_fired("dispatch") == 2 and fp.n_fired("pack") == 0

    def test_random_is_reproducible(self):
        a = FaultPlan.random(7, n_chunks=10, n_scenarios=100)
        b = FaultPlan.random(7, n_chunks=10, n_scenarios=100)
        assert a.specs == b.specs and a.poison == b.poison
        assert a.poison and all(0 <= i < 100 for i in a.poison)

    def test_poison_mask(self):
        fp = FaultPlan(poison={2, 5})
        np.testing.assert_array_equal(fp.poison_mask([1, 2, 3, 5]),
                                      [False, True, False, True])


class TestInjectedStages:
    """One test per injected fault stage: the campaign recovers and every
    metric row stays bitwise-identical to the fault-free run."""

    @pytest.mark.parametrize("stage", ["pack", "transfer", "dispatch"])
    def test_transient_fault_recovers_bitwise(self, runner, corpus, oracle,
                                              stage):
        fp = FaultPlan([FaultSpec(stage, chunk=1, times=1)])
        cr = _campaign(runner, corpus, faults=fp, **FAST)
        stats = runner.last_stats
        assert stats["status"] == "ok"
        assert fp.n_fired(stage) == 1
        assert stats["n_recovered_chunks"] == 1
        assert not cr.failures
        np.testing.assert_array_equal(cr.metrics, oracle)

    def test_transfer_retry_then_succeed(self, runner, corpus, oracle):
        # ×2 transient: pipeline attempt + first sync retry fail, second
        # retry succeeds — no quarantine, bitwise metrics
        fp = FaultPlan([FaultSpec("transfer", chunk=2, times=2)])
        cr = _campaign(runner, corpus, faults=fp, **FAST)
        stats = runner.last_stats
        assert stats["status"] == "ok"
        assert fp.n_fired("transfer") == 2
        assert stats["n_retries"] >= 1
        assert not cr.failures
        np.testing.assert_array_equal(cr.metrics, oracle)

    @pytest.mark.timeout_s(120)
    def test_hung_transfer_watchdog(self, runner, corpus, oracle):
        # the transfer worker sleeps past transfer_timeout_s: the watchdog
        # abandons the executor and the chunk re-runs synchronously
        fp = FaultPlan([FaultSpec("transfer", chunk=1, times=1,
                                  hang_s=5.0)])
        cr = _campaign(runner, corpus, faults=fp, transfer_timeout_s=0.25,
                       **FAST)
        stats = runner.last_stats
        assert stats["status"] == "ok"
        assert stats["n_recovered_chunks"] == 1
        assert not cr.failures
        np.testing.assert_array_equal(cr.metrics, oracle)

    def test_nan_epilogue_quarantined(self, runner, corpus, oracle):
        poisoned = 9
        fp = FaultPlan(poison={poisoned})
        cr = _campaign(runner, corpus, faults=fp, **FAST)
        assert runner.last_stats["status"] == "ok"
        assert np.isnan(cr.metrics[poisoned]).all()
        assert [f.scenario for f in cr.failures] == [poisoned]
        assert cr.failures[0].stage == "non_finite"
        np.testing.assert_array_equal(cr.quarantined, [poisoned])
        ok = np.arange(len(corpus)) != poisoned
        np.testing.assert_array_equal(cr.metrics[ok], oracle[ok])

    def test_retries_exhausted_quarantines_chunk(self, runner, corpus,
                                                 oracle):
        # permanently broken dispatch for chunk 0: retries exhaust, then
        # bisection exhausts — every scenario of that chunk quarantined
        # with the injected stage in its FailureRecord; the rest bitwise
        fp = FaultPlan([FaultSpec("dispatch", chunk=0, times=-1)])
        cr = _campaign(runner, corpus, faults=fp, max_retries=1, **FAST)
        stats = runner.last_stats
        assert stats["status"] == "ok"
        assert cr.failures and all(f.stage == "dispatch" and f.attempts > 1
                                   for f in cr.failures)
        bad = cr.quarantined
        assert len(bad) == stats["n_quarantined"] > 0
        assert np.isnan(cr.metrics[bad]).all()
        ok = np.ones(len(corpus), bool)
        ok[bad] = False
        np.testing.assert_array_equal(cr.metrics[ok], oracle[ok])


class TestBisection:
    def test_isolates_exactly_poisoned_in_mixed_chunk(self, runner, corpus,
                                                      oracle):
        # two poisoned scenarios landing in the same chunk plus one
        # elsewhere: bisection must quarantine exactly those three
        poisoned = {8, 10, 30}
        fp = FaultPlan(poison=poisoned)
        cr = _campaign(runner, corpus, faults=fp, **FAST)
        np.testing.assert_array_equal(cr.quarantined, sorted(poisoned))
        for i in poisoned:
            assert np.isnan(cr.metrics[i]).all()
        ok = np.ones(len(corpus), bool)
        ok[list(poisoned)] = False
        np.testing.assert_array_equal(cr.metrics[ok], oracle[ok])
        assert {f.scenario for f in cr.failures} == poisoned

    def test_finite_check_off_lets_nan_through(self, runner, corpus):
        # guard knob: with finite_check=False poisoned rows are recorded
        # as-is (NaN) but nothing is quarantined or re-run
        fp = FaultPlan(poison={3})
        cr = _campaign(runner, corpus, faults=fp, finite_check=False,
                       **FAST)
        assert np.isnan(cr.metrics[3]).all()
        assert not cr.failures
        assert runner.last_stats["n_recovered_chunks"] == 0


class TestAcceptance:
    """The ISSUE's headline scenario at full campaign scale."""

    def test_256_campaign_transient_plus_poison(self):
        sims = compile_fleet(campaign_fleet(256, seed=0))
        runner = FleetRunner()
        base = runner.run_campaign(sims, "tcp", seconds=SECONDS, dt=DT,
                                   chunk_rows=32)
        fp = FaultPlan([FaultSpec("transfer", times=2)], poison={100})
        cr = runner.run_campaign(sims, "tcp", seconds=SECONDS, dt=DT,
                                 chunk_rows=32, faults=fp, **FAST)
        assert runner.last_stats["status"] == "ok"
        assert fp.n_fired("transfer") == 2
        np.testing.assert_array_equal(cr.quarantined, [100])
        assert np.isnan(cr.metrics[100]).all()
        assert [f.scenario for f in cr.failures] == [100]
        ok = np.arange(256) != 100
        np.testing.assert_array_equal(cr.metrics[ok], base.metrics[ok])


class TestCheckpointResume:
    def test_kill_then_resume_bitwise(self, runner, corpus, oracle,
                                      tmp_path):
        ck = str(tmp_path / "ck")
        n_chunks = runner.last_stats["n_chunks"]
        # kill at the last chunk: by then the pipeline has collected (and
        # checkpointed) all but the ~2 chunks still in flight
        fp = FaultPlan([FaultSpec("abort", chunk=n_chunks - 1)])
        with pytest.raises(FaultAbort):
            _campaign(runner, corpus, faults=fp, checkpoint=ck)
        killed = runner.last_stats
        assert killed["status"] == "failed"
        assert "FaultAbort" in killed["error"]
        assert 0 < killed["n_chunks_done"] < n_chunks
        done = killed["n_chunks_done"]
        # resume: completed chunks restore bitwise without re-dispatching
        cr = _campaign(runner, corpus, checkpoint=ck)
        stats = runner.last_stats
        assert stats["status"] == "ok"
        assert stats["n_chunks_resumed"] == done
        assert stats["n_dispatches"] == n_chunks - done < n_chunks
        np.testing.assert_array_equal(cr.metrics, oracle)

    def test_completed_campaign_resumes_with_zero_dispatches(
            self, runner, corpus, oracle, tmp_path):
        ck = str(tmp_path / "ck")
        cr1 = _campaign(runner, corpus, checkpoint=ck)
        assert runner.last_stats["n_dispatches"] > 0
        cr2 = _campaign(runner, corpus, checkpoint=ck)
        stats = runner.last_stats
        assert stats["n_dispatches"] == 0
        assert stats["n_chunks_resumed"] == stats["n_chunks"]
        np.testing.assert_array_equal(cr2.metrics, cr1.metrics)
        np.testing.assert_array_equal(cr2.metrics, oracle)

    def test_failures_survive_resume(self, runner, corpus, tmp_path):
        ck = str(tmp_path / "ck")
        fp = FaultPlan(poison={5})
        cr1 = _campaign(runner, corpus, faults=fp, checkpoint=ck, **FAST)
        assert cr1.quarantined.tolist() == [5]
        cr2 = _campaign(runner, corpus, checkpoint=ck)
        assert runner.last_stats["n_dispatches"] == 0
        assert [f.scenario for f in cr2.failures] == [5]
        assert isinstance(cr2.failures[0], FailureRecord)
        np.testing.assert_array_equal(cr2.metrics, cr1.metrics)

    def test_fingerprint_mismatch_ignores_checkpoint(self, runner, corpus,
                                                     tmp_path):
        ck = str(tmp_path / "ck")
        _campaign(runner, corpus, checkpoint=ck)
        # different policy ⇒ different fingerprint ⇒ full re-run
        runner.run_campaign(corpus, "appaware", seconds=SECONDS, dt=DT,
                            chunk_rows=CHUNK, checkpoint=ck)
        stats = runner.last_stats
        assert stats["n_chunks_resumed"] == 0
        assert stats["n_dispatches"] == stats["n_chunks"]
        # checkpoint dir now serves both campaigns, keyed by fingerprint
        names = os.listdir(ck)
        assert sum(n.endswith(".npy") for n in names) == 2 * stats["n_chunks"]

    def test_checkpoint_rejects_trajectories(self, runner, corpus,
                                             tmp_path):
        with pytest.raises(ValueError, match="retain_trajectories"):
            _campaign(runner, corpus, checkpoint=str(tmp_path / "ck"),
                      retain_trajectories=True)


class TestTeardown:
    """Satellite: failure-aware `last_stats` + clean pipeline reset."""

    def test_failed_stats_regression(self, runner, corpus, oracle):
        sentinel = {"marker": "previous run"}
        runner.last_stats = sentinel
        fp = FaultPlan([FaultSpec("abort", chunk=2)])
        with pytest.raises(FaultAbort):
            _campaign(runner, corpus, faults=fp)
        stats = runner.last_stats
        assert stats is not sentinel, "failed run left stale last_stats"
        assert stats["mode"] == "campaign"
        assert stats["status"] == "failed"
        assert "FaultAbort" in stats["error"]
        assert stats["n_chunks_done"] < stats["n_chunks"]
        # per-run pipeline state was reset: the very next campaign is
        # clean and bitwise-correct on the same runner
        assert not runner._campaign_bufs
        cr = _campaign(runner, corpus)
        assert runner.last_stats["status"] == "ok"
        assert runner.last_stats["error"] is None
        np.testing.assert_array_equal(cr.metrics, oracle)

    def test_fault_free_stats_report_ok(self, runner, corpus):
        _campaign(runner, corpus)
        stats = runner.last_stats
        assert stats["status"] == "ok" and stats["error"] is None
        assert stats["n_chunks_done"] == stats["n_chunks"]
        assert stats["n_dispatches"] == stats["n_chunks"]
        assert stats["n_retries"] == 0 == stats["n_quarantined"]
        assert sum(stats["chunks_per_device"].values()) == stats["n_chunks"]

    def test_device_error_propagates_unquarantined(self, runner, corpus,
                                                   oracle, monkeypatch):
        # a compile error or exhausted device is not the scenarios' fault:
        # it surfaces as-is instead of being retried into quarantined rows
        import jax

        def exhausted(*a, **kw):
            raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: injected")

        with monkeypatch.context() as mp:
            mp.setattr(jax, "device_put", exhausted)
            with pytest.raises(jax.errors.JaxRuntimeError,
                               match="RESOURCE_EXHAUSTED"):
                _campaign(runner, corpus)
        stats = runner.last_stats
        assert stats["status"] == "failed"
        assert stats["n_retries"] == 0 == stats["n_quarantined"]
        np.testing.assert_array_equal(_campaign(runner, corpus).metrics,
                                      oracle)


class TestInputValidation:
    """Satellite: compile_sim / pad_sim reject poisoned fields by name."""

    @staticmethod
    def _scenario():
        return campaign_fleet(6, seed=0)[0]

    def test_nan_capacity_rejected(self):
        scn = self._scenario()
        scn.topo.links[0] = dataclasses.replace(scn.topo.links[0],
                                                capacity=np.nan)
        with pytest.raises(ValueError, match="capacities"):
            scn.compile()

    def test_negative_capacity_rejected(self):
        scn = self._scenario()
        scn.topo.links[0] = dataclasses.replace(scn.topo.links[0],
                                                capacity=-5.0)
        with pytest.raises(ValueError, match="capacities"):
            scn.compile()

    def test_nan_demand_rejected(self):
        scn = self._scenario()
        scn.graph.gen_rate[0] = np.nan
        with pytest.raises(ValueError, match="gen_rate"):
            scn.compile()

    def test_negative_demand_rejected(self):
        scn = self._scenario()
        scn.graph.gen_rate[0] = -1.0
        with pytest.raises(ValueError, match="gen_rate"):
            scn.compile()

    def test_nan_proc_rate_rejected_inf_allowed(self):
        scn = self._scenario()
        scn.graph.proc_rate[0] = np.inf   # load-bearing: "unbounded"
        scn.compile()
        scn.graph.proc_rate[0] = np.nan
        with pytest.raises(ValueError, match="proc_rate"):
            scn.compile()

    @pytest.mark.parametrize("field", ["ev_t0", "ev_t1"])
    def test_bad_event_times_rejected_inf_allowed(self, field):
        scn = self._scenario()
        sch = LinkSchedule.empty(scn.topo.n_links).with_event(
            0, t0=5.0, t1=np.inf, scale=0.5)  # inf t1 = permanent: fine
        scn = dataclasses.replace(scn, schedule=sch)
        scn.compile()
        for bad in (np.nan, -1.0):
            broken = dataclasses.replace(
                sch, **{field: np.array([bad], np.float32)})
            with pytest.raises(ValueError, match=field):
                dataclasses.replace(scn, schedule=broken).compile()

    def test_bad_event_scale_rejected(self):
        scn = self._scenario()
        sch = LinkSchedule.empty(scn.topo.n_links).with_event(
            0, t0=5.0, scale=0.5)
        for bad in (np.nan, np.inf, -0.5):
            broken = dataclasses.replace(
                sch, ev_scale=np.array([bad], np.float32))
            with pytest.raises(ValueError, match="ev_scale"):
                dataclasses.replace(scn, schedule=broken).compile()

    def test_pad_sim_rejects_poisoned_compiled_fields(self, corpus):
        from repro.streams import FleetShape, pad_sim
        sim = corpus[0]
        shape = FleetShape.cover([sim])
        bad_caps = np.asarray(sim.caps).copy()
        bad_caps[0] = np.nan
        with pytest.raises(ValueError, match="caps"):
            pad_sim(dataclasses.replace(sim, caps=bad_caps), shape)
        # a *dynamic* member has events to poison
        dyn = next(s for s in corpus if np.asarray(s.ev_t0).size)
        bad_ev = np.asarray(dyn.ev_t0).copy()
        bad_ev[0] = -2.0
        with pytest.raises(ValueError, match="ev_t0"):
            pad_sim(dataclasses.replace(dyn, ev_t0=bad_ev),
                    FleetShape.cover([dyn]))
