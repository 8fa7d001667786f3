"""Packed single-dispatch fleet runtime (PR 5).

The contract under test: a warm fleet run is ONE fused executable — every
bucket of the plan lives inside the same XLA program — and fusing the
dispatches changes *nothing*: results are bitwise-identical to dispatching
each bucket as its own executable (``fused=False``), for every policy, on
the canonical 44-scenario corpus (static and in-run-scheduled scenarios
mixed, including brute-force ``x_fixed`` studies whose rate vectors are
deliberately link-infeasible — the per-scenario enforcement mask keeps
their static members exactly on the static path). Plus the cache-isolation
and capacity-growth properties of the per-instance runner."""
import numpy as np
import pytest

from repro.net import big_switch, link_failure_schedule
from repro.streams import (
    FleetRunner,
    bench_fleet,
    compile_fleet,
    compile_sim,
    parallelize,
    round_robin,
    simulate,
    trending_topics,
)

SECONDS = 20.0
DT = 0.5


@pytest.fixture(scope="module")
def corpus():
    sims = compile_fleet(bench_fleet(seed=0))
    assert len(sims) == 44
    return sims


@pytest.fixture(scope="module")
def corpus_xf(corpus):
    # deliberately arbitrary (link-infeasible) brute-force rate vectors:
    # the regime the paper's motivation study sweeps, and the hard case
    # for packing static scenarios next to scheduled ones
    rng = np.random.default_rng(7)
    return [rng.uniform(0.2, 3.0, s.R.shape[0]).astype(np.float32)
            for s in corpus]


def _result_arrays(r):
    out = [r.sink_mb, r.sink_mb_app, r.latency, r.link_load]
    if r.caps_t is not None:
        out.append(r.caps_t)
    return out


class TestPackedVsPerBucketParity:
    """Fusing every bucket into one executable is a pure dispatch change:
    bitwise-identical SimResults, one kernel dispatch per run."""

    @pytest.mark.parametrize("policy", ["tcp", "appaware", "appfair",
                                        "fixed"])
    def test_bitwise_identical_on_corpus(self, corpus, corpus_xf, policy):
        kw = dict(x_fixed=corpus_xf) if policy == "fixed" else {}
        packed = FleetRunner(fused=True)
        per_bucket = FleetRunner(fused=False)
        a = packed.run(corpus, policy, seconds=SECONDS, dt=DT, **kw)
        b = per_bucket.run(corpus, policy, seconds=SECONDS, dt=DT, **kw)
        # one fused dispatch per device used (one on a single device)
        assert (packed.last_stats["n_dispatches"]
                == packed.last_stats["n_shards"])
        assert (per_bucket.last_stats["n_dispatches"]
                == per_bucket.last_stats["n_buckets"])
        for ra, rb in zip(a, b):
            for x, y in zip(_result_arrays(ra), _result_arrays(rb)):
                np.testing.assert_array_equal(x, y)
            assert np.isfinite(ra.sink_mb).all()
            assert np.isfinite(ra.latency).all()

    def test_packed_matches_per_scenario_simulate(self, corpus):
        # end-to-end parity against the unpadded single-scenario path
        # (padding re-associates some XLA reductions, so this is the
        # element-wise tolerance contract, not the bitwise one)
        runner = FleetRunner(fused=True)
        batch = runner.run(corpus[:8], "tcp", seconds=SECONDS, dt=DT)
        for sim, rb in zip(corpus[:8], batch):
            ref = simulate(sim, "tcp", seconds=SECONDS, dt=DT)
            np.testing.assert_allclose(rb.sink_mb, ref.sink_mb, atol=1e-4)
            np.testing.assert_allclose(rb.latency, ref.latency,
                                       rtol=1e-4, atol=1e-3)


class TestSingleDispatch:
    def test_heterogeneous_apps_still_one_dispatch(self):
        # appfair buckets by exact app count — but every bucket lives in
        # the same executable, so mixed-app fleets are still one dispatch
        def two_app(n_apps, cap):
            g = parallelize(trending_topics(), seed=0)
            app_of_inst = (np.arange(g.n_instances) % n_apps).astype(
                np.int32)
            return compile_sim(g, big_switch(8, cap), round_robin(g, 8),
                               app_of_inst=app_of_inst, n_apps=n_apps)

        sims = [two_app(2, 1.25), two_app(3, 1.875), two_app(2, 2.5)]
        runner = FleetRunner(fused=True)
        batch = runner.run(sims, "appfair", seconds=SECONDS, dt=DT,
                           shard=False)
        assert runner.last_stats["n_dispatches"] == 1
        assert runner.last_stats["n_buckets"] == 2  # one per app count
        for sim, rb in zip(sims, batch):
            ref = simulate(sim, "appfair", seconds=SECONDS, dt=DT)
            np.testing.assert_allclose(rb.sink_mb, ref.sink_mb, atol=1e-4)

    def test_overhead_aware_planner_collapses_cheap_ticks(self, corpus):
        # no solver in the scan -> per-bucket tick overhead dominates any
        # padded-FLOP waste and the planner merges below the cap; the
        # solver-heavy tcp fleet keeps tighter buckets under the same cap
        runner = FleetRunner(fused=True)
        fixed_plan = runner.plan(corpus, "fixed")
        tcp_plan = runner.plan(corpus, "tcp")
        assert len(fixed_plan) < len(tcp_plan) <= runner.max_buckets


class TestDevicePlacement:
    """``run(shard=True)`` spreads whole buckets over the devices; the
    assignment is pure host logic, checked here for any device count."""

    @pytest.mark.parametrize("n_dev", [1, 2, 3, 4, 8])
    def test_whole_buckets_spread_deterministically(self, corpus, n_dev):
        from repro.streams.fleet import (
            _assign_devices,
            _flop_cost,
            _round_rows,
        )
        runner = FleetRunner()
        plan = runner.plan(corpus, "tcp")
        rows = [_round_rows(len(idxs)) for idxs, _ in plan]
        owner = _assign_devices(plan, rows, n_dev, "tcp",
                                runner.tick_overhead)
        assert owner == _assign_devices(plan, rows, n_dev, "tcp",
                                        runner.tick_overhead)
        assert len(owner) == len(plan)
        assert all(0 <= d < n_dev for d in owner)
        assert len(set(owner)) == min(n_dev, len(plan))
        # longest first: the costliest bucket opens device 0
        cost = [r * _flop_cost(s, "tcp") for (_, s), r in zip(plan, rows)]
        assert owner[int(np.argmax(cost))] == 0

    def test_rows_do_not_depend_on_device_count(self):
        from repro.streams.fleet import _round_rows
        assert [_round_rows(n) for n in (1, 2, 3, 15, 16, 17, 21)] == [
            1, 2, 3, 15, 16, 20, 24]


class TestEnforcementMask:
    """A static scenario with a deliberately link-infeasible x_fixed keeps
    its exact static semantics when packed next to a scheduled scenario —
    the per-scenario enforcement gate, which replaced PR 3's split_sched
    bucketing carve-out."""

    def _static_and_scheduled(self):
        g = parallelize(trending_topics(), seed=0)
        topo = big_switch(8, 1.25)
        static = compile_sim(g, topo, round_robin(g, 8))
        sched = link_failure_schedule(topo, [0, 1], 5.0, 10.0, degrade=0.1)
        dyn = compile_sim(g, topo, round_robin(g, 8), schedule=sched)
        return g, static, dyn

    def test_infeasible_fixed_static_exact_in_scheduled_bucket(self):
        g, static, dyn = self._static_and_scheduled()
        # 10x the per-link capacity: grossly infeasible on purpose
        x = np.full(g.n_flows, 12.5, np.float32)
        runner = FleetRunner(fused=True)
        batch = runner.run([static, dyn], "fixed", seconds=SECONDS, dt=DT,
                           x_fixed=[x, x])
        assert runner.last_stats["n_buckets"] == 1  # they DO share a bucket
        ref = simulate(static, "fixed", seconds=SECONDS, dt=DT, x_fixed=x)
        np.testing.assert_allclose(batch[0].sink_mb, ref.sink_mb, atol=1e-5)
        np.testing.assert_allclose(batch[0].link_load, ref.link_load,
                                   atol=1e-5)
        # ... while the scheduled member's network DOES enforce caps(t)
        ref_dyn = simulate(dyn, "fixed", seconds=SECONDS, dt=DT, x_fixed=x)
        np.testing.assert_allclose(batch[1].sink_mb, ref_dyn.sink_mb,
                                   atol=1e-5)
        np.testing.assert_allclose(batch[1].caps_t, ref_dyn.caps_t,
                                   atol=1e-6)


class TestPerRunnerCaches:
    """Regression for the PR 4 @staticmethod-over-global-state cache:
    executable and plan caches are per-instance, so two runners with
    different knobs cannot poison each other's entries or assertions."""

    def test_compile_cache_isolated_between_runners(self, corpus):
        a = FleetRunner(max_buckets=2)
        a.run(corpus[:4], "tcp", seconds=5.0, dt=DT)
        size_a = a.compile_cache_size()
        assert size_a > 0
        # a second runner with a different plan compiles its own programs
        b = FleetRunner(max_buckets=1)
        assert b.compile_cache_size() == 0
        b.run(corpus[:4], "tcp", seconds=5.0, dt=DT)
        assert b.compile_cache_size() > 0
        # ... and none of them leaked into runner a's count
        assert a.compile_cache_size() == size_a
        out = a.run(corpus[:4], "tcp", seconds=5.0, dt=DT)
        assert a.compile_cache_size() == size_a  # still no recompile
        assert all(r is not None for r in out)

    def test_plan_cache_isolated_between_runners(self, corpus):
        a = FleetRunner(max_buckets=4, tick_overhead=0.0)
        b = FleetRunner(max_buckets=1)
        plan_a = a.plan(corpus, "tcp")
        plan_b = b.plan(corpus, "tcp")
        assert len(plan_a) == 4 and len(plan_b) == 1
        # re-planning returns each runner's own cached plan, unchanged
        assert a.plan(corpus, "tcp") is plan_a
        assert b.plan(corpus, "tcp") is plan_b


class TestCapacityGrowth:
    """Bucket rows are rounded up to a small capacity quantum: a fleet
    that grows only in scenario count within the padded capacity reuses
    its compiled executable (the spare rows were inert scenarios)."""

    def _fleet(self, n):
        g = parallelize(trending_topics(), seed=0)
        return [compile_sim(g, big_switch(8, 1.0 + 0.05 * i),
                            round_robin(g, 8)) for i in range(n)]

    def test_growth_within_capacity_reuses_executable(self):
        sims = self._fleet(18)            # rows round to 20: headroom 2
        runner = FleetRunner(fused=True)
        out = runner.run(sims, "tcp", seconds=10.0, dt=DT)
        assert runner.last_stats["rows"] == [20]
        size = runner.compile_cache_size()
        grown = sims + self._fleet(20)[18:]   # +2 scenarios, same shape
        out2 = runner.run(grown, "tcp", seconds=10.0, dt=DT)
        assert runner.last_stats["rows"] == [20]
        assert runner.compile_cache_size() == size  # no recompile
        # prefix results identical, new members correct
        for a, b in zip(out, out2[:18]):
            np.testing.assert_array_equal(a.sink_mb, b.sink_mb)
        ref = simulate(grown[-1], "tcp", seconds=10.0, dt=DT)
        np.testing.assert_allclose(out2[-1].sink_mb, ref.sink_mb, atol=1e-4)

    def test_inert_spare_rows_are_harmless(self):
        # 17 scenarios -> 20 rows: three spare rows run as inert
        # scenarios; every real result stays finite and correct
        sims = self._fleet(17)
        runner = FleetRunner(fused=True)
        out = runner.run(sims, "appaware", seconds=10.0, dt=DT)
        assert runner.last_stats["rows"] == [20]
        ref = simulate(sims[3], "appaware", seconds=10.0, dt=DT)
        np.testing.assert_allclose(out[3].sink_mb, ref.sink_mb, atol=1e-4)
        for r in out:
            assert np.isfinite(r.sink_mb).all()
            assert np.isfinite(r.latency).all()


class TestStagingFingerprint:
    """The staging-reuse fingerprint must cover field *content*, not just
    scenario object identity: CompiledSim is a plain (non-frozen)
    dataclass, so a caller can legally mutate a scenario's arrays in
    place between warm calls — the runner must restage, not replay the
    pre-mutation fleet from its buffers."""

    def test_inplace_mutation_restages(self):
        g = parallelize(trending_topics(), seed=0)
        sims = [compile_sim(g, big_switch(8, 1.0 + 0.1 * i),
                            round_robin(g, 8)) for i in range(3)]
        # re-back one scenario's gen_rate with a mutable numpy array — the
        # scenario OBJECT stays the same across both runs
        gen = np.asarray(sims[1].gen_rate).copy()
        sims[1].gen_rate = gen
        runner = FleetRunner(fused=True)
        out1 = runner.run(sims, "tcp", seconds=10.0, dt=DT)
        assert "order_rebuilds" in runner.last_stats
        # starve the sources (scaling UP would be invisible in sink_mb on
        # this bandwidth-bound corpus); in-place: identity check is blind
        gen *= 0.05
        out2 = runner.run(sims, "tcp", seconds=10.0, dt=DT)
        # the mutated scenario must reflect its new generation rate ...
        ref = simulate(sims[1], "tcp", seconds=10.0, dt=DT)
        np.testing.assert_allclose(out2[1].sink_mb, ref.sink_mb, atol=1e-4)
        assert not np.allclose(out1[1].sink_mb, out2[1].sink_mb)
        # ... while untouched scenarios reproduce bitwise
        np.testing.assert_array_equal(out1[0].sink_mb, out2[0].sink_mb)
        np.testing.assert_array_equal(out1[2].sink_mb, out2[2].sink_mb)

    def test_unmutated_warm_call_still_reuses_staging(self):
        g = parallelize(trending_topics(), seed=0)
        sims = [compile_sim(g, big_switch(8, 1.0 + 0.1 * i),
                            round_robin(g, 8)) for i in range(3)]
        runner = FleetRunner(fused=True)
        out1 = runner.run(sims, "tcp", seconds=10.0, dt=DT)
        size = runner.compile_cache_size()
        out2 = runner.run(sims, "tcp", seconds=10.0, dt=DT)
        assert runner.compile_cache_size() == size
        for a, b in zip(out1, out2):
            np.testing.assert_array_equal(a.sink_mb, b.sink_mb)
