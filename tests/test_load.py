"""In-run source load (``LoadSchedule``): the simulator's [T, I] rate grid
matches the schedule's numpy evaluation, a sim without a schedule runs
the constant-load path, and a constant-load sim padded into a loaded
bucket runs bitwise its own solo run; NEXmark Q5's topology as its
configuration states it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.net.topology import big_switch
from repro.streams import (FleetRunner, LoadSchedule, Scenario, compile_sim,
                           nexmark_q5, parallelize, round_robin, simulate,
                           trending_topics)
from repro.streams.fleet import FleetShape, _sim_shape, pad_sim
from repro.streams.simulator import CompiledSim, _load_over, resolve_upd_every

DT = 0.5


def _q5(events_per_s=25_000.0):
    g = parallelize(nexmark_q5(events_per_s), seed=0)
    return g, np.flatnonzero(g.gen_rate > 0)


def _sine_square(g, src):
    sine = LoadSchedule.empty(g.n_instances).with_sine(
        60.0, 1.0, 4.0, src, phase=0.7)
    square = LoadSchedule.empty(g.n_instances)
    for t0, t1 in ((5.0, 12.5), (20.1, 27.5)):
        square = square.with_steps(t0, t1, 3.0, src)
    return sine, square


def test_q5_topology_as_configured():
    """I = 26, F = 137 of which 17 machine-local under Storm's even
    scheduler on 8 machines; at 3.15 MB/s of events every machine link
    carries 0.596 MB/s."""
    from repro.streams.placement import _steady_state_flow_volume

    g, src = _q5()
    assert (g.n_instances, g.n_flows) == (26, 137)
    assert src.tolist() == list(range(8))
    np.testing.assert_allclose(g.gen_rate.sum(), 3.15)
    sim = compile_sim(g, big_switch(8, 1.25), np.arange(26) % 8)
    R = np.asarray(sim.R, np.float64)
    assert int((R.sum(1) == 0).sum()) == 17
    load = _steady_state_flow_volume(g) @ R
    np.testing.assert_allclose(load, 0.596, atol=2.5e-3)
    assert np.asarray(sim.path_w).sum() == pytest.approx(4.0)


@pytest.mark.parametrize("shape", ["sine", "square"])
def test_rate_grid_matches_numpy(shape):
    g, src = _q5()
    sched = dict(zip(("sine", "square"), _sine_square(g, src)))[shape]
    sim = compile_sim(g, big_switch(8, 1.25), np.arange(26) % 8, load=sched)
    assert sim.is_loaded
    ts = jnp.arange(80, dtype=jnp.float32) * DT
    got = np.asarray(_load_over(sim, ts))
    want = g.gen_rate[None, :] * sched.scale_at(np.asarray(ts))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=0)
    assert np.all(got[:, 8:] == 0.0)
    if shape == "square":
        # [t0, t1) decided in float32: on at 5.0, off at 12.5
        on = np.flatnonzero(got[:, 0] > g.gen_rate[0] * 1.5) * DT
        assert on.tolist() == [t for t in np.arange(80) * DT
                               if 5.0 <= t < 12.5 or 20.1 <= t < 27.5]


def test_schedule_checked_at_compile():
    g, src = _q5()
    topo, place = big_switch(8, 1.25), np.arange(26) % 8
    with pytest.raises(ValueError, match="generate nothing"):
        compile_sim(g, topo, place, load=LoadSchedule.empty(26).with_sine(
            60.0, 1.0, 2.0, [8]))
    with pytest.raises(ValueError, match="generate nothing"):
        compile_sim(g, topo, place, load=LoadSchedule.empty(26).with_steps(
            1.0, 2.0, 2.0, [25]))
    with pytest.raises(ValueError, match="covers 17 instances"):
        compile_sim(g, topo, place, load=LoadSchedule.empty(17))
    with pytest.raises(ValueError, match="out of range"):
        LoadSchedule.empty(26).with_steps(1.0, 2.0, 2.0, [26])
    with pytest.raises(ValueError, match="load_scale"):
        compile_sim(g, topo, place, load=LoadSchedule.empty(26).with_steps(
            1.0, 2.0, np.nan, src))
    empty = compile_sim(g, topo, place, load=LoadSchedule.empty(26))
    assert not empty.is_loaded


def test_load_moves_the_run():
    """Doubling the sources' rate over a window is what the run sees: a
    step at scale 1 is the constant run bitwise, a step at 2 sends more
    to the sink."""
    g, src = _q5()
    topo, place = big_switch(8, 2.5), np.arange(26) % 8

    def run(scale):
        load = LoadSchedule.empty(26).with_steps(10.0, 30.0, scale, src)
        sim = compile_sim(g, topo, place, load=load)
        return simulate(sim, "tcp", seconds=40.0, dt=DT)

    base = simulate(compile_sim(g, topo, place), "tcp", seconds=40.0, dt=DT)
    np.testing.assert_array_equal(run(1.0).metrics, base.metrics)
    assert run(2.0).metric("total_sink_mb") > 1.3 * base.metric(
        "total_sink_mb")


def _tt_static():
    g = parallelize(trending_topics(), seed=0)
    return Scenario("TT", g, big_switch(8, 1.25), round_robin(g, 8)).compile()


@pytest.mark.parametrize("policy", ["tcp", "appaware"])
def test_static_sim_padded_into_loaded_bucket_is_bitwise(policy):
    """A constant-load testbed sim padded with load axes (zero-amplitude
    sinusoids, never-starting steps) streams a rate grid and runs bitwise
    its own solo run, alone and batched beside a loaded sim."""
    sim = _tt_static()
    shape = dataclasses.replace(_sim_shape(sim), n_load_sins=1,
                                n_load_steps=8)
    padded = pad_sim(sim, shape)
    assert padded.is_loaded and not sim.is_loaded
    solo = simulate(sim, policy, seconds=30.0, dt=DT)
    pad = simulate(padded, policy, seconds=30.0, dt=DT)
    np.testing.assert_array_equal(pad.metrics, solo.metrics)
    np.testing.assert_array_equal(pad.sink_mb, solo.sink_mb)
    np.testing.assert_array_equal(pad.latency, solo.latency)

    g = parallelize(trending_topics(), seed=0)
    src = np.flatnonzero(g.gen_rate > 0)
    load = LoadSchedule.empty(g.n_instances).with_sine(20.0, 1.0, 3.0, src)
    for t0 in (2.0, 9.0, 16.0, 23.0):
        load = load.with_steps(t0, t0 + 3.0, 0.5, src)
    loaded = Scenario("TT_load", g, big_switch(8, 1.25), round_robin(g, 8),
                      load=load).compile()
    mixed = FleetRunner(max_buckets=1, tick_overhead=0.0)
    out = mixed.run([sim, loaded], policy, seconds=30.0, dt=DT)
    assert mixed.last_stats["n_buckets"] == 1
    twin = FleetRunner(max_buckets=1, tick_overhead=0.0)
    ref = twin.run([sim, _tt_static()], policy, seconds=30.0, dt=DT)
    np.testing.assert_array_equal(out[0].metrics, ref[0].metrics)
    assert not np.array_equal(out[1].metrics, out[0].metrics)


def test_campaign_counts_loaded_rows_and_names_the_scope():
    g, src = _q5()
    sine, square = _sine_square(g, src)
    topo, place = big_switch(8, 1.875), np.arange(26) % 8
    sims = [compile_sim(g, topo, place, load=ld)
            for ld in (sine, None, square, None, sine)]
    runner = FleetRunner()
    cr = runner.run_campaign(sims, "appaware", seconds=20.0, dt=DT,
                             chunk_rows=2)
    assert runner.last_stats["rows_loaded"] == 3
    mat = runner.run(sims, "appaware", seconds=20.0, dt=DT)
    for i, r in enumerate(mat):
        np.testing.assert_array_equal(cr.metrics[i], r.metrics)

    idxs, shape = runner.plan(sims, "appaware")[0]
    assert shape.n_load_sins == 1 and shape.n_load_steps == 2
    leaves = runner._fill_bucket({}, [sims[i] for i in idxs[:2]], shape, 2)
    pack = CompiledSim(tuples_per_mb=1.0, n_apps=shape.n_apps,
                       **{k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                          for k, v in leaves.items()})
    fn = runner._executable(("scopes", "appaware"), "appaware", 4, DT,
                            resolve_upd_every("appaware", DT, None), 0.5, 8,
                            "sort")
    text = fn.lower((pack,), (None,),
                    (jax.ShapeDtypeStruct((2,), np.bool_),),
                    jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    assert "/load/" in text


def test_fleet_shape_covers_load_axes():
    g, src = _q5()
    sine, square = _sine_square(g, src)
    topo, place = big_switch(8, 1.25), np.arange(26) % 8
    sims = [compile_sim(g, topo, place, load=ld) for ld in (sine, square)]
    cover = FleetShape.cover(sims)
    assert (cover.n_load_sins, cover.n_load_steps) == (1, 2)
    padded = pad_sim(sims[0], cover)
    assert np.all(np.asarray(padded.load_t0) == np.inf)
    np.testing.assert_array_equal(
        np.asarray(_load_over(padded, jnp.arange(40.0) * DT)),
        np.asarray(_load_over(sims[0], jnp.arange(40.0) * DT)))
