"""The tick's per-flow picks (``simulator._pick``): a one-hot select-and-sum
that reads, bit for bit, what the index gather ``v[idx]`` reads, alone
under vmap and through a whole padded mixed fleet bucket."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.net import big_switch
from repro.streams import (FleetRunner, LoadSchedule, Scenario, nexmark_q5,
                           parallelize, round_robin, trending_topics,
                           trucking_iot)
from repro.streams import simulator
from repro.streams.simulator import _pick

DT = 0.5

_TINY = np.float32(1e-40)  # subnormal in float32
_CASES = {
    # padded flows carry index 0 and read v[0], as the gather does
    "padded_with_zero": (
        np.array([[3.0, 0.25, 7.5, 1.0], [0.5, 2.0, 0.0, 4.0]], np.float32),
        np.array([[2, 1, 3, 0, 0, 0], [3, 3, 1, 0, 0, 0]], np.int32)),
    "hits_every_instance": (
        np.arange(1, 13, dtype=np.float32).reshape(2, 6) / 7.0,
        np.array([[5, 4, 3, 2, 1, 0, 0], [0, 1, 2, 3, 4, 5, 2]], np.int32)),
    "special_values": (
        np.array([[0.0, -0.0, _TINY, -_TINY, 8.0, 1e30, np.inf],
                  [1e30, 8.0, 0.0, _TINY, np.nan, -0.0, 2.0**-149]],
                 np.float32),
        np.array([[0, 1, 2, 3, 4, 5, 6, 2, 2], [6, 5, 4, 3, 2, 1, 0, 3, 0]],
                 np.int32)),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_pick_is_the_gather_bitwise(case):
    v, idx = _CASES[case]
    got = np.asarray(jax.jit(jax.vmap(_pick))(v, idx))
    want = np.asarray(jax.jit(jax.vmap(lambda a, i: a[i]))(v, idx))
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
    np.testing.assert_array_equal(
        want.view(np.int32), np.take_along_axis(v, idx, axis=1).view(np.int32))


def _mixed_bucket():
    """TT and TI static, Q5 under SINE input: three DAGs of different
    instance and flow counts, padded into one bucket."""
    topo = big_switch(8, 1.25)
    sims = []
    for name, app in (("TT", trending_topics()), ("TI", trucking_iot())):
        g = parallelize(app, seed=0)
        sims.append(Scenario(name, g, topo, round_robin(g, 8)).compile())
    g = parallelize(nexmark_q5(), seed=0)
    src = np.flatnonzero(g.gen_rate > 0)
    sine = LoadSchedule.empty(g.n_instances).with_sine(
        20.0, 1.0, 4.0, src, phase=0.7)
    sims.append(Scenario("Q5_SINE", g, big_switch(8, 1.875),
                         np.arange(g.n_instances) % 8, load=sine).compile())
    return sims


@pytest.mark.parametrize("policy", ["tcp", "appaware"])
def test_mixed_bucket_matches_the_gather(policy, monkeypatch):
    sims = _mixed_bucket()
    runner = FleetRunner(max_buckets=1, tick_overhead=0.0)
    out = runner.run(sims, policy, seconds=30.0, dt=DT)
    assert runner.last_stats["n_buckets"] == 1
    # jit caches the traced `_run`: drop it so the gather is traced in
    monkeypatch.setattr(simulator, "_pick", lambda v, idx: v[idx])
    jax.clear_caches()
    gather = FleetRunner(max_buckets=1, tick_overhead=0.0)
    ref = gather.run(sims, policy, seconds=30.0, dt=DT)
    for got, want in zip(out, ref):
        for field in ("sink_mb", "sink_mb_app", "latency", "link_load",
                      "order_rebuilds", "metrics"):
            a, b = getattr(got, field), getattr(want, field)
            assert a.shape == b.shape, field
            np.testing.assert_array_equal(a, b, err_msg=field)
        assert got.metric("total_sink_mb") > 0.0
    monkeypatch.undo()
    jax.clear_caches()


def test_pick_lowers_without_a_gather():
    v = jax.ShapeDtypeStruct((64, 26), jnp.float32)
    idx = jax.ShapeDtypeStruct((64, 137), jnp.int32)
    text = jax.jit(jax.vmap(_pick)).lower(v, idx).compile().as_text()
    assert " gather(" not in text
