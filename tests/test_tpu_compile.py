"""Compile the main path for a described TPU v5e chip — no chip attached.

The TPU compiler installed with JAX compiles for a chip that is described
(``topologies.get_topology_desc``) and not present, so what it would
refuse on the chip — a kernel tiling, VMEM or HBM over-use, an operation
it cannot lower — fails here first. Nothing runs: these tests say nothing
about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file. The persistent compilation cache is off around
these compiles (an entry written for a described chip cannot be read back
without one).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.waterfill.kernel import waterfill_pallas
from repro.streams import campaign_fleet, compile_fleet
from repro.streams.fleet import TICK_OVERHEAD_FLOPS_CPU, FleetRunner
from repro.streams.simulator import CompiledSim, resolve_upd_every

N_TICKS = 1200   # the paper's 600 s horizon at dt = 0.5 s
CHUNK_ROWS = 64  # run_campaign's default chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


@pytest.fixture(scope="module")
def campaign_texts(one_chip):
    """Compiled HLO of the first campaign bucket's chunk executable per
    policy — the program ``run_campaign`` dispatches for every chunk."""
    sims = compile_fleet(campaign_fleet(36, seed=0))
    runner = FleetRunner(tick_overhead=TICK_OVERHEAD_FLOPS_CPU)
    texts = {}

    def compile_policy(policy):
        if policy not in texts:
            idxs, shape = runner.plan(sims, policy)[0]
            leaves = runner._fill_bucket({}, [sims[i] for i in idxs],
                                         shape, CHUNK_ROWS)
            pack = CompiledSim(
                tuples_per_mb=1.0, n_apps=shape.n_apps,
                **{k: _spec(v.shape, v.dtype, one_chip)
                   for k, v in leaves.items()})
            fn = runner._executable(
                ("tpu", policy), policy, N_TICKS, 0.5,
                resolve_upd_every(policy, 0.5, None), 0.5, 8, "sort")
            enf = _spec((CHUNK_ROWS,), np.bool_, one_chip)
            qcap = _spec((), jnp.float32, one_chip)
            texts[policy] = fn.lower((pack,), (None,), (enf,),
                                     qcap).compile().as_text()
        return texts[policy]

    return compile_policy


@pytest.mark.parametrize("block_flows", [128, None])
def test_waterfill_kernel_compiles(one_chip, block_flows):
    L, F = 10240, 1024
    vec = _spec((F,), jnp.float32, one_chip)
    args = (vec, vec, vec, _spec((L, F), jnp.float32, one_chip),
            _spec((L,), jnp.float32, one_chip),
            _spec((L,), jnp.int32, one_chip))
    compiled = waterfill_pallas.lower(
        *args, dt=5.0, block_links=128, block_flows=block_flows,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("policy", ["tcp", "appaware", "appfair"])
def test_campaign_chunk_compiles(campaign_texts, policy):
    assert "while" in campaign_texts(policy)  # the scan over 1200 ticks


def test_tcp_contractions_run_at_highest(campaign_texts):
    # on the chip an f32 contraction without a precision runs as one bf16
    # pass; every contraction of the tcp chunk carries f32 values
    lines = [ln for ln in campaign_texts("tcp").splitlines()
             if " convolution(" in ln or " dot(" in ln]
    assert lines
    loose = [ln.strip()[:160] for ln in lines
             if "operand_precision={highest,highest}" not in ln]
    assert not loose, loose


def test_tcp_chunk_names_its_scopes(campaign_texts):
    # a chip trace names each operation by its op_name: the max-min solve
    # and the fluid tick keep their scopes through the TPU compiler
    text = campaign_texts("tcp")
    assert "/maxmin/" in text
    assert "/tick/" in text


def test_loaded_appaware_chunk_compiles(one_chip):
    """NEXmark Q5 under SINE and SQUARE input (F = 137, I = 26, the
    [T, I] rate grid of one sinusoid and 11 steps) in a 64-row appaware
    chunk over 1200 ticks: the load grid keeps its scope."""
    from repro.net.topology import big_switch
    from repro.streams import LoadSchedule, compile_sim, nexmark_q5, parallelize

    g = parallelize(nexmark_q5(), seed=0)
    src = np.flatnonzero(g.gen_rate > 0)
    topo, place = big_switch(8, 1.25), np.arange(g.n_instances) % 8
    sine = LoadSchedule.empty(g.n_instances).with_sine(120.0, 1.0, 4.0, src)
    square = LoadSchedule.empty(g.n_instances)
    for k in range(11):
        square = square.with_steps(60.0 * k, 60.0 * k + 30.0, 4.0, src)
    sims = [compile_sim(g, topo, place, load=ld) for ld in (sine, square)]
    runner = FleetRunner(max_buckets=1, tick_overhead=TICK_OVERHEAD_FLOPS_CPU)
    idxs, shape = runner.plan(sims, "appaware")[0]
    assert (shape.n_flows, shape.n_load_sins, shape.n_load_steps) == (
        137, 1, 11)
    leaves = runner._fill_bucket({}, sims, shape, CHUNK_ROWS)
    pack = CompiledSim(tuples_per_mb=1.0, n_apps=shape.n_apps,
                       **{k: _spec(v.shape, v.dtype, one_chip)
                          for k, v in leaves.items()})
    fn = runner._executable(("tpu", "q5"), "appaware", N_TICKS, 0.5,
                            resolve_upd_every("appaware", 0.5, None), 0.5, 8,
                            "sort")
    text = fn.lower((pack,), (None,), (_spec((CHUNK_ROWS,), np.bool_,
                                             one_chip),),
                    _spec((), jnp.float32, one_chip)).compile().as_text()
    assert "/load/" in text and "while" in text
