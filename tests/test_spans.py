"""The program's host spans and device scopes: a profiler trace names the
campaign pipeline's stretches and the controller's launch, and the
compiled programs name the work the device does for them."""
import glob
import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import FlowState, OnlineAllocator
from repro.core.allocator import allocate
from repro.net import fat_tree
from repro.spans import span
from repro.streams import FleetRunner, campaign_fleet, compile_fleet
from repro.streams.simulator import CompiledSim, resolve_upd_every

SECONDS, DT = 5.0, 0.5


def _host_spans(trace_dir) -> Counter:
    """Count of each program span on the host plane of the trace."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = Counter()
    for pl in ProfileData.from_file(path).planes:
        if pl.name == "/host:CPU":
            for ln in pl.lines:
                out.update(ev.name for ev in ln.events
                           if ev.name.startswith(("campaign.", "allocator.")))
    return out


def test_span_adds_its_seconds_also_on_an_exception():
    tot = {"a_s": 0.0}
    with span("test.a", tot, "a_s") as s:
        pass
    assert tot["a_s"] == s.seconds >= 0.0
    with pytest.raises(ZeroDivisionError):
        with span("test.a", tot, "a_s") as s2:
            1 / 0
    assert s2.seconds > 0.0
    assert tot["a_s"] == pytest.approx(s.seconds + s2.seconds)


def test_span_opened_by_hand_closes_once():
    tot = {"a_s": 0.0}
    s = span("test.a", tot, "a_s").__enter__()
    s.close()
    first = tot["a_s"]
    s.close()
    assert tot["a_s"] == first == s.seconds > 0.0


@pytest.fixture(scope="module")
def small_campaign():
    sims = compile_fleet(campaign_fleet(24, seed=0))
    runner = FleetRunner()
    runner.run_campaign(sims, "tcp", seconds=SECONDS, dt=DT, chunk_rows=8)
    return runner, sims


def test_campaign_spans_on_the_profiler_clock(small_campaign, tmp_path):
    runner, sims = small_campaign
    with jax.profiler.trace(str(tmp_path)):
        runner.run_campaign(sims, "tcp", seconds=SECONDS, dt=DT,
                            chunk_rows=8)
    st = runner.last_stats
    c = _host_spans(tmp_path)
    assert st["n_chunks"] > 1
    assert c["campaign.stage"] == st["n_chunks"]
    assert c["campaign.dispatch"] == st["n_dispatches"] == st["n_chunks"]
    assert c["campaign.h2d"] == c["campaign.wait_h2d"] == st["n_chunks"]
    assert c["campaign.collect"] == st["n_chunks"]
    assert c["campaign.startup"] == 1
    assert 0.0 < st["startup_s"] < st["wall_s"]
    assert st["rows_dispatched"] >= len(sims)
    assert st["rows_dispatched"] <= st["n_dispatches"] * max(st["rows"])


def test_allocator_launch_span(tmp_path):
    topo = fat_tree()
    rng = np.random.default_rng(0)
    m = topo.n_machines
    flows = [(int(a), int(b)) for a, b in rng.integers(0, m, (12, 2))]
    alloc = OnlineAllocator.from_topology(topo, flows)
    state = FlowState(*[jnp.asarray(rng.uniform(0.1, 5, 12), jnp.float32)
                        for _ in range(5)])
    want = np.asarray(alloc(state))
    with jax.profiler.trace(str(tmp_path)):
        got = alloc(state)
        assert isinstance(got, jax.Array)
        got = np.asarray(got)
    assert _host_spans(tmp_path)["allocator.launch"] == 1
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("solver,block_links", [
    ("sort", 0), ("sort", 4), ("pallas", None)])
def test_allocate_names_its_scopes(solver, block_links):
    F, L = 12, 24
    rng = np.random.default_rng(1)
    R = (rng.uniform(size=(F, L)) < 0.2).astype(np.float32)
    alloc = OnlineAllocator(R, np.full(L, 100.0), np.arange(L) % 3,
                            solver=solver)
    vec = jax.ShapeDtypeStruct((F,), jnp.float32)
    text = allocate.lower(
        alloc.program, FlowState(vec, vec, vec, vec, vec), dt=1.0,
        backfill_iters=8, solver=solver,
        block_links=block_links).compile().as_text()
    assert "/per_link/" in text
    assert "/backfill/" in text


def test_chunked_allocate_stacks_no_link_rows():
    # the chunked solve carries the per-flow line-22 min through its loop:
    # no block of per-link rows is written into a stacked [L, F] output
    F, L = 16, 40
    rng = np.random.default_rng(2)
    R = (rng.uniform(size=(F, L)) < 0.2).astype(np.float32)
    alloc = OnlineAllocator(R, np.full(L, 100.0), np.arange(L) % 3)
    vec = jax.ShapeDtypeStruct((F,), jnp.float32)
    text = allocate.lower(
        alloc.program, FlowState(vec, vec, vec, vec, vec), dt=1.0,
        backfill_iters=8, block_links=8).compile().as_text()
    assert "/per_link/" in text
    assert "dynamic-update-slice" not in text


def test_campaign_program_names_its_scopes():
    sims = compile_fleet(campaign_fleet(12, seed=0))
    runner = FleetRunner()
    idxs, shape = runner.plan(sims, "tcp")[0]
    rows = 4
    leaves = runner._fill_bucket({}, [sims[i] for i in idxs[:rows]], shape,
                                 rows)
    pack = CompiledSim(tuples_per_mb=1.0, n_apps=shape.n_apps,
                       **{k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                          for k, v in leaves.items()})
    fn = runner._executable(("scopes", "tcp"), "tcp", 4, DT,
                            resolve_upd_every("tcp", DT, None), 0.5, 8,
                            "sort")
    text = fn.lower((pack,), (None,),
                    (jax.ShapeDtypeStruct((rows,), np.bool_),),
                    jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    assert "/maxmin/" in text
    assert "/tick/" in text
