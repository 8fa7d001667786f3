"""The deployments the configurations describe, built from a seed."""
import dataclasses
import hashlib

import numpy as np
import pytest

from benchlib import deploy, reference, spec
from fault_run import benchmark

BIG_SEED = 2**40 + 123
TESTBED = spec.load_deployment("testbed_campaign")
FABRIC = spec.load_deployment("fabric_controller")


def _cfg(name):
    return spec.resolve({"storm-testbed-8": "testbed.campaign-tcp",
                         "fattree-k16": "fattree.controller"}[name]).config


def _traffic(cell, **kw):
    return dict(spec.resolve(cell).traffic, **kw)


def _testbed_controller():
    """The paper's testbed under the controller loop: a cell that
    BENCHMARK.json does not hold (its host latency spreads too widely
    between processes for the controller bounds), built from its files."""
    return spec.resolve("testbed.controller", bench=benchmark())


def test_storm_testbed_2048_scenarios_in_6_shapes():
    from repro.streams.fleet import _sim_shape

    sims = [TESTBED.program_scenario(sc).compile() for sc in
            TESTBED.corpus(_cfg("storm-testbed-8"), BIG_SEED)]
    assert len(sims) == 2048
    shapes = {dataclasses.astuple(_sim_shape(s)) for s in sims}
    assert len(shapes) == 6
    assert {s.R.shape for s in sims} == {(13, 16), (17, 16)}


def test_storm_testbed_seed_moves_jitter_not_sizes():
    cfg = dict(_cfg("storm-testbed-8"), n_scenarios=36)
    a = TESTBED.corpus(cfg, 7)
    b = TESTBED.corpus(cfg, 7)
    c = TESTBED.corpus(cfg, BIG_SEED)
    for x, y, z in zip(a, b, c):
        assert x.name == y.name == z.name
        assert np.array_equal(x.graph.w_out, y.graph.w_out)
        assert x.graph.w_out.shape == z.graph.w_out.shape
        assert x.events == y.events and len(x.events) == len(z.events)
        assert x.diurnal == y.diurnal
        assert (x.diurnal is None) == (z.diurnal is None)
    assert any(x.diurnal != z.diurnal for x, z in zip(a, c))


def test_program_scenario_carries_the_same_deployment():
    """The program's compiled scenario and the reference's own arrays
    describe the same links, flows and schedule (machine m's uplink and
    downlink mapped through the program's topology)."""
    cfg = dict(_cfg("storm-testbed-8"), n_scenarios=18)
    for sc in TESTBED.corpus(cfg, BIG_SEED):
        scen = TESTBED.program_scenario(sc)
        sim = scen.compile()
        ref = reference.testbed_arrays(sc.graph, sc.placement, sc.n_machines,
                                       sc.cap, sc.events, sc.diurnal)
        order = np.ravel(np.column_stack([scen.topo.uplink_idx,
                                          scen.topo.downlink_idx]))
        np.testing.assert_array_equal(np.asarray(sim.R)[:, order], ref["R"])
        np.testing.assert_array_equal(np.asarray(sim.kinds)[order],
                                      ref["kinds"])
        for k in ("caps", "p_in", "path_w", "w_of_flow", "gen_rate",
                  "proc_rate", "selectivity", "sin_amp", "sin_omega",
                  "sin_phase", "ev_t0", "ev_t1", "ev_scale"):
            a = np.asarray(getattr(sim, k))
            if k in ("caps", "sin_amp", "sin_omega", "sin_phase"):
                a = a[..., order]
            np.testing.assert_allclose(a, ref[k], rtol=1e-6, err_msg=k)
        np.testing.assert_array_equal(order[ref["ev_link"]],
                                      np.asarray(sim.ev_link))


def test_fat_tree_routes():
    """k=4: 16 hosts, 48 directed links; a flow climbs only as far as its
    hosts' common switch, and the two-level tables spread a pod's flows
    over every core link."""
    k = 4
    L, kind, tables = deploy.fat_tree_links(k)
    assert L == 16 * 2 + 4 * 16 and np.bincount(kind).tolist() == [16, 16, 64]
    used = set()
    for s in range(16):
        for d in range(16):
            p = deploy.fat_tree_route(k, tables, s, d)
            same_edge, same_pod = s // 2 == d // 2, s // 4 == d // 4
            want = 0 if s == d else 2 if same_edge else 4 if same_pod else 6
            assert len(p) == want and len(set(p)) == want
            if p:
                assert kind[p[0]] == 0 and kind[p[-1]] == 1
                assert all(kind[x] == 2 for x in p[1:-1])
            used.update(p)
    assert used == set(range(L))


def test_fattree_k16_1024_hosts_6144_links_1920_flows():
    fab = FABRIC.fabric(_cfg("fattree-k16"), BIG_SEED,
                        _traffic("fattree.controller"))
    assert fab.R.shape == (1920, 6144)
    assert len(fab.tenants) == 128
    hosts = np.concatenate([h for _, h in fab.tenants])
    assert len(set(hosts.tolist())) == 1024
    assert np.bincount(fab.kind).tolist() == [1024, 1024, 4096]
    assert set(fab.cap.tolist()) == {125.0}
    assert set(fab.R.sum(1).tolist()) <= {0.0, 2.0, 4.0, 6.0}


def test_flow_states_are_simulated_and_seeded():
    cfg = _cfg("fattree-k16")
    tr = _traffic("fattree.controller", n_states=6, warm_intervals=2)
    fab = FABRIC.fabric(cfg, BIG_SEED, tr)
    a = FABRIC.flow_states(cfg, fab, tr)
    b = FABRIC.flow_states(cfg, FABRIC.fabric(cfg, BIG_SEED, tr), tr)
    assert len(a) == 6 and all(len(s) == 5 for s in a)
    assert all(np.array_equal(x, y) for s, t in zip(a, b)
               for x, y in zip(s, t))
    allv = np.concatenate([f for s in a for f in s])
    assert allv.dtype == np.float32 and allv.min() >= 0.0
    assert allv.max() <= 8.0 * float(cfg["qcap_mb"])
    assert len({b"".join(f.tobytes() for f in s) for s in a}) == 6
    assert all(s[2].sum() > 0 for s in a)


def _corpus_digest(corpus) -> str:
    h = hashlib.sha256()
    for sc in corpus:
        h.update(repr((sc.name, sc.n_machines, sc.cap, sc.events,
                       sc.diurnal)).encode())
        h.update(np.asarray(sc.placement, np.int64).tobytes())
        h.update(np.asarray(sc.graph.w_out, np.float64).tobytes())
    return h.hexdigest()


def _fabric_digest(fab, states) -> str:
    h = hashlib.sha256()
    for a in (fab.R, fab.cap, fab.kind, *(x for st in states for x in st)):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_deployments_read_what_they_read_before_the_move():
    """Digests taken on the benchmark before the deployments moved into
    ``bench/deployments/``: storm-testbed-8's corpus (names, placements,
    links, events, cycles, flow weights) and fattree-k16's R, capacities,
    kinds and 6 flow states, at one seed."""
    corpus = TESTBED.corpus(_cfg("storm-testbed-8"), BIG_SEED)
    assert _corpus_digest(corpus) == (
        "25a33f8f99f517b4c757a19ad2037de4f836ff0953fde9754ed3961c6d3aca5d")
    cfg = _cfg("fattree-k16")
    tr = _traffic("fattree.controller", n_states=6, warm_intervals=2)
    fab = FABRIC.fabric(cfg, BIG_SEED, tr)
    assert _fabric_digest(fab, FABRIC.flow_states(cfg, fab, tr)) == (
        "bb0ecb1673a67b345ea94cb25621d3e9ef62931c1661d6802ae53d88a4228f18")


def test_testbed_controller_16_links_17_flows_64_states():
    """The controller traffic's testbed: TT on 8 machines at 1.875 MB/s,
    every flow across one uplink and one downlink; its flow states are
    the reference's appaware simulation of that testbed, seeded, and
    most of them differ."""
    cell = _testbed_controller()
    cfg, tr = cell.config, cell.traffic
    fab = TESTBED.fabric(cfg, BIG_SEED, tr)
    assert fab.R.shape == (17, 16) and fab.R.dtype == np.float32
    assert fab.R.sum(1).tolist() == [2.0] * 17
    assert fab.kind.tolist() == [0, 1] * 8
    assert set(fab.cap.tolist()) == {1.875}
    a = TESTBED.flow_states(cfg, fab, tr)
    b = TESTBED.flow_states(cfg, TESTBED.fabric(cfg, BIG_SEED, tr), tr)
    assert len(a) == 64 and all(len(s) == 5 for s in a)
    assert all(np.array_equal(x, y) for s, t in zip(a, b)
               for x, y in zip(s, t))
    allv = np.concatenate([f for s in a for f in s])
    assert allv.dtype == np.float32 and allv.min() >= 0.0
    assert len({b"".join(f.tobytes() for f in s) for s in a}) >= 32
    c = TESTBED.flow_states(cfg, TESTBED.fabric(cfg, 7, tr), tr)
    assert not all(np.array_equal(x, y) for s, t in zip(a, c)
                   for x, y in zip(s, t))


@pytest.mark.parametrize("testbed", [
    {"app": "linkedin_tags", "link_mb_s": 1.875},
    {"app": "trending_topics", "link_mb_s": 3.0},
])
def test_testbed_controller_takes_only_what_the_configuration_states(
        testbed):
    """A controller traffic can name only a testbed that the
    configuration, and so its source, states: one of its apps at one of
    its capacities."""
    cell = _testbed_controller()
    with pytest.raises(ValueError, match="configuration's"):
        TESTBED.fabric(cell.config, 7, dict(cell.traffic, testbed=testbed))
