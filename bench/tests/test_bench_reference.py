"""The plain numpy reference agrees with the program on the CPU, which
shows it implements the same semantics (the chip runs compare against
it)."""
import numpy as np
import pytest

from benchlib import reference, spec

campaign = spec.load_loop("campaign")


@pytest.mark.parametrize("policy", ["tcp", "appaware"])
def test_simulation_reference_matches_program(policy):
    """One scenario of every stratum, the reference built from the
    scenario's own parameters: tcp rows agree to rounding; appaware rows
    nearly all do (a join that stalls or not may amplify rounding in a
    few)."""
    from repro.streams import simulate

    cell = spec.resolve("testbed.campaign-tcp")
    dep = cell.deployment
    cfg = dict(cell.config, n_scenarios=18)
    kw = campaign.settings(cfg, policy)
    gaps = []
    for sc in dep.corpus(cfg, 2**33 + 5):
        sim = dep.program_scenario(sc).compile()
        prog = simulate(sim, policy, seconds=kw["seconds"], dt=kw["dt"],
                        upd_every=kw["upd_every"], qcap=kw["qcap"]).metrics
        ref = dep.reference_row(sc, policy, kw)
        gaps.append(campaign.row_gap(prog[None], ref, kw["seconds"])[0])
    gaps = np.asarray(gaps)
    if policy == "tcp":
        assert gaps.max() < 1e-5, gaps
    else:
        assert np.median(gaps) < 2e-6 and np.sum(gaps < 1e-4) >= 16, gaps


def test_maxmin_reference_matches_progressive_filling_oracle():
    from repro.core.tcp import demand_limited_maxmin_np

    rng = np.random.default_rng(3)
    for F, L in ((17, 16), (40, 12)):
        R = np.zeros((F, L))
        for f in range(F):
            R[f, rng.choice(L, size=2, replace=False)] = 1.0
        R[0] = 0.0                                # one flow off the net
        cap = rng.uniform(1.0, 5.0, L)
        d = rng.uniform(0.0, 2.0, F)
        x = reference.maxmin_ref(R, cap, d, reference.Arith("exact"))
        np.testing.assert_allclose(x, demand_limited_maxmin_np(R, cap, d),
                                   rtol=1e-9, atol=1e-12)


def test_allocate_reference_matches_program():
    from repro.core.allocator import OnlineAllocator
    from repro.core.flowstate import FlowState

    cell = spec.resolve("fattree.controller")
    cfg, dep = cell.config, cell.deployment
    tr = dict(cell.traffic, n_states=2, warm_intervals=2)
    fab = dep.fabric(cfg, 11, tr)
    alloc = OnlineAllocator(fab.R, fab.cap, fab.kind, dt=5.0)
    for st in dep.flow_states(cfg, fab, tr):
        x = np.asarray(alloc(FlowState(*st)), np.float64)
        r = reference.allocate_ref(fab.R, fab.cap, fab.kind, st, 5.0,
                                   reference.Arith("exact"))
        assert np.abs(x - r).max() < 1e-3
