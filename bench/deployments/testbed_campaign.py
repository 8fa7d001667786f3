"""A ``testbed_campaign`` deployment: Storm apps on machines behind one
SDN switch (paper Sec. VI).

A scenario is kept as plain parameters (app, machines, capacity, failed
links by machine and direction, schedule constants). The program's
scenario is built from them with its public constructors
(:func:`program_scenario`); the reference builds its own input arrays from
the same parameters (``reference.testbed_arrays``), so a fault in the
program's compilation of a scenario reaches one side only.

What the campaign loop calls: :func:`corpus`, :func:`program_scenario`,
:func:`reference_row`. What the controller loop calls: :func:`fabric`,
:func:`flow_states`, for the one testbed its traffic's ``testbed`` names.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchlib import deploy, reference

UP, DOWN = 0, 1     # direction of a machine's link: to or from its switch


@dataclasses.dataclass
class TestbedScenario:
    """One scenario of a one-switch testbed campaign."""

    name: str
    graph: object              # the app's parallelized instance DAG
    placement: np.ndarray      # [I] machine of every instance
    n_machines: int
    cap: float                 # MB/s of every machine link
    # (machine, UP or DOWN, t0, t1, capacity scale) per capacity event
    events: list = dataclasses.field(default_factory=list)
    # (period s, amplitude, phase rad) of a cycle on every link, or None
    diurnal: tuple | None = None


def _machines(cfg: dict) -> int:
    topo = cfg["topology"]
    if topo["constructor"] != "big_switch":
        raise ValueError(f"testbed constructor {topo['constructor']!r} "
                         f"unknown")
    return int(topo["n_machines"])


def corpus(cfg: dict, seed: int) -> list[TestbedScenario]:
    """The campaign corpus: ``n_scenarios`` scenarios tiling apps x
    capacities x schedule kinds (scenario k: app k % A, capacity
    (k // A) % C, schedule (k // (A*C)) % S), with per-scenario jitter
    drawn from ``seed``."""
    from repro.streams.app import parallelize

    n_mach = _machines(cfg)
    rng = np.random.default_rng(seed)
    graphs = [parallelize(deploy.app(a), seed=seed) for a in cfg["apps"]]
    # Storm's even scheduler: instance i on machine i % n
    places = [np.arange(g.n_instances) % n_mach for g in graphs]
    caps = [float(c) for c in cfg["capacities_mb_s"]]
    kinds = list(cfg["schedules"])
    fail, diu = cfg["fail"], cfg["diurnal"]
    A, C, S = len(graphs), len(caps), len(kinds)
    out = []
    for k in range(int(cfg["n_scenarios"])):
        kind = kinds[(k // (A * C)) % S]
        sc = TestbedScenario(f"{cfg['apps'][k % A]}_{kind}{k}",
                             graphs[k % A], places[k % A], n_mach,
                             caps[(k // A) % C])
        if kind == "fail":
            # link j of the 2n machine links: machine j // 2, UP or DOWN
            failed = rng.choice(2 * n_mach, size=int(fail["n_links"]),
                                replace=False)
            t0 = float(rng.uniform(*fail["t_fail_s"]))
            t1 = t0 + float(rng.uniform(*fail["duration_s"]))
            scale = float(rng.uniform(*fail["degrade"]))
            sc.events = [(int(j) // 2, int(j) % 2, t0, t1, scale)
                         for j in failed]
        elif kind == "diurnal":
            sc.diurnal = (float(rng.uniform(*diu["period_s"])),
                          float(rng.uniform(*diu["amplitude"])),
                          float(rng.uniform(*diu["phase_rad"])))
        elif kind != "static":
            raise ValueError(f"schedule kind {kind!r} unknown")
        out.append(sc)
    return out


def program_scenario(sc: TestbedScenario):
    """The program's ``Scenario`` of one testbed scenario, built with its
    public constructors."""
    from repro.net.topology import LinkSchedule, big_switch
    from repro.streams.scenarios import Scenario

    topo = big_switch(sc.n_machines, sc.cap)
    sched = None
    if sc.events or sc.diurnal is not None:
        sched = LinkSchedule.empty(topo.n_links)
        for m, d, t0, t1, scale in sc.events:
            link = topo.uplink_idx[m] if d == UP else topo.downlink_idx[m]
            sched = sched.with_event([int(link)], t0, t1, scale)
        if sc.diurnal is not None:
            period, amp, phase = sc.diurnal
            sched = sched.with_diurnal(period, amp, phase=phase)
    return Scenario(sc.name, sc.graph, topo, sc.placement, schedule=sched)


def reference_row(sc: TestbedScenario, policy: str, kw: dict,
                  precision: str = "exact") -> np.ndarray:
    """The plain reference's metric row ([7], ``reference.METRICS``) of
    one scenario under the campaign settings ``kw``, at ``precision``
    (``reference.Arith``)."""
    s = reference.testbed_arrays(sc.graph, sc.placement, sc.n_machines,
                                 sc.cap, sc.events, sc.diurnal)
    return reference.simulate_ref(s, policy,
                                  int(round(kw["seconds"] / kw["dt"])),
                                  kw["dt"], kw["upd_every"], kw["qcap"],
                                  reference.Arith(precision))


def fabric(cfg: dict, seed: int, traffic: dict) -> deploy.Fabric:
    """The static testbed that the traffic's ``testbed`` names: ``app``,
    one of the configuration's ``apps``, with every machine link at
    ``link_mb_s``, one of its ``capacities_mb_s``; the app parallelized
    with the seed and placed by Storm's even scheduler: machine m's
    uplink is link 2m and its downlink 2m + 1."""
    tb = traffic["testbed"]
    if tb["app"] not in cfg["apps"]:
        raise ValueError(f"testbed app {tb['app']!r} is not one of the "
                         f"configuration's apps {cfg['apps']}")
    if float(tb["link_mb_s"]) not in map(float, cfg["capacities_mb_s"]):
        raise ValueError(f"testbed link {tb['link_mb_s']} MB/s is not one "
                         f"of the configuration's capacities "
                         f"{cfg['capacities_mb_s']}")
    from repro.streams.app import parallelize

    n_mach = _machines(cfg)
    g = parallelize(deploy.app(tb["app"]), seed=seed)
    s = reference.testbed_arrays(g, np.arange(g.n_instances) % n_mach,
                                 n_mach, float(tb["link_mb_s"]))
    return deploy.Fabric(np.asarray(s["R"], np.float32),
                         np.asarray(s["caps"], np.float64),
                         np.asarray(s["kinds"], np.int32),
                         [(g, np.arange(n_mach))])


def flow_states(cfg: dict, fab: deploy.Fabric, traffic: dict
                ) -> list[tuple[np.ndarray, ...]]:
    """The traffic's ``n_states`` flow states of the testbed after its
    ``warm_intervals`` (:func:`deploy.simulated_states`)."""
    (g, hosts), = fab.tenants
    return deploy.simulated_states(
        cfg, [g], len(hosts), float(traffic["testbed"]["link_mb_s"]),
        int(traffic["n_states"]), int(traffic["warm_intervals"]))
