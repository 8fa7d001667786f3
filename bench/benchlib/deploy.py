"""Deployments built from a configuration file and a seed.

Each function reads the recipe in its configuration's JSON and draws the
deployment's parameters from the seed itself, so the yardstick does not
move when the program's own scenario generators change. The same seed
gives the same deployment, and every seed gives the same shapes: seeds
move jitter, key skew and placement, never a size.

A deployment is kept as plain parameters (apps, machines, capacities,
failed links by machine and direction, schedule constants). The program's
scenario is built from them with its public constructors
(:func:`program_scenario`); the reference builds its own input arrays from
the same parameters (``reference.testbed_arrays``), so a fault in the
program's compilation of a scenario reaches one side only.
"""
from __future__ import annotations

import dataclasses

import numpy as np

UP, DOWN = 0, 1     # direction of a machine's link: to or from its switch


def _apps():
    from repro.streams import workloads
    return {"trending_topics": workloads.trending_topics,
            "trucking_iot": workloads.trucking_iot}


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


def testbed_corpus(cfg: dict, seed: int) -> list[TestbedScenario]:
    """The campaign corpus of a ``testbed_campaign`` configuration:
    ``n_scenarios`` scenarios tiling apps x capacities x schedule kinds
    (scenario k: app k % A, capacity (k // A) % C, schedule
    (k // (A*C)) % S), with per-scenario jitter drawn from ``seed``."""
    from repro.streams.app import parallelize

    topo_cfg = cfg["topology"]
    if topo_cfg["constructor"] != "big_switch":
        raise ValueError(f"testbed constructor {topo_cfg['constructor']!r} unknown")
    n_mach = int(topo_cfg["n_machines"])
    mk = _apps()
    rng = np.random.default_rng(seed)
    graphs = [parallelize(mk[a](), seed=seed) for a in cfg["apps"]]
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


# ----------------------------------------------------------------- fabric
@dataclasses.dataclass
class Fabric:
    """A fabric with its tenants' flows routed on it."""

    R: np.ndarray          # [F, L] float32: flow f crosses link l
    cap: np.ndarray        # [L] MB/s
    kind: np.ndarray       # [L] int32: 0 host uplink, 1 host downlink,
                           #            2 switch-to-switch
    tenants: list          # (app instance DAG, [8] hosts) per tenant


def fat_tree_links(k: int):
    """The directed links of a k-ary fat-tree (Al-Fares et al. 2008, Sec.
    3): k pods of k/2 edge and k/2 aggregation switches, (k/2)^2 core
    switches, k/2 hosts on every edge switch. Returns the link count, the
    kinds, and index tables: host up and down [H], edge-to-aggregation
    and back [k, k/2, k/2] (pod, edge, aggregation), aggregation-to-core
    and back [k, k/2, k/2] (pod, aggregation, core port)."""
    h2 = k // 2
    H = k * h2 * h2
    n = k * h2 * h2                 # links of one switch-to-switch kind
    up, down = np.arange(H), H + np.arange(H)
    e2a, a2e, a2c, c2a = (2 * H + i * n + np.arange(n).reshape(k, h2, h2)
                          for i in range(4))
    L = 2 * H + 4 * n
    kind = np.full(L, 2, np.int32)
    kind[up], kind[down] = 0, 1
    return L, kind, (up, down, e2a, a2e, a2c, c2a)


def fat_tree_route(k: int, tables, src: int, dst: int) -> list[int]:
    """Links of the flow from host ``src`` to host ``dst`` under the
    paper's two-level routing tables (Sec. 3.3): going up, an edge or
    aggregation switch z picks its upward port (h + z) mod k/2 from the
    destination's host number h on its edge switch; core port j of
    aggregation switch a leads to core switch a*k/2 + j, which reaches
    aggregation switch a of every pod. Hosts are numbered pod-major,
    then edge switch, then h."""
    if src == dst:
        return []
    up, down, e2a, a2e, a2c, c2a = tables
    h2 = k // 2
    ps, es = divmod(src // h2, h2)
    pd, ed = divmod(dst // h2, h2)
    h = dst % h2
    path = [int(up[src])]
    if (ps, es) != (pd, ed):
        a = (h + es) % h2
        path.append(int(e2a[ps, es, a]))
        if ps != pd:
            j = (h + a) % h2
            path += [int(a2c[ps, a, j]), int(c2a[pd, a, j])]
        path.append(int(a2e[pd, ed, a]))
    path.append(int(down[dst]))
    return path


def fabric(cfg: dict, seed: int) -> Fabric:
    """The fabric of a ``fabric_controller`` configuration with its
    tenants: every tenant app is parallelized with its own seed and placed
    by Storm's even scheduler on ``hosts_per_tenant`` hosts of a seeded
    permutation of all hosts, one tenant per host."""
    t = cfg["topology"]
    if t["constructor"] != "fat_tree":
        raise ValueError(f"fabric constructor {t['constructor']!r} unknown")
    from repro.streams.app import parallelize

    k = int(t["k"])
    L, kind, tables = fat_tree_links(k)
    H = k ** 3 // 4
    cap = np.full(L, float(t["link_mb_s"]))
    per = int(cfg["hosts_per_tenant"])
    names = [a for a, n in cfg["tenants"].items() for _ in range(int(n))]
    if len(names) * per > H:
        raise ValueError("more tenant hosts than the fabric has")
    rng = np.random.default_rng(seed)
    hosts = rng.permutation(H)
    mk = _apps()
    tenants, rows = [], []
    for j, app in enumerate(names):
        g = parallelize(mk[app](), seed=int(rng.integers(0, 2**63 - 1)))
        mine = hosts[j * per:(j + 1) * per]
        tenants.append((g, mine))
        place = mine[np.arange(g.n_instances) % per]
        for s, d in zip(g.src_of_flow, g.dst_of_flow):
            rows.append(fat_tree_route(k, tables, int(place[s]),
                                       int(place[d])))
    R = np.zeros((len(rows), L), np.float32)
    for f, links in enumerate(rows):
        R[f, links] = 1.0
    return Fabric(R, cap, kind, tenants)


def flow_states(cfg: dict, fab: Fabric, n_states: int, warm_intervals: int
                ) -> list[tuple[np.ndarray, ...]]:
    """``n_states`` flow states of the fabric's tenants, one per control
    interval after ``warm_intervals`` intervals from empty queues: each
    the five FlowState fields ([F] float32) that the plain reference's
    simulation of the tenants under Alg. 1 hands its controller.

    Each tenant is simulated on its own hosts' links at the fabric's
    capacity; the fabric's switch-to-switch links are taken as never
    binding, which is checked: a state whose transfers would load a
    fabric link above its capacity raises."""
    from benchlib import reference

    per = int(cfg["hosts_per_tenant"])
    link = float(cfg["topology"]["link_mb_s"])
    # tenants with the same instance DAG run the same: simulate each once
    uniq: dict = {}
    of_tenant = []
    for g, _ in fab.tenants:
        key = (g.app.name, g.w_out.tobytes())
        of_tenant.append(uniq.setdefault(key, (len(uniq), g))[0])
    graphs = [g for _, g in sorted(uniq.values(), key=lambda t: t[0])]
    s = reference.concat_arrays(
        [reference.testbed_arrays(g, np.arange(g.n_instances) % per, per,
                                  link) for g in graphs])
    offs = np.cumsum([0] + [g.n_flows for g in graphs])
    take = np.concatenate([np.arange(offs[u], offs[u + 1])
                           for u in of_tenant])
    dt = float(cfg["controller_interval_s"])
    tick = float(cfg["dt_s"])
    upd = int(round(dt / tick))
    seen: list = []
    n_ticks = (warm_intervals + 1 + n_states) * upd
    reference.simulate_ref(s, "appaware", n_ticks, tick, upd,
                           float(cfg["qcap_mb"]), reference.Arith("exact"),
                           observe=seen.append)
    states = [tuple(np.asarray(a, np.float32)[take] for a in st)
              for st in seen[warm_intervals + 1:warm_intervals + 1 + n_states]]
    if len(states) != n_states:
        raise RuntimeError(f"simulation gave {len(states)} states, "
                           f"{n_states} asked for")
    load = np.stack([st[2] for st in states]) / dt @ fab.R   # [n, L]
    over = load.max(axis=0) > fab.cap
    if over.any():
        raise ValueError(f"tenants load {int(over.sum())} fabric links "
                         f"above capacity: the states assume none")
    return states
