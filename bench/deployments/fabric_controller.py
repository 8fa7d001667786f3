"""A ``fabric_controller`` deployment: a k-ary fat-tree (Al-Fares et al.
2008) with Storm tenants routed on it, and the flow states its online
controller is handed. What the controller loop calls: :func:`fabric` and
:func:`flow_states`."""
from __future__ import annotations

import numpy as np

from benchlib import deploy


def fabric(cfg: dict, seed: int, traffic: dict) -> deploy.Fabric:
    """The fabric with its tenants: every tenant app is parallelized with
    its own seed and placed by Storm's even scheduler on
    ``hosts_per_tenant`` hosts of a seeded permutation of all hosts, one
    tenant per host."""
    t = cfg["topology"]
    if t["constructor"] != "fat_tree":
        raise ValueError(f"fabric constructor {t['constructor']!r} unknown")
    from repro.streams.app import parallelize

    k = int(t["k"])
    L, kind, tables = deploy.fat_tree_links(k)
    H = k ** 3 // 4
    cap = np.full(L, float(t["link_mb_s"]))
    per = int(cfg["hosts_per_tenant"])
    names = [a for a, n in cfg["tenants"].items() for _ in range(int(n))]
    if len(names) * per > H:
        raise ValueError("more tenant hosts than the fabric has")
    rng = np.random.default_rng(seed)
    hosts = rng.permutation(H)
    tenants, rows = [], []
    for j, name in enumerate(names):
        g = parallelize(deploy.app(name),
                        seed=int(rng.integers(0, 2**63 - 1)))
        mine = hosts[j * per:(j + 1) * per]
        tenants.append((g, mine))
        place = mine[np.arange(g.n_instances) % per]
        for s, d in zip(g.src_of_flow, g.dst_of_flow):
            rows.append(deploy.fat_tree_route(k, tables, int(place[s]),
                                              int(place[d])))
    R = np.zeros((len(rows), L), np.float32)
    for f, links in enumerate(rows):
        R[f, links] = 1.0
    return deploy.Fabric(R, cap, kind, tenants)


def flow_states(cfg: dict, fab: deploy.Fabric, traffic: dict
                ) -> list[tuple[np.ndarray, ...]]:
    """The traffic's ``n_states`` flow states of the fabric's tenants
    after its ``warm_intervals`` (:func:`deploy.simulated_states`).

    Each tenant is simulated on its own hosts' links at the fabric's
    capacity; the fabric's switch-to-switch links are taken as never
    binding, which is checked: a state whose transfers would load a
    fabric link above its capacity raises."""
    per = int(cfg["hosts_per_tenant"])
    link = float(cfg["topology"]["link_mb_s"])
    # tenants with the same instance DAG run the same: simulate each once
    uniq: dict = {}
    of_tenant = []
    for g, _ in fab.tenants:
        key = (g.app.name, g.w_out.tobytes())
        of_tenant.append(uniq.setdefault(key, (len(uniq), g))[0])
    graphs = [g for _, g in sorted(uniq.values(), key=lambda t: t[0])]
    offs = np.cumsum([0] + [g.n_flows for g in graphs])
    take = np.concatenate([np.arange(offs[u], offs[u + 1])
                           for u in of_tenant])
    states = [tuple(a[take] for a in st) for st in deploy.simulated_states(
        cfg, graphs, per, link, int(traffic["n_states"]),
        int(traffic["warm_intervals"]))]
    dt = float(cfg["controller_interval_s"])
    load = np.stack([st[2] for st in states]) / dt @ fab.R   # [n, L]
    over = load.max(axis=0) > fab.cap
    if over.any():
        raise ValueError(f"tenants load {int(over.sum())} fabric links "
                         f"above capacity: the states assume none")
    return states
