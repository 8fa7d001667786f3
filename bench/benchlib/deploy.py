"""Pieces the deployments share (``bench/deployments/<name>.py``).

A deployment draws its parameters from its configuration's recipe and the
seed itself, so the yardstick does not move when the program's own
scenario generators change: the same seed gives the same deployment, and
every seed gives the same shapes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchlib import reference


def app(name: str):
    """A new instance of the program's Sec. VI app ``name``: the function
    of that name in ``repro.streams.workloads``."""
    from repro.streams import workloads

    make = getattr(workloads, name, None)
    # a function defined there, not a name it imports
    if name.startswith("_") or \
            getattr(make, "__module__", None) != workloads.__name__:
        raise ValueError(f"no app {name!r} in repro.streams.workloads")
    return make()


@dataclasses.dataclass
class Fabric:
    """A fabric with its tenants' flows routed on it."""

    R: np.ndarray          # [F, L] float32: flow f crosses link l
    cap: np.ndarray        # [L] MB/s
    kind: np.ndarray       # [L] int32: 0 host uplink, 1 host downlink,
                           #            2 switch-to-switch
    tenants: list          # (app instance DAG, its hosts) per tenant


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


def simulated_states(cfg: dict, graphs: list, n_hosts: int,
                     link_mb_s: float, n_states: int, warm_intervals: int
                     ) -> list[tuple[np.ndarray, ...]]:
    """``n_states`` flow states of ``graphs`` side by side, one per control
    interval after ``warm_intervals`` intervals from empty queues: each the
    five FlowState fields ([sum of F] float32) that the plain reference's
    simulation under Alg. 1 hands its controller. Each graph runs alone
    on ``n_hosts`` machines behind one switch, placed by Storm's even
    scheduler, every machine link at ``link_mb_s``; the configuration
    gives the tick, the controller interval and the queue capacity."""
    s = reference.concat_arrays(
        [reference.testbed_arrays(g, np.arange(g.n_instances) % n_hosts,
                                  n_hosts, link_mb_s) for g in graphs])
    dt = float(cfg["controller_interval_s"])
    tick = float(cfg["dt_s"])
    upd = int(round(dt / tick))
    seen: list = []
    n_ticks = (warm_intervals + 1 + n_states) * upd
    reference.simulate_ref(s, "appaware", n_ticks, tick, upd,
                           float(cfg["qcap_mb"]), reference.Arith("exact"),
                           observe=seen.append)
    states = [tuple(np.asarray(a, np.float32) for a in st)
              for st in seen[warm_intervals + 1:warm_intervals + 1 + n_states]]
    if len(states) != n_states:
        raise RuntimeError(f"simulation gave {len(states)} states, "
                           f"{n_states} asked for")
    return states
