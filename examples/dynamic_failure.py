"""In-run network dynamics demo: a mid-run link failure and recovery,
TCP vs the paper's app-aware allocator.

Links 0-3 drop to 10% capacity at t=50s and recover at t=70s — *inside*
one simulation run (a `LinkSchedule`, evaluated per tick in the scan).
The interesting regime is the transient: how deep does throughput dip,
how fast does each policy recover, and who ends up better off after the
event (the paper's Fig. 5/12 question, which a static capacity grid can
never ask).

    PYTHONPATH=src python examples/dynamic_failure.py
"""
from __future__ import annotations

from repro.compile_cache import setup_compile_cache
from repro.net import big_switch, link_failure_schedule
from repro.streams import (
    compile_sim,
    parallelize,
    round_robin,
    simulate,
    trending_topics,
)

T_FAIL, T_RECOVER = 50.0, 70.0
SECONDS = 120.0


def main() -> None:
    setup_compile_cache()
    g = parallelize(trending_topics(), seed=0)
    topo = big_switch(8, 1.25)
    sched = link_failure_schedule(topo, [0, 1, 2, 3], t_fail=T_FAIL,
                                  t_recover=T_RECOVER, degrade=0.1)
    sim = compile_sim(g, topo, round_robin(g, 8), schedule=sched)

    print(f"{'policy':10s} {'tput t/s':>9s} {'post-event':>11s} "
          f"{'dip':>6s} {'recovery s':>11s}")
    for policy in ("tcp", "appaware"):
        r = simulate(sim, policy, seconds=SECONDS, dt=0.5)
        i = int(T_FAIL / r.dt)
        post = float(r.sink_mb[i:].mean() / r.dt * r.tuples_per_mb)
        print(f"{policy:10s} {r.throughput_tps:9.1f} {post:11.1f} "
              f"{r.dip_depth(T_FAIL):6.2f} {r.recovery_time_s(T_FAIL):11.1f}")


if __name__ == "__main__":
    main()
