"""Fig. 13 (§VII): application-level fairness among 5 competing apps with
1..5 flows each. Paper: Jain index — TCP 0.84; App-Fair 0.98–0.99 across
α ∈ {0.25, 0.5, 0.75, 1.0} at Δt = 10 s."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit
from repro.compile_cache import setup_compile_cache
from repro.core import AppFairScheduler, jain_index, maxmin_rates


def run(seconds: int = 600, dt_alloc: float = 10.0) -> list[dict]:
    n_apps = 5
    app_of_flow = np.concatenate([[a] * (a + 1) for a in range(n_apps)])
    F = len(app_of_flow)
    R = jnp.ones((F, 1), jnp.float32)
    cap = jnp.array([100.0])
    x_tcp = np.asarray(maxmin_rates(R, cap))
    tcp_app = np.array([x_tcp[app_of_flow == a].sum() for a in range(n_apps)])
    j_tcp = float(jain_index(jnp.asarray(tcp_app)))

    rows = [{
        "name": "fig13_fairness_TCP",
        "jain": round(j_tcp, 3),
        "per_app": "/".join(f"{t:.0f}" for t in tcp_app),
    }]
    intervals = int(seconds / dt_alloc)
    for alpha in (0.25, 0.5, 0.75, 1.0):
        sched = AppFairScheduler(n_apps, alpha=alpha, n_groups=5)
        state = sched.init()
        aof = jnp.asarray(app_of_flow)
        total = np.zeros(n_apps)
        prev = np.zeros(n_apps, np.float32)
        for _ in range(intervals):
            state, x = sched.step(state, jnp.asarray(prev), R, cap, aof)
            xn = np.asarray(x)
            per = np.array([xn[app_of_flow == a].sum()
                            for a in range(n_apps)])
            total += per
            prev = per.astype(np.float32)
        j = float(jain_index(jnp.asarray(total / intervals)))
        rows.append({
            "name": f"fig13_fairness_AppFair_alpha{alpha}",
            "jain": round(j, 3),
            "per_app": "/".join(f"{t:.0f}" for t in total / intervals),
        })
    return rows


def main() -> None:
    emit(run(), "fig13")


if __name__ == "__main__":
    setup_compile_cache()
    main()
