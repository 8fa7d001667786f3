"""§VII demo: five applications with 1..5 flows sharing one bottleneck.
TCP's flow-level fairness hands the many-flow app the biggest share;
App-Fair's EWMA grouping + strict priority + displacement equalizes the
apps (paper: Jain 0.84 -> 0.98+).

    PYTHONPATH=src python examples/multiapp_fairness.py
"""
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import setup_compile_cache
from repro.core import AppFairScheduler, jain_index, maxmin_rates


def main() -> None:
    setup_compile_cache()
    n_apps = 5
    app_of_flow = np.concatenate([[a] * (a + 1) for a in range(n_apps)])
    F = len(app_of_flow)
    R = jnp.ones((F, 1), jnp.float32)
    cap = jnp.array([100.0])

    x = np.asarray(maxmin_rates(R, cap))
    tcp = np.array([x[app_of_flow == a].sum() for a in range(n_apps)])
    print("TCP     per-app Mb/s:", np.round(tcp, 1),
          " Jain:", round(float(jain_index(jnp.asarray(tcp))), 3))

    for alpha in (0.25, 0.5, 0.75, 1.0):
        sched = AppFairScheduler(n_apps, alpha=alpha, n_groups=5)
        state = sched.init()
        total = np.zeros(n_apps)
        prev = np.zeros(n_apps, np.float32)
        T = 60
        for _ in range(T):
            state, xf = sched.step(state, jnp.asarray(prev), R, cap,
                                   jnp.asarray(app_of_flow))
            xn = np.asarray(xf)
            per = np.array([xn[app_of_flow == a].sum()
                            for a in range(n_apps)])
            total += per
            prev = per.astype(np.float32)
        avg = total / T
        print(f"App-Fair(α={alpha:4.2f}) per-app:", np.round(avg, 1),
              " Jain:", round(float(jain_index(jnp.asarray(avg))), 3))


if __name__ == "__main__":
    main()
