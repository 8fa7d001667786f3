"""Faults of a campaign cell's timed path, planted by ``fault_run.py``,
and the small size its CPU runs take."""
import numpy as np

from benchlib import spec

SMALL = {"config": {"n_scenarios": 12, "horizon_s": 60.0, "chunk_rows": 2}}
ROW_FAULTS = ("half_batch", "quarter_batch", "one_bucket", "one_chunk")
FAULTS = ("state_unchanged", *ROW_FAULTS, "answer_altered")


def plant(fault: str, hooks: dict) -> None:
    import jax.numpy as jnp

    from repro.streams import simulator

    if fault == "state_unchanged":
        def tick(sim, Qs, Qr, x, dt, qcap, caps_t=None, enforce=True,
                 R_t=None):
            z = jnp.zeros_like(Qs)
            L = sim.R.shape[1]
            return Qs, Qr, z, z, (jnp.zeros(()), jnp.zeros((1,)), z,
                                  jnp.zeros((L,)))
        simulator._tick = tick
    elif fault == "answer_altered":
        epilogue = simulator._metrics_epilogue

        def altered(*a, **k):
            m = epilogue(*a, **k)
            return m.at[0].multiply(1.0 + 1e-4)
        simulator._metrics_epilogue = altered
    elif fault in ROW_FAULTS:
        def wrap(run_campaign):
            def run(sims, policy, **k):
                cr = run_campaign(sims, policy, **k)
                plan = run_campaign.__self__.plan(sims, policy)
                break_rows(cr.metrics, fault, plan, k["chunk_rows"])
                return cr
            return run
        hooks["wrap_campaign"] = wrap
    elif fault != "none":
        raise ValueError(fault)


def break_rows(m, fault: str, plan, chunk_rows: int) -> None:
    """Plant one of ``ROW_FAULTS`` in a campaign's metric slab ([n, 7])."""
    n = m.shape[0]
    if fault in ("half_batch", "quarter_batch"):
        lo = n // 2 if fault == "half_batch" else n - n // 4
        m[lo:] = m[:lo].mean(axis=0)
        return
    rows = (plan[-1][0] if fault == "one_bucket"
            else spec.load_loop("campaign").chunks(plan, chunk_rows)[-1])
    rows = np.asarray(rows)
    m[rows] = m[np.roll(rows, 1)]
