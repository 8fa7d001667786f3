"""Faults of a controller cell's timed path, planted by ``fault_run.py``,
and the small size its CPU runs take."""
import numpy as np

SMALL = {"traffic": {"n_states": 32}}
FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def plant(fault: str, hooks: dict) -> None:
    from repro.core import allocator

    if fault == "state_unchanged":
        allocator.backfill = lambda x, program, iters=8, damping=0.9: x
    elif fault in ("half_batch", "answer_altered"):
        def wrap(solve):
            def run(state):
                x = np.array(solve(state))
                if fault == "half_batch":
                    x[x.shape[0] // 2:] = x[:x.shape[0] // 2].mean()
                else:
                    x[np.argmax(x)] *= 1.0 + 1e-3
                return x
            return run
        hooks["wrap_solve"] = wrap
    elif fault != "none":
        raise ValueError(fault)
