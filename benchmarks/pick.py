"""The tick's per-flow pick ``v[idx]`` on the chip: the index gather against
the one-hot select-and-sum (``simulator._pick``), vmapped over 64 rows as
in a campaign chunk, at F = 137 flows (NEXmark Q5) and I instances.

Each form runs inside a ``fori_loop`` of ``ITERS`` steps whose carry is
the [64, I] vector, so nothing is hoisted; the loop's other work is the
same for every form, and the form ``none``, which reads no flow, times it.
Prints one JSON line per (form, I): the median time of one step over
``REPS`` timed loops, in µs, and per picked element, in ns. Refuses to run
on anything but a TPU.

    PYTHONPATH=src python benchmarks/pick.py
"""
from __future__ import annotations

import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.streams.simulator import _pick

ROWS, FLOWS = 64, 137
INSTS = (26, 256, 2048)   # Q5's I, and two beyond any DAG in the repo
ITERS, REPS = 1200, 7     # one campaign scan's ticks; timed loops per form

FORMS = {"none": lambda v, idx: jnp.broadcast_to(v[0], idx.shape),
         "gather": lambda v, idx: v[idx], "onehot": _pick}


def _loop(form):
    pick = jax.vmap(FORMS[form])

    @jax.jit
    def run(v, idx):
        def step(_, v):
            p = pick(v, idx)                                   # [64, F]
            return v * 0.5 + jnp.mean(p, axis=1, keepdims=True)
        return jax.lax.fori_loop(0, ITERS, step, v)

    return run


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"benchmarks/pick.py times the TPU; found {dev.platform}")
    rng = np.random.default_rng(0)
    for n_insts in INSTS:
        v = jnp.asarray(rng.uniform(0.0, 8.0, (ROWS, n_insts)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, n_insts, (ROWS, FLOWS)), jnp.int32)
        for form in FORMS:
            run = _loop(form)
            run(v, idx).block_until_ready()
            times = []
            for _ in range(REPS):
                t0 = time.perf_counter()
                run(v, idx).block_until_ready()
                times.append(time.perf_counter() - t0)
            step_us = statistics.median(times) / ITERS * 1e6
            print(json.dumps({
                "form": form, "insts": n_insts, "rows": ROWS, "flows": FLOWS,
                "step_us": step_us,
                "ns_per_element": step_us * 1e3 / (ROWS * FLOWS),
                "device": dev.device_kind}), flush=True)


if __name__ == "__main__":
    main()
