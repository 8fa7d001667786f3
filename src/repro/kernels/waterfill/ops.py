"""Jitted wrapper for the waterfill kernel: padding, backend selection.

On TPU the Pallas kernel runs compiled; on CPU (the test platform) it runs
in ``interpret=True`` mode, which executes the kernel body per-program in
Python — bit-identical control flow, validated against ``ref.py``. Any
other platform is an error: the kernel is never silently interpreted on
an accelerator.

Padding happens *inside* one jitted function whose pad targets are static
arguments derived from the input shapes, so repeat calls at the same shape
hit the jit cache instead of re-dispatching un-jitted ``jnp.pad`` ops for
both axes on every call.

Two entry points share the kernel:

* :func:`waterfill` — dense per-link [L, F] inputs (the oracle cross-check
  surface: every link may carry its own w/backlog/ρ);
* :func:`waterfill_flows` — per-flow [F] vectors shared by all links (the
  allocator hot path: only the on-link mask is per-link, so the dense
  broadcasts are never materialized).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.waterfill.kernel import waterfill_pallas
from repro.kernels.waterfill.ref import waterfill_ref


def _pad_to(x, n, axis, value=0.0):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("dt", "block_links", "block_flows", "interpret",
                     "Fp", "Lp"))
def _waterfill_padded(weights, backlog, rho, mask, capacity, kind, *,
                      dt, block_links, block_flows, interpret, Fp, Lp):
    L, F = mask.shape
    w, b, r = (jnp.asarray(a, jnp.float32) for a in (weights, backlog, rho))
    if w.ndim == 2:  # dense per-link inputs
        w, b, r = (_pad_to(_pad_to(a, Fp, 1), Lp, 0) for a in (w, b, r))
    else:            # shared per-flow vectors
        w, b, r = (_pad_to(a, Fp, 0) for a in (w, b, r))
    m = _pad_to(_pad_to(jnp.asarray(mask, jnp.float32), Fp, 1), Lp, 0)
    cap = _pad_to(jnp.asarray(capacity, jnp.float32), Lp, 0)
    knd = _pad_to(jnp.asarray(kind, jnp.int32), Lp, 0)
    out = waterfill_pallas(w, b, r, m, cap, knd, dt=dt,
                           block_links=block_links, block_flows=block_flows,
                           interpret=interpret)
    return out[:L, :F]


def _dispatch(weights, backlog, rho, mask, capacity, kind, dt, block_links,
              block_flows, interpret):
    if interpret is None:
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(
                f"waterfill kernel runs compiled on tpu or interpreted on "
                f"cpu; default backend is {backend!r}")
        interpret = backend == "cpu"
    if block_flows is not None:
        assert block_flows % 128 == 0, block_flows
    L, F = mask.shape
    bf = 128 if block_flows is None else block_flows
    Fp = -(-F // bf) * bf
    Lp = -(-L // block_links) * block_links
    return _waterfill_padded(
        weights, backlog, rho, mask, capacity, kind, dt=dt,
        block_links=block_links, block_flows=block_flows,
        interpret=interpret, Fp=Fp, Lp=Lp)


def waterfill(weights, backlog, rho, mask, capacity, kind, dt: float = 1.0,
              block_links: int = 8, block_flows: int | None = None,
              interpret: bool | None = None):
    """Batched per-link allocator solve, dense per-link inputs.

    Shapes: weights/backlog/rho/mask [L, F] + capacity/kind [L];
    returns [L, F]. Padding to lane/block multiples is jit-cached.
    """
    return _dispatch(weights, backlog, rho, mask, capacity, kind, dt,
                     block_links, block_flows, interpret)


def waterfill_flows(weights, backlog, rho, mask, capacity, kind,
                    dt: float = 1.0, block_links: int = 8,
                    block_flows: int | None = None,
                    interpret: bool | None = None):
    """Batched per-link solve with *shared* per-flow inputs.

    weights/backlog/rho: [F] (the same flow state is visible to every
    link); mask: [L, F]; capacity/kind: [L]. Returns [L, F]. Equivalent to
    :func:`waterfill` on ``jnp.broadcast_to(v, (L, F))`` inputs without
    ever materializing the broadcasts.
    """
    assert weights.ndim == 1, weights.shape
    return _dispatch(weights, backlog, rho, mask, capacity, kind, dt,
                     block_links, block_flows, interpret)


def waterfill_reference(weights, backlog, rho, mask, capacity, kind,
                        dt: float = 1.0):
    return waterfill_ref(weights, backlog, rho, mask, capacity, kind, dt)
