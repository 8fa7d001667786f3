"""Pallas TPU flash attention (prefill/train path, GQA-aware).

Canonical online-softmax tiling for the MXU:
  grid = (B·H, S/BQ, T/BK) with the KV dimension innermost ("arbitrary"
  semantics). Per (b,h,qblk): f32 scratch accumulators (acc [BQ,hd],
  m/l [BQ,1]) persist across KV steps; initialized at kv==0 and written out
  (acc/l) at the last KV step. Causal programs where the whole KV block is
  masked are skipped via ``pl.when`` wrapping the compute.

Block sizes default to MXU-aligned 128×128 tiles; VMEM per program =
BQ·hd + 2·BK·hd + BQ·BK f32 ≈ 0.2 MB at defaults — far under the ~16 MB
VMEM budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, causal: bool, bq: int, bk: int,
                  n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip programs with no unmasked key (kv block fully after q blk)
    run = (not causal) or (ki * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # [BQ, hd]
        k = k_ref[0].astype(jnp.float32)                   # [BK, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [BQ, BK]
        if causal:
            rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]                                # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # [BQ, BK]
        corr = jnp.exp(m_prev - m_new)                     # [BQ, 1]
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, causal: bool = True, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: [B,H,S,hd]; k,v: [B,K,T,hd] with H = K·G. Returns [B,H,S,hd].

    KV heads are indexed via the grid (no repeat materialization).
    """
    B, H, S, hd = q.shape
    _, K, T, _ = k.shape
    G = H // K
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    grid = (B * H, S // bq, T // bk)
    scale = 1.0 / math.sqrt(hd)

    qs = pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0))
    ks = pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // G, j, 0))
    out = pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0))

    o = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, n_kv=T // bk),
        grid=grid,
        in_specs=[qs, ks, ks],
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q.reshape(B * H, S, hd), k.reshape(B * K, T, hd),
      v.reshape(B * K, T, hd))
    return o.reshape(B, H, S, hd)
