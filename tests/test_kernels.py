"""Per-kernel validation (assignment: sweep shapes/dtypes, assert_allclose
against the pure-jnp ref.py oracle; interpret mode executes the kernel body
on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.ops import (
    flash_attention,
    flash_attention_reference,
)
from repro.kernels.ssd_scan.ops import ssd_scan, ssd_reference
from repro.kernels.waterfill.ops import (
    waterfill,
    waterfill_flows,
    waterfill_reference,
)


# ------------------------------------------------------------- waterfill
class TestWaterfill:
    @pytest.mark.parametrize("L,F", [(4, 16), (10, 37), (32, 128), (7, 200)])
    @pytest.mark.parametrize("dt", [0.5, 1.0, 5.0])
    def test_matches_oracle(self, L, F, dt):
        rng = np.random.default_rng(L * F)
        w = rng.uniform(0, 20, (L, F)).astype(np.float32)
        bl = rng.uniform(0, 30, (L, F)).astype(np.float32)
        rho = rng.uniform(0.1, 10, (L, F)).astype(np.float32)
        mask = (rng.random((L, F)) < 0.7).astype(np.float32)
        cap = rng.uniform(1, 50, L).astype(np.float32)
        kind = rng.integers(0, 2, L).astype(np.int32)
        out = np.asarray(waterfill(w, bl, rho, mask, cap, kind, dt=dt))
        ref = np.asarray(waterfill_reference(
            *(jnp.asarray(a) for a in (w, bl, rho, mask, cap, kind)), dt))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_property_feasible(self, seed):
        rng = np.random.default_rng(seed)
        L, F = int(rng.integers(1, 12)), int(rng.integers(2, 64))
        w = rng.uniform(0, 20, (L, F)).astype(np.float32)
        bl = rng.uniform(0, 30, (L, F)).astype(np.float32)
        rho = rng.uniform(0.1, 10, (L, F)).astype(np.float32)
        mask = (rng.random((L, F)) < 0.8).astype(np.float32)
        cap = rng.uniform(1, 50, L).astype(np.float32)
        kind = rng.integers(0, 2, L).astype(np.int32)
        out = np.asarray(waterfill(w, bl, rho, mask, cap, kind))
        assert out.min() >= -1e-5
        assert np.all(out * (1 - mask) == 0)
        has = mask.sum(1) > 0
        np.testing.assert_allclose(out.sum(1)[has], cap[has], rtol=1e-3)

    def test_all_zero_demand(self):
        # zero backlog everywhere (downlink) / zero weight (uplink): the
        # bisection and the exact sort must agree on the degenerate fills
        L, F = 6, 32
        z = np.zeros((L, F), np.float32)
        rho = np.full((L, F), 2.0, np.float32)
        mask = np.ones((L, F), np.float32)
        cap = np.full(L, 12.0, np.float32)
        kind = np.arange(L, dtype=np.int32) % 2
        out = np.asarray(waterfill(z, z, rho, mask, cap, kind, dt=1.0))
        ref = np.asarray(waterfill_reference(
            *(jnp.asarray(a) for a in (z, z, rho, mask, cap, kind)), 1.0))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        # work conservation even with no demand signal
        np.testing.assert_allclose(out.sum(1), cap, rtol=1e-3)

    def test_single_flow_takes_link(self):
        # one masked flow per link: it gets the whole capacity on both kinds
        L, F = 4, 16
        rng = np.random.default_rng(7)
        w = rng.uniform(0.1, 5, (L, F)).astype(np.float32)
        bl = rng.uniform(0, 10, (L, F)).astype(np.float32)
        rho = rng.uniform(0.5, 4, (L, F)).astype(np.float32)
        mask = np.zeros((L, F), np.float32)
        keep = rng.integers(0, F, L)
        mask[np.arange(L), keep] = 1.0
        cap = rng.uniform(1, 20, L).astype(np.float32)
        kind = np.array([0, 1, 0, 1], np.int32)
        out = np.asarray(waterfill(w, bl, rho, mask, cap, kind, dt=0.5))
        ref = np.asarray(waterfill_reference(
            *(jnp.asarray(a) for a in (w, bl, rho, mask, cap, kind)), 0.5))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(out[np.arange(L), keep], cap, rtol=1e-3)

    def test_vector_inputs_match_dense(self):
        # waterfill_flows([F] vectors) == waterfill on the dense broadcasts
        rng = np.random.default_rng(3)
        L, F = 10, 150
        w = rng.uniform(0, 20, F).astype(np.float32)
        bl = rng.uniform(0, 30, F).astype(np.float32)
        rho = rng.uniform(0.1, 10, F).astype(np.float32)
        mask = (rng.random((L, F)) < 0.6).astype(np.float32)
        cap = rng.uniform(1, 50, L).astype(np.float32)
        kind = rng.integers(0, 2, L).astype(np.int32)
        dense = lambda v: np.broadcast_to(v[None, :], (L, F)).copy()
        out_v = np.asarray(waterfill_flows(w, bl, rho, mask, cap, kind,
                                           dt=0.5))
        out_d = np.asarray(waterfill(dense(w), dense(bl), dense(rho), mask,
                                     cap, kind, dt=0.5))
        np.testing.assert_allclose(out_v, out_d, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("block_flows", [128, 256])
    def test_block_flows_tiling_independence(self, block_flows):
        # chunked flow-axis traversal must not change the solve
        rng = np.random.default_rng(4)
        L, F = 8, 300
        w = rng.uniform(0, 20, (L, F)).astype(np.float32)
        bl = rng.uniform(0, 30, (L, F)).astype(np.float32)
        rho = rng.uniform(0.1, 10, (L, F)).astype(np.float32)
        mask = (rng.random((L, F)) < 0.7).astype(np.float32)
        cap = rng.uniform(1, 50, L).astype(np.float32)
        kind = rng.integers(0, 2, L).astype(np.int32)
        a = np.asarray(waterfill(w, bl, rho, mask, cap, kind, dt=1.0))
        b = np.asarray(waterfill(w, bl, rho, mask, cap, kind, dt=1.0,
                                 block_flows=block_flows))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_interprets_only_on_cpu(self, monkeypatch):
        # compiled on tpu, interpreted on cpu, refused anywhere else: the
        # kernel is never silently interpreted on an accelerator
        a = np.ones((4, 16), np.float32)
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            waterfill(a, a, a, a, np.ones(4, np.float32),
                      np.zeros(4, np.int32))

    def test_padding_is_jit_cached(self):
        # repeat same-shape calls reuse the padded executable (the pad ops
        # trace once; no per-call un-jitted jnp.pad dispatch chain)
        from repro.kernels.waterfill.ops import _waterfill_padded

        rng = np.random.default_rng(5)
        L, F = 6, 37
        args = (rng.uniform(0, 5, (L, F)).astype(np.float32),
                rng.uniform(0, 5, (L, F)).astype(np.float32),
                rng.uniform(0.1, 5, (L, F)).astype(np.float32),
                np.ones((L, F), np.float32),
                rng.uniform(1, 9, L).astype(np.float32),
                np.zeros(L, np.int32))
        waterfill(*args, dt=1.0)
        size = _waterfill_padded._cache_size()
        for _ in range(3):
            waterfill(*args, dt=1.0)
        assert _waterfill_padded._cache_size() == size

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_property_parity_random(self, seed):
        # randomized (backlog, rho, mask, capacity): bisection == exact sort
        rng = np.random.default_rng(seed)
        L, F = int(rng.integers(1, 10)), int(rng.integers(1, 80))
        w = rng.uniform(0, 20, (L, F)).astype(np.float32)
        bl = rng.uniform(0, 30, (L, F)).astype(np.float32)
        rho = rng.uniform(0.05, 10, (L, F)).astype(np.float32)
        mask = (rng.random((L, F)) < 0.6).astype(np.float32)
        cap = rng.uniform(0.5, 50, L).astype(np.float32)
        kind = rng.integers(0, 2, L).astype(np.int32)
        dt = float(rng.choice([0.5, 1.0, 5.0]))
        out = np.asarray(waterfill(w, bl, rho, mask, cap, kind, dt=dt))
        ref = np.asarray(waterfill_reference(
            *(jnp.asarray(a) for a in (w, bl, rho, mask, cap, kind)), dt))
        np.testing.assert_allclose(out, ref, rtol=5e-4, atol=5e-4)


# -------------------------------------------------------- flash attention
class TestFlashAttention:
    @pytest.mark.parametrize("B,S,T,H,K,hd", [
        (2, 128, 128, 4, 2, 64),
        (1, 256, 256, 8, 8, 32),
        (1, 128, 128, 6, 3, 64),     # non-pow2 head count (whisper-like)
        (2, 64, 64, 4, 1, 128),      # MQA
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, B, S, T, H, K, hd, causal):
        rng = np.random.default_rng(S * H)
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, K, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, K, hd)), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        ref = flash_attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), dtype)
        k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), dtype)
        v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), dtype)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        ref = flash_attention_reference(q, k, v)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol)
        assert out.dtype == dtype

    def test_block_shape_independence(self):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
        a = flash_attention(q, k, v, block_q=64, block_k=64)
        b = flash_attention(q, k, v, block_q=128, block_k=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- ssd scan
class TestSSDScan:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (2, 128, 4, 32, 16, 32),
        (1, 256, 2, 64, 32, 64),
        (2, 64, 3, 16, 8, 64),
        (1, 128, 8, 64, 128, 128),   # mamba2-370m-like head
    ])
    def test_matches_sequential_reference(self, B, S, H, P, N, chunk):
        rng = np.random.default_rng(S + H)
        x = jnp.asarray(rng.standard_normal((B, S, H, P)) * 0.5, jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
        y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
        yr, hr = ssd_reference(x, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                                   rtol=1e-4, atol=1e-4)

    def test_chunk_independence(self):
        rng = np.random.default_rng(5)
        B, S, H, P, N = 1, 256, 2, 32, 16
        x = jnp.asarray(rng.standard_normal((B, S, H, P)) * 0.5, jnp.float32)
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, H)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2.0, (H,)), jnp.float32)
        Bm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
        Cm = jnp.asarray(rng.standard_normal((B, S, N)) * 0.5, jnp.float32)
        y32, _ = ssd_scan(x, dt, A, Bm, Cm, chunk=32)
        y128, _ = ssd_scan(x, dt, A, Bm, Cm, chunk=128)
        np.testing.assert_allclose(np.asarray(y32), np.asarray(y128),
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------- model block vs kernel oracle
def test_mamba2_block_matches_ssd_reference():
    """blocks.mamba2_forward's chunked jnp path must equal the sequential
    oracle when fed the same pre-activations (cross-check of the model)."""
    from repro.models.lm import ModelConfig
    from repro.models import blocks as Bl

    cfg = ModelConfig(name="t", family="ssm", n_layers=1, d_model=32,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab=16,
                      ssm_state=16, ssm_head_dim=16, ssm_expand=2,
                      dtype=jnp.float32, ssd_chunk=16)
    key = jax.random.PRNGKey(0)
    p = Bl.build_params(key, Bl.mamba2_specs(32, 16, 16, 2, 4))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 32)) * 0.3
    y1, _ = Bl.mamba2_forward(p, x, cfg, chunk=16)
    y2, _ = Bl.mamba2_forward(p, x, cfg, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
