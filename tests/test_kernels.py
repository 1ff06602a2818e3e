"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,hd,bq,bk", [
    (1, 128, 2, 2, 64, 64, 64),      # MHA
    (2, 256, 4, 2, 64, 128, 128),    # GQA
    (1, 128, 4, 1, 128, 64, 64),     # MQA
    (1, 256, 2, 2, 256, 128, 64),    # big head_dim (gemma), uneven blocks
])
def test_flash_attention_matches_ref(dtype, B, S, Hq, Hkv, hd, bq, bk):
    q = _rand((B, S, Hq, hd), dtype)
    k = _rand((B, S, Hkv, hd), dtype)
    v = _rand((B, S, Hkv, hd), dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    exp = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_sliding_window():
    q = _rand((1, 256, 2, 64), jnp.float32)
    k = _rand((1, 256, 2, 64), jnp.float32)
    v = _rand((1, 256, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, window=64, block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-4, rtol=2e-4)


def test_flash_attention_softcap():
    q = _rand((1, 128, 2, 64), jnp.float32)
    k = _rand((1, 128, 2, 64), jnp.float32)
    v = _rand((1, 128, 2, 64), jnp.float32)
    out = ops.flash_attention(q, k, v, softcap=20.0, block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------- ssm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Bb,S,d,N,bd", [
    (1, 32, 64, 8, 64),
    (2, 64, 128, 16, 64),
    (1, 48, 256, 4, 128),
    (1, 200, 64, 8, 64),      # two lane slabs, zero-padded tail
])
def test_ssm_scan_matches_ref(dtype, Bb, S, d, N, bd):
    u = _rand((Bb, S, d), dtype)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (Bb, S, d)), dtype)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (d, N)), jnp.float32)
    B = _rand((Bb, S, N), dtype)
    C = _rand((Bb, S, N), dtype)
    D = _rand((d,), jnp.float32)
    y, h = ops.ssm_scan(u, dt, A, B, C, D, block_d=bd)
    ye, he = ref.ssm_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h), np.asarray(he),
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssm_scan_with_initial_state():
    Bb, S, d, N = 1, 32, 64, 8
    u = _rand((Bb, S, d), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (Bb, S, d)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (d, N)), jnp.float32)
    B = _rand((Bb, S, N), jnp.float32)
    C = _rand((Bb, S, N), jnp.float32)
    D = _rand((d,), jnp.float32)
    h0 = _rand((Bb, d, N), jnp.float32)
    y, h = ops.ssm_scan(u, dt, A, B, C, D, h0=h0, block_d=64)
    ye, he = ref.ssm_scan_ref(u, dt, A, B, C, D, h0=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------- moe gemm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,M,K,N,bm,bn,bk", [
    (2, 64, 128, 64, 64, 64, 64),
    (4, 128, 256, 128, 64, 64, 128),
    (8, 64, 64, 192, 64, 64, 64),
])
def test_expert_gemm_matches_ref(dtype, E, M, K, N, bm, bn, bk):
    x = _rand((E, M, K), dtype)
    w = _rand((E, K, N), dtype)
    out = ops.expert_gemm(x, w, block_m=bm, block_n=bn, block_k=bk)
    exp = ref.expert_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-3,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-3)


# ------------------------------------------------- model-internal XLA paths
def test_chunked_attention_matches_full():
    """models.attention.chunked_attention is the XLA fallback for long
    sequences — must agree with naive full attention."""
    from repro.configs.base import ModelConfig
    from repro.models.attention import chunked_attention, full_attention
    cfg = ModelConfig(n_heads=4, n_kv_heads=2, head_dim=32)
    q = _rand((2, 256, 4, 32), jnp.float32)
    k = _rand((2, 256, 2, 32), jnp.float32)
    v = _rand((2, 256, 2, 32), jnp.float32)
    out = chunked_attention(q, k, v, cfg, chunk_q=64, chunk_k=64)
    exp = full_attention(q, k, v, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-4,
                               rtol=2e-4)


def test_chunked_selective_scan_matches_sequential():
    """models.ssm.selective_scan (chunked assoc-scan) vs sequential oracle."""
    from repro.models.ssm import selective_scan
    Bb, S, d, N = 2, 128, 64, 8
    u = _rand((Bb, S, d), jnp.float32)
    dt = jnp.asarray(RNG.uniform(1e-3, 0.1, (Bb, S, d)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (d, N)), jnp.float32)
    B = _rand((Bb, S, N), jnp.float32)
    C = _rand((Bb, S, N), jnp.float32)
    D = _rand((d,), jnp.float32)
    y, h = selective_scan(u, dt, A, B, C, D, chunk=32)
    ye, he = ref.ssm_scan_ref(u, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=1e-4,
                               rtol=1e-4)


def test_mlstm_parallel_matches_recurrent():
    """mLSTM chunked-parallel (train) form vs step-by-step recurrence."""
    from repro.models.xlstm import _mlstm_parallel, _mlstm_recurrent_step
    B, H, S, dh = 1, 2, 64, 32
    q = _rand((B, H, S, dh), jnp.float32)
    k = _rand((B, H, S, dh), jnp.float32)
    v = _rand((B, H, S, dh), jnp.float32)
    ig = jnp.asarray(RNG.standard_normal((B, H, S)), jnp.float32)
    fg = jnp.asarray(RNG.standard_normal((B, H, S)) + 2.0, jnp.float32)
    par = _mlstm_parallel(q, k, v, ig, fg, chunk=16)
    state = {"C": jnp.zeros((B, H, dh, dh)), "n": jnp.zeros((B, H, dh)),
             "m": jnp.full((B, H), -1e30)}
    outs = []
    for t in range(S):
        h, state = _mlstm_recurrent_step(
            q[:, :, t:t+1], k[:, :, t:t+1], v[:, :, t:t+1],
            ig[:, :, t:t+1], fg[:, :, t:t+1], state)
        outs.append(h[:, :, 0])
    rec = jnp.stack(outs, axis=2)
    np.testing.assert_allclose(np.asarray(par), np.asarray(rec), atol=1e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------- slstm
@pytest.mark.parametrize("B,S,H,dh,chunk", [
    (1, 32, 2, 16, 8),
    (2, 64, 2, 32, 16),
    (2, 48, 4, 16, 48),
])
def test_slstm_scan_matches_sequential(B, S, H, dh, chunk):
    d = H * dh
    pre = _rand((B, S, 4, d), jnp.float32)
    r = jnp.asarray(RNG.standard_normal((4, H, dh, dh)) * 0.2, jnp.float32)
    zeros = jnp.zeros((B, H, dh))
    minf = jnp.full((B, H, dh), -1e30)
    hs, (cT, nT, mT, hT) = ops.slstm_scan(pre, r, zeros, zeros, minf, zeros,
                                          chunk_t=chunk)

    hs_ref, final = ref.slstm_scan_ref(pre, r, zeros, zeros, minf, zeros)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs_ref),
                               atol=1e-5, rtol=1e-5)
    for got, exp in zip((cT, nT, mT, hT), final):
        np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)


def test_slstm_model_kernel_path_matches_xla_path():
    """The whole xlstm model forward with use_pallas must match the XLA path."""
    import jax as _jax
    from repro.configs import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config("xlstm_1_3b")
    model = build_model(cfg)
    params = model.init(_jax.random.PRNGKey(0))
    toks = _jax.random.randint(_jax.random.PRNGKey(1), (2, 32), 0,
                               cfg.vocab_size)
    a, _, _ = model.forward(params, toks, use_pallas=False)
    b, _, _ = model.forward(params, toks, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=3e-2,
                               rtol=3e-2)
