"""Mamba-1 selective-scan mixer (jamba's SSM layers).

TPU adaptation of the CUDA selective-scan kernel: the recurrence is evaluated
as a *chunked* scan — `lax.scan` over time chunks carrying the [B, d_inner, N]
state, with an associative scan inside each chunk. This bounds live memory to
one chunk (the CUDA kernel's SRAM tiling ↦ our VMEM chunking; see DESIGN.md)
and is remat-friendly: the backward pass keeps only chunk-boundary states.

The Pallas kernel (kernels/ssm_scan.py) implements the same chunking with the
state resident in VMEM; this file is the pure-jnp oracle path used for
training on CPU and for dry-run lowering.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.layers import dtype_of, rmsnorm, rmsnorm_init

SSM_CHUNK = 64


def d_inner_of(cfg: ModelConfig) -> int:
    return cfg.ssm.expand * cfg.d_model


def dt_rank_of(cfg: ModelConfig) -> int:
    return cfg.ssm.dt_rank or -(-cfg.d_model // 16)


def ssm_init(key, cfg: ModelConfig):
    s = cfg.ssm
    dt = dtype_of(cfg)
    d, din, n = cfg.d_model, d_inner_of(cfg), s.d_state
    dtr = dt_rank_of(cfg)
    ks = jax.random.split(key, 8)
    return {
        "in_proj": (jax.random.normal(ks[0], (d, 2 * din)) * d ** -0.5).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, din)) * s.d_conv ** -0.5).astype(dt),
        "conv_b": jnp.zeros((din,), dt),
        "x_dt": (jax.random.normal(ks[2], (din, dtr)) * din ** -0.5).astype(dt),
        "x_b": (jax.random.normal(ks[3], (din, n)) * din ** -0.5).astype(dt),
        "x_c": (jax.random.normal(ks[4], (din, n)) * din ** -0.5).astype(dt),
        "dt_proj": (jax.random.normal(ks[5], (dtr, din)) * dtr ** -0.5).astype(dt),
        "dt_bias": jnp.log(jnp.expm1(  # softplus^-1 of dt in [1e-3, 1e-1]
            jnp.exp(jax.random.uniform(ks[6], (din,),
                                       minval=jnp.log(1e-3), maxval=jnp.log(1e-1))))),
        "a_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), (din, 1))),
        "ssm_d": jnp.ones((din,), jnp.float32),
        "out_proj": (jax.random.normal(ks[7], (din, d)) * din ** -0.5).astype(dt),
        **({"dt_norm": rmsnorm_init(dtr), "b_norm": rmsnorm_init(n),
            "c_norm": rmsnorm_init(n)} if s.inner_norms else {}),
    }


def causal_conv1d(x, w, b, state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv. x: [B,S,din], w: [K,din]. state: [B,K-1,din].

    Returns (y, new_state) where new_state holds the last K-1 inputs.
    """
    K = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):] if K > 1 else jnp.zeros_like(pad)
    return y, new_state


def _chunk_scan(dA, dBx, h0):
    """First-order recurrence h_t = exp(dA_t)·h_{t-1} + dBx_t within a chunk.

    dA, dBx: [B, L, din, N] (fp32); h0: [B, din, N]. Returns (h_all, h_last).
    Uses an associative scan over (log-decay, value) pairs.
    """
    def op(a, b):
        (la, xa), (lb, xb) = a, b
        return la + lb, xa * jnp.exp(lb) + xb

    logdec, vals = jax.lax.associative_scan(op, (dA, dBx), axis=1)
    h_all = vals + jnp.exp(logdec) * h0[:, None]
    return h_all, h_all[:, -1]


def selective_scan(u, dt, A, B, C, D, h0=None, chunk: int = SSM_CHUNK):
    """u: [B,S,din]; dt: [B,S,din]; A: [din,N]; B,C: [B,S,N]; D: [din].

    Returns (y [B,S,din], h_last [B,din,N]), both fp32: all math fp32, and
    the caller rounds y once, after its gate. A length that is not a
    multiple of the chunk is padded at its end with dt = 0 and u = 0: such
    a step leaves h as it is (exp(0 * A) = 1, dt * u * B = 0), and its
    outputs are dropped.
    """
    Bb, S, din = u.shape
    N = A.shape[1]
    u32, dt32 = u.astype(jnp.float32), dt.astype(jnp.float32)
    B32, C32 = B.astype(jnp.float32), C.astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((Bb, din, N), jnp.float32)
    chunk = min(chunk, S)
    nchunks = -(-S // chunk)
    pad = nchunks * chunk - S
    if pad:
        u32, dt32, B32, C32 = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                               for a in (u32, dt32, B32, C32))

    def step(h, xs):
        uc, dtc, Bc, Cc = xs  # [B, chunk, ...]
        dA = dtc[..., None] * A  # [B,L,din,N]
        dBx = (dtc * uc)[..., None] * Bc[:, :, None, :]
        h_all, h_last = _chunk_scan(dA, dBx, h)
        yc = jnp.einsum("blhn,bln->blh", h_all, Cc)
        return h_last, yc

    xs = tuple(
        a.reshape(Bb, nchunks, chunk, *a.shape[2:]).swapaxes(0, 1)
        for a in (u32, dt32, B32, C32)
    )
    h_last, ys = jax.lax.scan(step, h0, xs)
    y = ys.swapaxes(0, 1).reshape(Bb, nchunks * chunk, din)[:, :S]
    y = y + u32[:, :S] * D
    return y, h_last


def ssm_apply(params, x, cfg: ModelConfig,
              state: Optional[Dict] = None, return_state: bool = False,
              use_pallas: bool = False):
    """Mamba mixer. x: [B,S,d]. state: {"conv": [B,K-1,din], "h": [B,din,N]}.

    Returns (y, new_state|None).
    """
    B, S, d = x.shape
    din, n = d_inner_of(cfg), cfg.ssm.d_state
    xz = x @ params["in_proj"]
    xz = constrain(xz, "batch", "seq", "ssm_inner")
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_state = state["conv"] if state is not None else None
    f32 = jnp.float32
    # the taps, the activation and the gate in float32, rounded once before
    # each projection, as the fused Mamba kernels compute them
    xi, new_conv = causal_conv1d(
        xi.astype(f32), params["conv_w"].astype(f32),
        params["conv_b"].astype(f32),
        None if conv_state is None else conv_state.astype(f32))
    xi = jax.nn.silu(xi).astype(x.dtype)

    dt_in = xi @ params["x_dt"]
    Bm = xi @ params["x_b"]
    Cm = xi @ params["x_c"]
    if cfg.ssm.inner_norms:
        dt_in = rmsnorm(params["dt_norm"], dt_in, cfg.norm_eps)
        Bm = rmsnorm(params["b_norm"], Bm, cfg.norm_eps)
        Cm = rmsnorm(params["c_norm"], Cm, cfg.norm_eps)
    dt = jax.nn.softplus(dt_in @ params["dt_proj"] + params["dt_bias"])
    A = -jnp.exp(params["a_log"])
    h0 = state["h"] if state is not None else None
    if use_pallas and S > 1:
        from repro.kernels.ops import ssm_scan as pallas_scan
        # u in fp32, so that the kernel writes y in fp32 too
        y, h_last = pallas_scan(xi.astype(f32), dt, A, Bm, Cm,
                                params["ssm_d"], h0=h0)
    else:
        y, h_last = selective_scan(xi, dt, A, Bm, Cm, params["ssm_d"], h0=h0)
    y = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype)
    out = y @ params["out_proj"]
    out = constrain(out, "batch", "seq", "embed")
    new_state = None
    if return_state or state is not None:
        new_state = {"conv": new_conv.astype(x.dtype), "h": h_last}
    return out, new_state


def init_ssm_state(cfg: ModelConfig, batch: int):
    din, n = d_inner_of(cfg), cfg.ssm.d_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, din), dtype_of(cfg)),
        "h": jnp.zeros((batch, din, n), jnp.float32),
    }


def ssm_state_spec(cfg: ModelConfig, batch: int):
    din, n = d_inner_of(cfg), cfg.ssm.d_state
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.ssm.d_conv - 1, din), dtype_of(cfg)),
        "h": jax.ShapeDtypeStruct((batch, din, n), jnp.float32),
    }
