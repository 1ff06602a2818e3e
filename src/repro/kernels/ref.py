"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Deliberately naive: full materialization, fp32 math — tests sweep shapes and
dtypes asserting allclose(kernel, ref).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0,
                        softcap: float = 0.0):
    """q: [B,S,Hq,hd]; k,v: [B,T,Hkv,hd] -> [B,S,Hq,hd] (GQA grouped)."""
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg, kf) * hd ** -0.5
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, vf)
    return out.reshape(B, S, Hq, hd).astype(q.dtype)


def ssm_scan_ref(u, dt, A, B, C, D, h0=None):
    """Sequential Mamba-1 selective scan, fp32.

    u, dt: [Bb,S,d]; A: [d,N]; B,C: [Bb,S,N]; D: [d].
    Returns (y [Bb,S,d], h_last [Bb,d,N]).
    """
    Bb, S, d = u.shape
    N = A.shape[1]
    u32 = u.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    B32 = B.astype(jnp.float32)
    C32 = C.astype(jnp.float32)
    A32 = A.astype(jnp.float32)
    h = jnp.zeros((Bb, d, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, xs):
        ut, dtt, Bt, Ct = xs
        dA = jnp.exp(dtt[..., None] * A32)          # [Bb,d,N]
        dBx = (dtt * ut)[..., None] * Bt[:, None, :]
        h = h * dA + dBx
        y = jnp.einsum("bdn,bn->bd", h, Ct)
        return h, y

    h, ys = jax.lax.scan(
        step, h, (u32.swapaxes(0, 1), dt32.swapaxes(0, 1),
                  B32.swapaxes(0, 1), C32.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) + u32 * D.astype(jnp.float32)
    return y.astype(u.dtype), h


def expert_gemm_ref(x, w):
    """Grouped expert matmul: x [E,M,K] @ w [E,K,N] -> [E,M,N] (fp32 accum)."""
    return jnp.einsum("emk,ekn->emn", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


def slstm_scan_ref(pre, r_all, c0, n0, m0, h0):
    """Sequential sLSTM recurrence, fp32.

    pre: [B,S,4,d] preactivations; r_all: [4,H,dh,dh]; c0/n0/m0/h0:
    [B,H,dh]. Returns (hs [B,S,d], (cT,nT,mT,hT) [B,H,dh]).
    """
    B, S, _, d = pre.shape
    _, H, dh, _ = r_all.shape
    r32 = r_all.astype(jnp.float32)

    def cell(carry, pre_t):
        c, n, m, h = carry
        rec = jnp.einsum("bhk,ghkl->gbhl", h.reshape(B, H, dh),
                         r32).reshape(4, B, d)
        i = pre_t[:, 0] + rec[0]
        f = pre_t[:, 1] + rec[1]
        z = jnp.tanh(pre_t[:, 2] + rec[2])
        o = jax.nn.sigmoid(pre_t[:, 3] + rec[3])
        logf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(logf + m, i)
        c = c * jnp.exp(logf + m - m_new) + jnp.exp(i - m_new) * z
        n = n * jnp.exp(logf + m - m_new) + jnp.exp(i - m_new)
        h = o * c / jnp.maximum(n, 1e-6)
        return (c, n, m_new, h), h

    carry = tuple(x.astype(jnp.float32).reshape(B, d)
                  for x in (c0, n0, m0, h0))
    carry, hs = jax.lax.scan(cell, carry,
                             pre.astype(jnp.float32).swapaxes(0, 1))
    return (hs.swapaxes(0, 1).astype(pre.dtype),
            tuple(x.reshape(B, H, dh) for x in carry))
