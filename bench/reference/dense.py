"""Weights from a seed, and a float32 reference forward, for a dense
decoder of the Phi-3 / Phi-4-mini form (arXiv:2503.01743; the published
``modeling_phi3``): pre-norm RMSNorm blocks, grouped-query attention with
rotary embedding on the first ``rope_dims`` of each head (rotate-half
pairing, no bias), SwiGLU feed-forward, tied input and output embedding.

Departures from the published model, shared with the program: LongRoPE's
per-frequency short factors and its attention scaling are left out (plain
RoPE with ``rope_theta``); weights are random.

The weights are drawn here in this module's own layout (rotate-half order
of the rotary dimensions). What the program is given is the same numbers
after the permutation a checkpoint converter applies to its
interleaved-pair rotary layout; this reference never sees the program.
Norm scales are float32, every matrix bfloat16 as served; the reference
reads the same bfloat16 values in float32, one layer at a time.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_dims: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def of(cls, doc: dict) -> "Shapes":
        """From a configuration file's published keys."""
        hd = doc["hidden_size"] // doc["num_attention_heads"]
        rot = int(hd * doc["partial_rotary_factor"])
        return cls(doc["num_hidden_layers"], doc["hidden_size"],
                   doc["num_attention_heads"], doc["num_key_value_heads"],
                   hd, doc["intermediate_size"], doc["vocab_size"],
                   rot - rot % 2, float(doc["rope_theta"]),
                   float(doc["rms_norm_eps"]))


# ----------------------------------------------------------------- weights
def root_key(seed: int):
    """A key from any whole seed, 2**31 and above included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def layer_weights(sh: Shapes, key, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights (``layer`` may be traced)."""
    d, H, Hk, hd, ff = (sh.d_model, sh.heads, sh.kv_heads, sh.head_dim,
                        sh.d_ff)
    ks = jax.random.split(jax.random.fold_in(key, layer), 9)
    bf = jnp.bfloat16

    def mat(k, shape, fan_in):
        return (jax.random.normal(k, shape) * fan_in ** -0.5).astype(bf)

    def norm(k):
        return 1.0 + 0.1 * jax.random.normal(k, (d,))
    return {"attn_norm": norm(ks[0]),
            "wq": mat(ks[1], (d, H, hd), d),
            "wk": mat(ks[2], (d, Hk, hd), d),
            "wv": mat(ks[3], (d, Hk, hd), d),
            "wo": mat(ks[4], (H, hd, d), H * hd),
            "ffn_norm": norm(ks[5]),
            "w_gate": mat(ks[6], (d, ff), d),
            "w_up": mat(ks[7], (d, ff), d),
            "w_down": mat(ks[8], (ff, d), ff)}


def embed_weights(sh: Shapes, key):
    """Token embedding [vocab, d] (bf16) and the final norm's scale."""
    ke, kn = jax.random.split(jax.random.fold_in(key, 1 << 30))
    emb = (jax.random.normal(ke, (sh.vocab, sh.d_model)) * 0.02
           ).astype(jnp.bfloat16)
    return emb, 1.0 + 0.1 * jax.random.normal(kn, (sh.d_model,))


def interleave_rope(w, rope_dims: int):
    """Reorder the last axis from rotate-half pairs (j, j + r/2) to
    adjacent pairs (2j, 2j + 1); the other dimensions stay."""
    h = rope_dims // 2
    perm = np.concatenate([np.stack([np.arange(h), np.arange(h) + h],
                                    1).reshape(-1),
                           np.arange(rope_dims, w.shape[-1])])
    return w[..., perm]


# --------------------------------------------------------------- reference
def _fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, fp8):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, sh: Shapes):
    """x [S, heads, hd], positions 0..S-1; rotate-half on the first dims."""
    r, h = sh.rope_dims, sh.rope_dims // 2
    inv = sh.rope_theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    xr = x[..., :r]
    half = jnp.concatenate([-xr[..., h:], xr[..., :h]], -1)
    return jnp.concatenate([xr * cos + half * sin, x[..., r:]], -1)


def _block(sh: Shapes, w, x, fp8: bool):
    """One layer over one sequence x [S, d]."""
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rms(x, f32["attn_norm"], sh.norm_eps)
    q = _rope(_mm("sd,dhk->shk", h, f32["wq"], fp8), sh)
    k = _rope(_mm("sd,dhk->shk", h, f32["wk"], fp8), sh)
    v = _mm("sd,dhk->shk", h, f32["wv"], fp8)
    g = sh.heads // sh.kv_heads
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhk,thk->hqt", q, k,
                   precision=jax.lax.Precision.HIGHEST) * sh.head_dim ** -0.5
    n = x.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqt,thk->qhk", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _mm("qhk,hkd->qd", o, f32["wo"], fp8)
    h = _rms(x, f32["ffn_norm"], sh.norm_eps)
    a = jax.nn.silu(_mm("sd,df->sf", h, f32["w_gate"], fp8)) \
        * _mm("sd,df->sf", h, f32["w_up"], fp8)
    return x + _mm("sf,fd->sd", a, f32["w_down"], fp8)


def token_gaps(sh: Shapes, seed: int,
               seqs: Sequence[Tuple[Sequence[int], Sequence[int]]],
               *, control: bool = False) -> List[Dict[str, np.ndarray]]:
    """For each (prompt, served tokens), at each served position: how far
    the served token's reference logit lies below the reference's best.

    With ``control`` it also gives, at the same positions, the gap of the
    token that the float8 computation of the same model puts first.
    Sequences are padded to one length (the padding follows every read
    position, so causal attention never sees it) and run one layer at a
    time, weights regenerated from the seed per layer.
    """
    key = root_key(seed)
    width = max(len(p) + len(o) - 1 for p, o in seqs)
    width = -(-width // 128) * 128
    toks = np.zeros((len(seqs), width), np.int32)
    for i, (p, o) in enumerate(seqs):
        s = list(p) + list(o)[:-1]
        toks[i, :len(s)] = s
    streams = (False, True) if control else (False,)

    emb, final = jax.jit(lambda k: embed_weights(sh, k))(key)
    xs = [jnp.take(emb, jnp.asarray(toks), axis=0).astype(jnp.float32)
          for _ in streams]
    weights = jax.jit(lambda k, l: layer_weights(sh, k, l))
    block = jax.jit(lambda w, x, fp8: jax.lax.map(
        lambda xi: _block(sh, w, xi, fp8), x), static_argnums=2)
    for layer in range(sh.layers):
        w = weights(key, layer)
        xs = [block(w, x, fp8) for x, fp8 in zip(xs, streams)]
        del w

    @jax.jit
    def heads(x, pos, served, e, g):
        e = e.astype(jnp.float32)
        rows = _rms(x[pos], g, sh.norm_eps)
        ref = jnp.einsum("pd,vd->pv", rows, e,
                         precision=jax.lax.Precision.HIGHEST)
        best = jnp.max(ref, -1)
        return best, best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]

    @jax.jit
    def low_top(x8, pos, e, g):
        rows = _rms(x8[pos], g, sh.norm_eps)
        return jnp.argmax(_mm("pd,vd->pv", rows, e.astype(jnp.float32),
                              True), -1)

    m = max(len(o) for _, o in seqs)
    out = []
    for i, (p, o) in enumerate(seqs):
        pos = np.full((m,), len(p) - 1, np.int32)
        pos[:len(o)] = np.arange(len(p) - 1, len(p) - 1 + len(o))
        served = np.zeros((m,), np.int32)
        served[:len(o)] = o
        best, gap = heads(xs[0][i], pos, served, emb, final)
        r = {"gap": np.asarray(gap)[:len(o)]}
        if control:
            top8 = low_top(xs[1][i], pos, emb, final)
            _, g8 = heads(xs[0][i], pos, top8, emb, final)
            r["control_gap"] = np.asarray(g8)[:len(o)]
        out.append(r)
    return out
