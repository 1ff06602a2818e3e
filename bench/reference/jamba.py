"""Weights from a seed, and a float32 reference forward, for a dense Jamba
hybrid (AI21-Jamba2-3B; the published ``modeling_jamba`` of transformers
4.57): pre-norm RMSNorm blocks whose mixer is, by ``layers_block_type``,
attention where ``layer % attn_layer_period == attn_layer_offset`` and a
Mamba-1 mixer elsewhere, each followed by a dense SwiGLU feed-forward
(``num_experts`` 1); a final RMSNorm and logits on the tied embedding.

- Attention: grouped-query, no bias, no positional encoding, causal over
  the whole sequence, scaled by head_dim ** -0.5.
- Mamba (``JambaMambaMixer.slow_forward``): ``in_proj`` to the hidden
  states and the gate; a depthwise causal conv1d with bias and SiLU;
  ``x_proj`` to Δ (``dt_rank``), B and C (``d_state`` each), each through
  its RMSNorm; Δ = softplus(``dt_proj``(Δ)); then, one step at a time,
  state = exp(A Δ) state + Δ B u with A = -exp(``A_log``), output
  state · C + D u; the output times SiLU(gate), then ``out_proj``.

Departures from the published model: none but the weights, which are
random. They are drawn here per layer in the published (transformers)
layout, ``[out, in]`` matrices and conv taps ``[d_inner, 1, d_conv]``; the
program is given the same numbers after a converter's transposes and
splits (``bench/models/jamba.py``), and this reference never sees the
program. Norm scales, ``A_log``, ``D`` and the Δ bias are float32, every
matrix, the conv taps and the conv bias bfloat16 as served; the reference
reads them in float32 under ``jax.default_matmul_precision("highest")``,
one layer at a time.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import _mm, _rms, root_key

ATTENTION, MAMBA = "attention", "mamba"


@dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    attn_period: int
    attn_offset: int
    norm_eps: float

    @classmethod
    def of(cls, doc: dict) -> "Shapes":
        """From a configuration file's published keys."""
        return cls(doc["num_hidden_layers"], doc["hidden_size"],
                   doc["num_attention_heads"], doc["num_key_value_heads"],
                   doc["hidden_size"] // doc["num_attention_heads"],
                   doc["intermediate_size"], doc["vocab_size"],
                   doc["mamba_expand"] * doc["hidden_size"],
                   doc["mamba_d_state"], doc["mamba_d_conv"],
                   doc["mamba_dt_rank"], doc["attn_layer_period"],
                   doc["attn_layer_offset"], float(doc["rms_norm_eps"]))

    def kind(self, layer: int) -> str:
        """``layers_block_type`` of ``configuration_jamba``."""
        return ATTENTION if layer % self.attn_period == self.attn_offset \
            else MAMBA


# ----------------------------------------------------------------- weights
def layer_weights(sh: Shapes, key, layer, kind: str) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights in the published layout (``layer`` may be
    traced; ``kind`` is ``sh.kind`` of it)."""
    d, ff, din, n, dtr = (sh.d_model, sh.d_ff, sh.d_inner, sh.d_state,
                          sh.dt_rank)
    ks = iter(jax.random.split(jax.random.fold_in(key, layer), 20))
    bf = jnp.bfloat16

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape) * fan_in ** -0.5
                ).astype(bf)

    def norm(width):
        return 1.0 + 0.1 * jax.random.normal(next(ks), (width,))
    w = {"input_layernorm": norm(d), "pre_ff_layernorm": norm(d),
         "gate_proj": mat((ff, d), d), "up_proj": mat((ff, d), d),
         "down_proj": mat((d, ff), ff)}
    if kind == ATTENTION:
        H, Hk, hd = sh.heads, sh.kv_heads, sh.head_dim
        w.update(q_proj=mat((H * hd, d), d), k_proj=mat((Hk * hd, d), d),
                 v_proj=mat((Hk * hd, d), d),
                 o_proj=mat((d, H * hd), H * hd))
        return w
    # Δ's bias: softplus^-1 of a step drawn log-uniform in [1e-3, 1e-1],
    # as Mamba initialises it
    step = jnp.exp(jax.random.uniform(next(ks), (din,),
                                      minval=jnp.log(1e-3),
                                      maxval=jnp.log(1e-1)))
    w.update(
        in_proj=mat((2 * din, d), d),
        conv1d_weight=mat((din, 1, sh.d_conv), sh.d_conv),
        conv1d_bias=(0.1 * jax.random.normal(next(ks), (din,))).astype(bf),
        x_proj=mat((dtr + 2 * n, din), din),
        dt_layernorm=norm(dtr), b_layernorm=norm(n), c_layernorm=norm(n),
        dt_proj_weight=mat((din, dtr), dtr),
        dt_proj_bias=step + jnp.log(-jnp.expm1(-step)),
        A_log=jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None]
        + 0.1 * jax.random.normal(next(ks), (din, n)),
        D=1.0 + 0.1 * jax.random.normal(next(ks), (din,)),
        out_proj=mat((d, din), din))
    return w


def embed_weights(sh: Shapes, key):
    """Token embedding [vocab, d] (bf16, tied to the logits) and the final
    norm's scale."""
    ke, kn = jax.random.split(jax.random.fold_in(key, 1 << 30))
    emb = (jax.random.normal(ke, (sh.vocab, sh.d_model)) * 0.02
           ).astype(jnp.bfloat16)
    return emb, 1.0 + 0.1 * jax.random.normal(kn, (sh.d_model,))


# --------------------------------------------------------------- reference
def _attention(sh: Shapes, f, h, fp8: bool):
    """h [S, d]; causal attention with no positions."""
    S, H, Hk, hd = h.shape[0], sh.heads, sh.kv_heads, sh.head_dim
    q = _mm("sd,ed->se", h, f["q_proj"], fp8).reshape(S, H, hd)
    k = _mm("sd,ed->se", h, f["k_proj"], fp8).reshape(S, Hk, hd)
    v = _mm("sd,ed->se", h, f["v_proj"], fp8).reshape(S, Hk, hd)
    k, v = jnp.repeat(k, H // Hk, axis=1), jnp.repeat(v, H // Hk, axis=1)
    s = jnp.einsum("qhk,thk->hqt", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)
    return _mm("se,de->sd", o.reshape(S, H * hd), f["o_proj"], fp8)


def _mamba(sh: Shapes, f, h, fp8: bool):
    """h [S, d]; ``slow_forward`` with no cache, one step at a time."""
    S, din, n, K = h.shape[0], sh.d_inner, sh.d_state, sh.d_conv
    proj = _mm("sd,ed->se", h, f["in_proj"], fp8)
    u, gate = proj[:, :din], proj[:, din:]
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[k:k + S] * f["conv1d_weight"][:, 0, k]
                        for k in range(K)) + f["conv1d_bias"])
    p = _mm("sc,ec->se", u, f["x_proj"], fp8)
    ts = _rms(p[:, :sh.dt_rank], f["dt_layernorm"], sh.norm_eps)
    B = _rms(p[:, sh.dt_rank:sh.dt_rank + n], f["b_layernorm"], sh.norm_eps)
    C = _rms(p[:, sh.dt_rank + n:], f["c_layernorm"], sh.norm_eps)
    dt = jax.nn.softplus(_mm("sr,cr->sc", ts, f["dt_proj_weight"], fp8)
                         + f["dt_proj_bias"])
    A = -jnp.exp(f["A_log"])

    def step(state, xs):
        dt_t, b_t, c_t, u_t = xs
        state = jnp.exp(A * dt_t[:, None]) * state \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return state, state @ c_t
    _, y = jax.lax.scan(step, jnp.zeros((din, n), jnp.float32),
                        (dt, B, C, u))
    y = (y + u * f["D"]) * jax.nn.silu(gate)
    return _mm("sc,dc->sd", y, f["out_proj"], fp8)


def _block(sh: Shapes, kind: str, w, x, fp8: bool):
    """One layer over one sequence x [S, d]."""
    f = {k: v.astype(jnp.float32) for k, v in w.items()}
    mixer = _attention if kind == ATTENTION else _mamba
    x = x + mixer(sh, f, _rms(x, f["input_layernorm"], sh.norm_eps), fp8)
    h = _rms(x, f["pre_ff_layernorm"], sh.norm_eps)
    a = jax.nn.silu(_mm("sd,fd->sf", h, f["gate_proj"], fp8)) \
        * _mm("sd,fd->sf", h, f["up_proj"], fp8)
    return x + _mm("sf,df->sd", a, f["down_proj"], fp8)


def token_gaps(sh: Shapes, seed: int,
               seqs: Sequence[Tuple[Sequence[int], Sequence[int]]],
               *, control: bool = False) -> List[Dict[str, np.ndarray]]:
    """For each (prompt, served tokens), at each served position: how far
    the served token's reference logit lies below the reference's best.

    With ``control`` it also gives, at the same positions, the gap of the
    token that the float8 computation of the same model puts first.
    Sequences are padded to one length (the padding follows every read
    position, and both mixers are causal, so it is never seen) and run one
    layer at a time, weights regenerated from the seed per layer.
    """
    with jax.default_matmul_precision("highest"):
        return _token_gaps(sh, seed, seqs, control)


def _token_gaps(sh, seed, seqs, control):
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
    weights = jax.jit(lambda k, l, kind: layer_weights(sh, k, l, kind),
                      static_argnums=2)
    # the recurrence runs once for every sequence at a time; attention,
    # whose scores grow with the square of the length, one at a time
    block = jax.jit(lambda w, x, kind, fp8: (
        jax.vmap(lambda xi: _block(sh, kind, w, xi, fp8))(x)
        if kind == MAMBA else
        jax.lax.map(lambda xi: _block(sh, kind, w, xi, fp8), x)),
        static_argnums=(2, 3))
    for layer in range(sh.layers):
        kind = sh.kind(layer)
        w = weights(key, layer, kind)
        xs = [block(w, x, kind, fp8) for x, fp8 in zip(xs, streams)]
        del w

    @jax.jit
    def heads(x, pos, served, e, g):
        rows = _rms(x[pos], g, sh.norm_eps)
        ref = jnp.einsum("pd,vd->pv", rows, e.astype(jnp.float32))
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
        _, gap = heads(xs[0][i], pos, served, emb, final)
        r = {"gap": np.asarray(gap)[:len(o)]}
        if control:
            top8 = low_top(xs[1][i], pos, emb, final)
            _, g8 = heads(xs[0][i], pos, top8, emb, final)
            r["control_gap"] = np.asarray(g8)[:len(o)]
        out.append(r)
    return out
