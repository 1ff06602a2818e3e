"""A dense Jamba hybrid (AI21-Jamba2-3B: Mamba-1 layers with an attention
layer in each period, a dense SwiGLU on every layer) for
``bench/drivers/serve.py``: the program's config and weights from a
configuration file, the counts of its steps, and the reference's check
(``bench/reference/jamba.py``)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.counts import BF16
from bench.reference import jamba as ref

#: program fields set from a configuration file's published keys
_PUBLISHED = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "norm_eps": "rms_norm_eps", "attn_every": "attn_layer_period",
    "attn_offset": "attn_layer_offset",
}
_SSM = {"d_state": "mamba_d_state", "d_conv": "mamba_d_conv",
        "expand": "mamba_expand", "dt_rank": "mamba_dt_rank"}

token_gaps = ref.token_gaps


def program_config(doc: dict):
    """The program's config for this file: its named repo config with every
    published size set from the file (the file, not the repo, is the
    yardstick), and the fields that differ from the repo's, as
    ``{field: (repo, file)}``."""
    from repro.configs import MoEConfig, get_config

    if doc["num_experts"] != 1 or doc["hidden_act"] != "silu" \
            or doc["mamba_proj_bias"] or not doc["mamba_conv_bias"] \
            or doc["sliding_window"] is not None:
        raise ValueError("not a dense Jamba with SiLU, conv bias, no "
                         "projection bias and full attention")
    base = get_config(doc["program_config"])
    cfg = dataclasses.replace(
        base, **{f: doc[k] for f, k in _PUBLISHED.items()},
        head_dim=doc["hidden_size"] // doc["num_attention_heads"],
        ssm=dataclasses.replace(base.ssm, inner_norms=True,
                                **{f: doc[k] for f, k in _SSM.items()}),
        moe=MoEConfig(), act="swiglu", rope_fraction=0.0,
        tie_embeddings=doc["tie_word_embeddings"], sliding_window=0,
        attn_logit_softcap=0.0, dtype="bfloat16",
        max_seq_len=max(base.max_seq_len, doc["engine"]["max_len"]))
    if cfg.family != "hybrid":
        raise ValueError(f"{cfg.name} is not a hybrid stack")
    changed = {f: (getattr(base, f), getattr(cfg, f))
               for f in _PUBLISHED if getattr(base, f) != getattr(cfg, f)}
    changed.update({f"ssm.{f.name}": (getattr(base.ssm, f.name),
                                      getattr(cfg.ssm, f.name))
                    for f in dataclasses.fields(cfg.ssm)
                    if getattr(base.ssm, f.name) != getattr(cfg.ssm, f.name)})
    return cfg, changed


def shapes(doc: dict) -> ref.Shapes:
    return ref.Shapes.of(doc)


# ------------------------------------------------------------------ counts
@dataclasses.dataclass(frozen=True)
class Hybrid:
    """Operations and bytes of a dense Jamba hybrid's steps, from shapes
    alone (as ``bench/counts.py`` counts the dense decoder)."""
    layers: int
    attn_layers: int
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

    @classmethod
    def of(cls, cfg) -> "Hybrid":
        attn = sum(cfg.layer_kind(i) == "attn" for i in range(cfg.n_layers))
        s = cfg.ssm
        return cls(cfg.n_layers, attn, cfg.d_model, cfg.n_heads,
                   cfg.n_kv_heads, cfg.resolved_head_dim, cfg.d_ff,
                   cfg.vocab_size, s.expand * cfg.d_model, s.d_state,
                   s.d_conv, s.dt_rank)

    @property
    def mamba_layers(self) -> int:
        return self.layers - self.attn_layers

    @property
    def mamba_matmul_params(self) -> int:
        d, din, n, r = self.d_model, self.d_inner, self.d_state, self.dt_rank
        return 2 * d * din + din * (r + 2 * n) + r * din + din * d

    @property
    def attn_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d

    @property
    def params(self) -> int:
        """Every parameter: matrices, conv taps and biases, A, D, norms and
        the tied embedding."""
        d, din, n = self.d_model, self.d_inner, self.d_state
        mamba = (self.mamba_matmul_params + din * self.d_conv + din  # conv
                 + din + din * n + din  # Δ bias, A, D
                 + self.dt_rank + 2 * n)  # inner norms
        per_layer = 3 * d * self.d_ff + 2 * d  # feed-forward, two norms
        return (self.mamba_layers * mamba
                + self.attn_layers * self.attn_matmul_params
                + self.layers * per_layer + self.vocab * d + d)

    @property
    def state_bytes_per_lane(self) -> int:
        """Each Mamba layer's float32 state [d_inner, d_state] and its bf16
        conv window [d_conv - 1, d_inner]."""
        return self.mamba_layers * (self.d_inner * self.d_state * 4
                                    + (self.d_conv - 1) * self.d_inner * BF16)

    @property
    def kv_bytes_per_position(self) -> int:
        """Keys and values of one position over the attention layers, bf16."""
        return self.attn_layers * 2 * self.kv_heads * self.head_dim * BF16

    def weight_bytes(self) -> float:
        return float(self.params * BF16)

    def _token_flops(self) -> float:
        """One token through every layer, attention scores left out: the
        matrices; the conv taps; and the scan's 6 operations per
        (d_inner, d_state) element (Δ·A, Δ·u·B, the state's multiply-add,
        the output's multiply-add)."""
        din = self.d_inner
        mamba = (2.0 * self.mamba_matmul_params + 2.0 * self.d_conv * din
                 + 6.0 * din * self.d_state)
        return (self.mamba_layers * mamba
                + 2.0 * self.attn_layers * self.attn_matmul_params
                + 2.0 * self.layers * 3 * self.d_model * self.d_ff)

    def _attn_flops(self, q_tokens_ctx: float) -> float:
        return 4.0 * self.attn_layers * self.heads * self.head_dim \
            * q_tokens_ctx

    def prefill_flops(self, s: int) -> float:
        """A prompt of ``s`` tokens, causal attention over the positions
        before each, logits of the last one."""
        return (self._token_flops() * s + self._attn_flops(s * (s + 1) / 2)
                + 2.0 * self.d_model * self.vocab)

    def decode_flops(self, contexts) -> float:
        """One token for each lane, lane i attending over ``contexts[i]``
        positions (itself included)."""
        return ((self._token_flops() + 2.0 * self.d_model * self.vocab)
                * len(contexts) + self._attn_flops(float(sum(contexts))))

    def decode_bytes(self, contexts) -> float:
        """Weights read once, each active lane's recurrent state read and
        written, keys and values of the occupied positions read: the least
        the step's tokens need. The engine's step carries every lane, as the
        dense count's step reads every position of every lane; the idle
        lanes' state is left out here as the empty positions are there."""
        return (self.weight_bytes()
                + 2.0 * self.state_bytes_per_lane * len(contexts)
                + self.kv_bytes_per_position * float(sum(contexts)))


def counts(cfg) -> Hybrid:
    return Hybrid.of(cfg)


# ----------------------------------------------------------------- weights
def conv_taps(w):
    """Conv taps ``[..., d_inner, 1, d_conv]`` to the program's
    ``[..., d_conv, d_inner]``, the oldest input's tap first in both."""
    return jnp.swapaxes(w[..., 0, :], -1, -2)


def split_x_proj(w, sh: ref.Shapes):
    """``x_proj`` ``[..., dt_rank + 2 d_state, d_inner]`` to the program's
    ``x_dt``, ``x_b``, ``x_c`` (each ``[..., d_inner, width]``)."""
    r, n = sh.dt_rank, sh.d_state
    t = jnp.swapaxes(w, -1, -2)
    return t[..., :r], t[..., r:r + n], t[..., r + n:]


def _mixer(sh: ref.Shapes, kind: str, w):
    """One position's mixer, every layer of it stacked on a leading axis."""
    T = lambda a: jnp.swapaxes(a, -1, -2)
    g = w["input_layernorm"].shape[0]
    if kind == ref.ATTENTION:
        H, Hk, hd, d = sh.heads, sh.kv_heads, sh.head_dim, sh.d_model
        return {"wq": T(w["q_proj"]).reshape(g, d, H, hd),
                "wk": T(w["k_proj"]).reshape(g, d, Hk, hd),
                "wv": T(w["v_proj"]).reshape(g, d, Hk, hd),
                "wo": T(w["o_proj"]).reshape(g, H, hd, d)}
    x_dt, x_b, x_c = split_x_proj(w["x_proj"], sh)
    return {"in_proj": T(w["in_proj"]), "conv_w": conv_taps(
                w["conv1d_weight"]), "conv_b": w["conv1d_bias"],
            "x_dt": x_dt, "x_b": x_b, "x_c": x_c,
            "dt_proj": T(w["dt_proj_weight"]), "dt_bias": w["dt_proj_bias"],
            "a_log": w["A_log"], "ssm_d": w["D"],
            "out_proj": T(w["out_proj"]),
            "dt_norm": {"scale": w["dt_layernorm"]},
            "b_norm": {"scale": w["b_layernorm"]},
            "c_norm": {"scale": w["c_layernorm"]}}


def program_params(cfg, sh: ref.Shapes, seed: int):
    """The program's parameter tree, made on the device in one call: layer
    l of the published stack is group l // period at position
    l % period."""
    from repro.models import build_model

    period = cfg.resolved_scan_period
    kinds = [sh.kind(p) for p in range(period)]
    if [k == ref.ATTENTION for k in kinds] != \
            [cfg.layer_kind(p) == "attn" for p in range(period)]:
        raise ValueError("the program's layer order is not the file's")
    T = lambda a: jnp.swapaxes(a, -1, -2)

    def make(key):
        stack = {}
        for p, kind in enumerate(kinds):
            w = jax.vmap(lambda l: ref.layer_weights(sh, key, l, kind))(
                jnp.arange(p, sh.layers, period))
            stack[f"pos{p:02d}"] = {
                "mixer_norm": {"scale": w["input_layernorm"]},
                "mixer": _mixer(sh, kind, w),
                "ffn_norm": {"scale": w["pre_ff_layernorm"]},
                "ffn": {"w_gate": T(w["gate_proj"]), "w_up": T(w["up_proj"]),
                        "w_down": T(w["down_proj"])}}
        emb, final = ref.embed_weights(sh, key)
        stack["final_norm"] = {"scale": final}
        pad = cfg.padded_vocab - sh.vocab
        return {"embed": {"tok_embed": jnp.pad(emb, ((0, pad), (0, 0)))},
                "stack": stack}

    key = ref.root_key(seed)
    want = build_model(cfg).param_specs()
    got = jax.eval_shape(make, key)
    if jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), got) != \
            jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), want):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree_util.tree_structure(want)}")
    return jax.block_until_ready(jax.jit(make)(key))
