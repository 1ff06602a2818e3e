"""A dense GQA decoder with a SwiGLU feed-forward (Phi-3 / Phi-4-mini form)
for ``bench/drivers/serve.py``: the program's config and weights from a
configuration file, the shapes the reference and the counts take, and the
reference's check (``bench/reference/dense.py``)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench.counts import Dense
from bench.reference import dense as ref

#: program fields set from a configuration file's published keys
_PUBLISHED = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rope_fraction": "partial_rotary_factor",
    "norm_eps": "rms_norm_eps",
}

token_gaps = ref.token_gaps


def program_config(doc: dict):
    """The program's config for this file: its named repo config with every
    published size set from the file (the file, not the repo, is the
    yardstick), and the fields that differ from the repo's, as
    ``{field: (repo, file)}``."""
    from repro.configs import get_config

    base = get_config(doc["program_config"])
    cfg = dataclasses.replace(
        base, **{f: doc[k] for f, k in _PUBLISHED.items()},
        head_dim=doc["hidden_size"] // doc["num_attention_heads"],
        act="swiglu", tie_embeddings=doc["tie_word_embeddings"],
        sliding_window=0, attn_logit_softcap=0.0, dtype="bfloat16",
        max_seq_len=max(base.max_seq_len, doc["engine"]["max_len"]))
    if cfg.family != "dense" or cfg.resolved_scan_period != 1:
        raise ValueError(f"{cfg.name} is not a plain dense stack")
    changed = {f: (getattr(base, f), getattr(cfg, f))
               for f in _PUBLISHED if getattr(base, f) != getattr(cfg, f)}
    return cfg, changed


def shapes(doc: dict) -> ref.Shapes:
    return ref.Shapes.of(doc)


def counts(cfg) -> Dense:
    return Dense.of(cfg)


def program_params(cfg, sh: ref.Shapes, seed: int):
    """The program's parameter tree, made on the device in one call."""
    from repro.models import build_model

    def make(key):
        layers = jax.vmap(lambda l: ref.layer_weights(sh, key, l))(
            jnp.arange(sh.layers))
        emb, final = ref.embed_weights(sh, key)
        pad = cfg.padded_vocab - sh.vocab
        return {
            "embed": {"tok_embed": jnp.pad(emb, ((0, pad), (0, 0)))},
            "stack": {"pos00": {
                "mixer_norm": {"scale": layers["attn_norm"]},
                "mixer": {"wq": ref.interleave_rope(layers["wq"],
                                                    sh.rope_dims),
                          "wk": ref.interleave_rope(layers["wk"],
                                                    sh.rope_dims),
                          "wv": layers["wv"], "wo": layers["wo"]},
                "ffn_norm": {"scale": layers["ffn_norm"]},
                "ffn": {"w_gate": layers["w_gate"], "w_up": layers["w_up"],
                        "w_down": layers["w_down"]}},
                "final_norm": {"scale": final}}}

    key = ref.root_key(seed)
    want = build_model(cfg).param_specs()
    got = jax.eval_shape(make, key)
    if jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), got) != \
            jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), want):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree_util.tree_structure(want)}")
    return jax.block_until_ready(jax.jit(make)(key))
