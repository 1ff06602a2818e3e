"""Prefill + decode must reproduce full-forward logits for every family.

For MoE archs the capacity-based dispatch is order-dependent (token drops
differ between grouping contexts), so MoE configs are tested with a high
capacity factor where routing is lossless — the drop semantics themselves
are covered in test_moe.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import build_model

CONSISTENCY_ARCHS = ["phi4_mini_3_8b", "gemma_2b", "chatglm3_6b",
                     "codeqwen15_7b", "musicgen_large", "jamba_v01_52b",
                     "jamba2_3b",
                     "xlstm_1_3b", "granite_moe_1b_a400m"]


@pytest.mark.parametrize("arch", CONSISTENCY_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    if cfg.moe.enabled:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, P = 2, 16, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full, _, _ = model.forward(params, toks)
    full = np.asarray(full, np.float32)

    last, caches = model.prefill(params, toks[:, :P], max_len=S)
    errs = [np.abs(np.asarray(last, np.float32) - full[:, P - 1]).max()]
    for i in range(P, S):
        lg, caches = model.decode_step(params, toks[:, i:i + 1], caches,
                                       jnp.int32(i))
        errs.append(np.abs(np.asarray(lg, np.float32) - full[:, i]).max())
    assert max(errs) < 2e-2, (arch, errs)


def test_per_lane_cache_index_decode():
    """Array cache_index (continuous batching) == scalar per-lane decode."""
    cfg = get_smoke_config("phi4_mini_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                              cfg.vocab_size)
    # two lanes at different positions
    _, caches_a = model.prefill(params, toks[:1, :8], max_len=16)
    _, caches_b = model.prefill(params, toks[1:, :5], max_len=16)
    merged = jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a, b], axis=1), caches_a, caches_b)
    tok = jnp.stack([toks[0, 8:9], toks[1, 5:6]])
    lg_arr, _ = model.decode_step(params, tok, merged,
                                  jnp.asarray([8, 5], jnp.int32))
    lg_a, _ = model.decode_step(params, toks[:1, 8:9], caches_a, jnp.int32(8))
    lg_b, _ = model.decode_step(params, toks[1:, 5:6], caches_b, jnp.int32(5))
    np.testing.assert_allclose(np.asarray(lg_arr[0], np.float32),
                               np.asarray(lg_a[0], np.float32),
                               atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(lg_arr[1], np.float32),
                               np.asarray(lg_b[0], np.float32),
                               atol=1e-2, rtol=1e-2)
