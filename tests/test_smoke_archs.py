"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and no NaNs (assignment
requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.optim import AdamW


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32, seed=1):
    k = jax.random.PRNGKey(seed)
    toks = jax.random.randint(k, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.frontend != "none":
        batch["frontend_embeds"] = jnp.ones((B, 8, cfg.frontend_dim),
                                            jnp.bfloat16)
        mask = np.ones((B, S), np.float32)
        mask[:, :8] = 0.0
        batch["loss_mask"] = jnp.asarray(mask)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_no_nan(arch, key):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(key)
    batch = _batch(cfg)
    logits, caches, aux = model.forward(params, batch["tokens"],
                                        batch.get("frontend_embeds"))
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert caches is None
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_no_nan(arch, key):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(key)
    opt = AdamW(learning_rate=1e-3)
    opt_state = opt.init(params)
    batch = _batch(cfg)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        params, opt_state, om = opt.update(grads, opt_state, params)
        return params, opt_state, loss, om["grad_norm"]

    p1, o1, loss, gnorm = step(params, opt_state, batch)
    assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(gnorm))
    assert float(gnorm) > 0.0
    # params actually changed
    delta = jax.tree_util.tree_reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x[0].astype(jnp.float32)
                                               - x[1].astype(jnp.float32)))),
        jax.tree_util.tree_map(lambda a, b: (a, b), p1, params), 0.0)
    assert delta > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full config carries the exact assigned dimensions."""
    cfg = get_config(arch)
    expected = {
        "jamba_v01_52b": (32, 4096, 32, 8, 14336, 65536),
        "jamba2_3b": (28, 2560, 20, 1, 8192, 65536),
        "arctic_480b": (35, 7168, 56, 8, 4864, 32000),
        "granite_moe_1b_a400m": (24, 1024, 16, 8, 512, 49155),
        "phi4_mini_3_8b": (32, 3072, 24, 8, 8192, 200064),
        "codeqwen15_7b": (32, 4096, 32, 32, 13440, 92416),
        "gemma_2b": (18, 2048, 8, 1, 16384, 256000),
        "chatglm3_6b": (28, 4096, 32, 2, 13696, 65024),
        "xlstm_1_3b": (48, 2048, 4, 4, 0, 50304),
        "internvl2_2b": (24, 2048, 16, 8, 8192, 92553),
        "musicgen_large": (48, 2048, 32, 32, 8192, 2048),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expected, (arch, got, expected)


def test_moe_configs():
    assert get_config("arctic_480b").moe.n_experts == 128
    assert get_config("arctic_480b").moe.top_k == 2
    assert get_config("arctic_480b").moe.dense_residual
    assert get_config("granite_moe_1b_a400m").moe.n_experts == 32
    assert get_config("granite_moe_1b_a400m").moe.top_k == 8
    assert get_config("jamba_v01_52b").moe.n_experts == 16
    j = get_config("jamba_v01_52b")
    # 1:7 attention:mamba interleave
    kinds = [j.layer_kind(i) for i in range(8)]
    assert kinds.count("attn") == 1 and kinds.count("ssm") == 7


def test_param_counts_plausible():
    """Analytic parameter counts are in the right ballpark for the names."""
    assert 45e9 < get_config("jamba_v01_52b").param_count()["total"] < 60e9
    assert 350e9 < get_config("arctic_480b").param_count()["total"] < 550e9
    assert 2e9 < get_config("gemma_2b").param_count()["total"] < 3.3e9
    assert 5.5e9 < get_config("codeqwen15_7b").param_count()["total"] < 8.5e9
    g = get_config("granite_moe_1b_a400m").param_count()
    assert 0.9e9 < g["total"] < 1.8e9
    assert g["active"] < 0.65e9
