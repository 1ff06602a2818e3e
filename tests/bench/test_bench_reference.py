"""The float32 reference of ``bench/reference/dense.py`` against the
program's model (``repro.models``) at a smoke size, partial RoPE on."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve
from bench.reference import dense
from smoke import shrink

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 11


def _smoke():
    doc = json.loads((ROOT / "bench/configs/phi4-mini-3.8b.json").read_text())
    doc, _ = shrink(doc, {"arrivals": {}, "prompt": {}, "output": {}})
    cfg, _ = serve.model_config(doc)
    return doc, cfg, dense.Shapes.of(doc)


def test_config_file_sets_the_published_partial_rope():
    doc, cfg, sh = _smoke()
    assert cfg.rope_fraction == 0.75 == doc["partial_rotary_factor"]
    assert sh.rope_dims == 12            # 0.75 of a head of 16
    full = json.loads((ROOT / "bench/configs/phi4-mini-3.8b.json")
                      .read_text())
    fcfg, changed = serve.model_config(full)
    assert (fcfg.n_layers, fcfg.d_model, fcfg.n_heads, fcfg.n_kv_heads,
            fcfg.resolved_head_dim, fcfg.d_ff, fcfg.vocab_size) == \
        (32, 3072, 24, 8, 128, 8192, 200064)
    assert changed == {"rope_fraction": (1.0, 0.75)}


def test_rope_layouts_agree_after_the_converter_permutation():
    from repro.models.layers import apply_rope

    _, cfg, sh = _smoke()
    q, k = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))
    pos = jnp.arange(9)[None]
    ref = jnp.einsum("shk,thk->hst", dense._rope(q, sh), dense._rope(k, sh))
    perm = lambda x: dense.interleave_rope(x, sh.rope_dims)
    got = jnp.einsum("shk,thk->hst", apply_rope(perm(q)[None], pos, cfg)[0],
                     apply_rope(perm(k)[None], pos, cfg)[0])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_weights_drawn_per_layer_equal_the_stacked_draw():
    _, _, sh = _smoke()
    key = dense.root_key(SEED)
    stacked = jax.vmap(lambda l: dense.layer_weights(sh, key, l))(
        jnp.arange(sh.layers))
    one = dense.layer_weights(sh, key, 1)
    for name, leaf in one.items():
        np.testing.assert_array_equal(stacked[name][1], leaf)


def _served(cfg, sh, params, n_req=3):
    from repro.serving import ServeRequest, ServingEngine

    eng = ServingEngine(cfg, params, lanes=2, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(prompt=rng.integers(0, sh.vocab, 5 + 7 * i)
                         .tolist(), max_new_tokens=12) for i in range(n_req)]
    eng.run(reqs)
    return [(r.prompt, r.output) for r in reqs]


def test_served_tokens_sit_at_the_reference_top():
    _, cfg, sh = _smoke()
    seqs = _served(cfg, sh, serve.program_params(cfg, sh, SEED))
    gaps = dense.token_gaps(sh, SEED, seqs, control=True)
    worst = max(float(g["gap"].max()) for g in gaps)
    control = max(float(g["control_gap"].max()) for g in gaps)
    assert worst < 0.02
    # the float8 control misses by far more than sound bf16 serving
    assert control > 3 * worst


def test_without_the_rope_permutation_the_reference_disagrees(monkeypatch):
    _, cfg, sh = _smoke()
    monkeypatch.setattr(dense, "interleave_rope", lambda w, r: w)
    seqs = _served(cfg, sh, serve.program_params(cfg, sh, SEED))
    monkeypatch.undo()
    gaps = dense.token_gaps(sh, SEED, seqs)
    assert max(float(g["gap"].max()) for g in gaps) > 0.05
