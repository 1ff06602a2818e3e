"""AI21-Jamba2-3B served by the program against the float32 reference of
``bench/reference/jamba.py`` at a smoke size, with config and weights from
``bench/models/jamba.py`` as the serve driver takes them; and the faults
that the comparison deciding ``correct`` must catch in its cell."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.models import jamba as model
from bench.reference import jamba as ref
from smoke import at_smoke, shrink

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "serve.jamba2-3b.chat"
PATH = "bench/configs/jamba2-3b.json"
SEED = 2 ** 31 + 17


def _doc(smoke=True):
    doc = json.loads((ROOT / PATH).read_text())
    return at_smoke(doc, PATH) if smoke else doc


def _smoke():
    doc = _doc()
    cfg, _ = model.program_config(doc)
    return doc, cfg, model.shapes(doc)


def _served(cfg, sh, params, prompts, new=10, lanes=2):
    """Requests through ``ServingEngine``: more requests than lanes, so
    lanes serve two at a time and are reused."""
    from repro.serving import ServeRequest, ServingEngine

    eng = ServingEngine(cfg, params, lanes=lanes, max_len=160)
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(prompt=rng.integers(0, sh.vocab, n).tolist(),
                         max_new_tokens=new) for n in prompts]
    eng.run(reqs)
    return [(r.prompt, r.output) for r in reqs]


def _worst(sh, seqs, **kw):
    gaps = model.token_gaps(sh, SEED, seqs, **kw)
    return (max(float(g["gap"].max()) for g in gaps),
            max(float(g.get("control_gap", np.zeros(1)).max()) for g in gaps))


# --------------------------------------------------------------- the model
def test_the_file_holds_every_published_key_at_full_size():
    doc = _doc(smoke=False)
    cfg, changed = model.program_config(doc)
    assert changed == {} and doc["reduced"] == []
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == \
        (28, 2560, 20, 1, 128, 8192, 65536)
    assert (cfg.ssm.d_state, cfg.ssm.d_conv, cfg.ssm.expand,
            cfg.ssm.dt_rank, cfg.ssm.inner_norms) == (16, 4, 2, 160, True)
    assert cfg.rope_fraction == 0.0 and cfg.tie_embeddings
    assert [i for i in range(28) if cfg.layer_kind(i) == "attn"] == [7, 21]
    sh = model.shapes(doc)
    assert [i for i in range(28) if sh.kind(i) == ref.ATTENTION] == [7, 21]


def test_parameters_state_and_keys_by_hand():
    from repro.models import build_model
    from repro.models.transformer import init_caches

    cfg, _ = model.program_config(_doc(smoke=False))
    c = model.counts(cfg)
    assert c.params == 3_029_337_472
    program = build_model(cfg).param_specs()
    assert sum(s.size for s in jax.tree_util.tree_leaves(program)) == \
        c.params
    # 26 Mamba layers x (5120 x 16 fp32 + 3 x 5120 bf16)
    assert c.state_bytes_per_lane == 9_318_400
    # 2 attention layers x (k, v) x 1 head x 128 x 2 B
    assert c.kv_bytes_per_position == 1_024
    one = init_caches(cfg, 1, 1, spec=True)
    two = init_caches(cfg, 1, 2, spec=True)
    size = lambda t: sum(s.size * s.dtype.itemsize
                         for s in jax.tree_util.tree_leaves(t))
    assert size(two) - size(one) == c.kv_bytes_per_position
    assert size(one) - c.kv_bytes_per_position == c.state_bytes_per_lane
    # a decode step's bytes: the weights, each lane's state twice, the keys
    # and values of the occupied positions
    assert c.decode_bytes([10, 30]) == \
        2 * c.params + 2 * 2 * 9_318_400 + 1_024 * 40


def test_weights_drawn_per_layer_equal_the_stacked_draw():
    _, _, sh = _smoke()
    key = ref.root_key(SEED)
    for kind, layer in ((ref.MAMBA, 5), (ref.ATTENTION, 6)):
        stacked = jax.vmap(lambda l: ref.layer_weights(sh, key, l, kind))(
            jnp.asarray([layer - 4, layer]))
        one = ref.layer_weights(sh, key, layer, kind)
        for name, leaf in one.items():
            np.testing.assert_array_equal(stacked[name][1], leaf)


def test_prefill_then_decode_agrees_with_the_reference():
    """Prompts of 1, 16, 80 and 130 tokens (80 and 130 end in a padded
    chunk of the scan), two lanes live at once, each lane reused."""
    doc, cfg, sh = _smoke()
    seqs = _served(cfg, sh, model.program_params(cfg, sh, SEED),
                   [1, 16, 80, 130])
    assert [len(o) for _, o in seqs] == [10] * 4
    worst, _ = _worst(sh, seqs)
    assert worst < doc["limits"]["max_logit_gap"]


@pytest.mark.parametrize("fault", ["conv taps reversed", "x_b and x_c swapped"])
def test_a_wrong_weight_conversion_disagrees_with_the_reference(
        fault, monkeypatch):
    doc, cfg, sh = _smoke()
    if fault == "conv taps reversed":
        real = model.conv_taps
        monkeypatch.setattr(model, "conv_taps", lambda w: real(w)[..., ::-1, :])
    else:
        real = model.split_x_proj
        monkeypatch.setattr(model, "split_x_proj", lambda w, s: (
            lambda dt, b, c: (dt, c, b))(*real(w, s)))
    seqs = _served(cfg, sh, model.program_params(cfg, sh, SEED), [16, 80])
    monkeypatch.undo()
    worst, _ = _worst(sh, seqs)
    assert worst > doc["limits"]["max_logit_gap"]


# ------------------------------------------- the cell's check and its faults
def _run(*, control=False):
    c, doc, mix = run.resolve(SPEC, CELL)
    doc, mix = shrink(c, doc, mix)
    return run.run_cell(SPEC, c, doc, mix, seed=SEED, seconds=0.5,
                        trace=False, control=control)


def test_the_float8_control_is_not_correct():
    out = _run(control=True)
    assert not out["correct"], out["checks"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving import ServingEngine

    real = ServingEngine._decode_fn

    def altered(self, params, caches, tokens, positions):
        tok, caches = real(self, params, caches, tokens, positions)
        return (tok + 1) % self.cfg.vocab_size, caches
    monkeypatch.setattr(ServingEngine, "_decode_fn", altered)
    assert not _run()["correct"]


def test_a_decode_step_that_keeps_its_recurrent_state_is_not_correct(
        monkeypatch):
    """Keys and values advance; the Mamba layers' state does not."""
    from repro.serving import ServingEngine

    real = ServingEngine._decode_fn

    def stale(self, params, caches, tokens, positions):
        tok, new = real(self, params, caches, tokens, positions)
        return tok, jax.tree_util.tree_map(
            lambda kind, old, fresh: fresh if kind == "kv" else old,
            self.cache_layout, caches, new)
    monkeypatch.setattr(ServingEngine, "_decode_fn", stale)
    out = _run()
    assert not out["correct"], out["checks"]


def test_state_scatter_is_read_per_request_admitted(monkeypatch):
    from repro.obs import spans

    cap = spans.Capture()
    for rid, (a, s) in enumerate([(10_000_000, 2_000_000),
                                  (12_000_000, 4_000_000)]):
        cap.add("engine.admit", rid, 0, a)
        cap.add("engine.scatter.state", rid, 0, s)
    monkeypatch.setattr(spans, "last_capture", lambda: cap)
    read = run.reader("state_scatter_ms_per_req")
    assert read({"trace": object()}) == pytest.approx(3.0)
    dense = spans.Capture()
    dense.add("engine.admit", 1, 0, 5)
    dense.add("engine.scatter.kv", 1, 0, 2)
    monkeypatch.setattr(spans, "last_capture", lambda: dense)
    assert read({"trace": object()}) is None
