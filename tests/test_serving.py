"""Serving engine: continuous batching correctness + multilevel accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import ServeRequest, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("phi4_mini_3_8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _greedy_ref(model, params, prompt, n_new, max_len):
    last, caches = model.prefill(
        params, jnp.asarray(prompt, jnp.int32)[None], max_len=max_len)
    toks = [int(jnp.argmax(last[0]))]
    for i in range(n_new - 1):
        lg, caches = model.decode_step(
            params, jnp.asarray([[toks[-1]]], jnp.int32), caches,
            jnp.int32(len(prompt) + i))
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def test_continuous_batching_matches_single_stream(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(3)
    eng = ServingEngine(cfg, params, lanes=3, max_len=48)
    reqs = [ServeRequest(prompt=list(rng.integers(0, cfg.vocab_size, 7)),
                         max_new_tokens=5) for _ in range(7)]
    eng.run(reqs)
    for r in reqs:
        ref = _greedy_ref(model, params, r.prompt, 5, 48)
        assert r.output == ref, (r.request_id, r.output, ref)


def test_lane_reuse_and_stats(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(4)
    eng = ServingEngine(cfg, params, lanes=2, max_len=32)
    reqs = [ServeRequest(prompt=list(rng.integers(0, cfg.vocab_size, 4)),
                         max_new_tokens=3) for _ in range(6)]
    stats = eng.run(reqs)
    assert stats["requests"] == 6
    assert stats["decode_tokens"] == 6 * 2   # 3 new tokens = 1 prefill + 2 decode
    # aggregation: fewer dispatches than request-serial decoding
    assert stats["decode_steps"] < 6 * 2
    assert stats["tokens_per_dispatch"] > 1.0


def test_eos_stops_early(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, cfg.vocab_size, 6))
    ref = _greedy_ref(model, params, prompt, 8, 32)
    # EOS must be a token value that does not occur earlier in the stream:
    # the smoke model's greedy rollout can repeat its first tokens, and a
    # repeated value would stop generation at its first occurrence, not at
    # the index it was picked from
    k = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eos = ref[k]
    eng = ServingEngine(cfg, params, lanes=1, max_len=32)
    req = ServeRequest(prompt=prompt, max_new_tokens=8, eos_token=eos)
    eng.run([req])
    assert req.output == ref[:k + 1]   # stopped at the producing step
    assert req.output[-1] == eos
    assert len(req.output) == k + 1


def test_eos_at_prefill_emits_no_extra_token(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, cfg.vocab_size, 6))
    ref = _greedy_ref(model, params, prompt, 1, 32)
    eng = ServingEngine(cfg, params, lanes=1, max_len=32)
    req = ServeRequest(prompt=prompt, max_new_tokens=8, eos_token=ref[0])
    stats = eng.run([req])
    assert req.output == [ref[0]]      # EOS from prefill ends the request
    assert stats["decode_steps"] == 0  # no post-EOS decode dispatch


# ----------------------------------------------- lane writes, by cache kind
#: one dense stack (keys and values only), one hybrid (recurrent state
#: beside keys and values)
CACHE_ARCHS = ["phi4_mini_3_8b", "jamba2_3b"]


def _engine(arch, lanes=3, max_len=32):
    cfg = get_smoke_config(arch)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, ServingEngine(cfg, params, lanes=lanes, max_len=max_len)


@pytest.mark.parametrize("arch", CACHE_ARCHS)
def test_admission_overwrites_every_cache_leaf_of_its_lane(arch):
    from repro.models.transformer import cache_layout

    cfg, eng = _engine(arch)
    kinds = set(jax.tree_util.tree_leaves(cache_layout(cfg)))
    assert kinds == ({"kv", "state"} if cfg.family == "hybrid" else {"kv"})
    # a lane left by an earlier request: every leaf holds something else
    eng.caches = jax.tree_util.tree_map(lambda a: jnp.full_like(a, 7),
                                        eng.caches)
    prompt = list(np.random.default_rng(8).integers(0, cfg.vocab_size, 9))
    eng.submit(ServeRequest(prompt=prompt, max_new_tokens=4))
    eng._admit()
    lane = int(np.nonzero(eng.active_mask)[0][0])
    _, want = eng._prefill_one(eng.params, jnp.asarray(prompt)[None])
    for got, one in zip(jax.tree_util.tree_leaves(eng.caches),
                        jax.tree_util.tree_leaves(want)):
        got, one = np.asarray(got), np.asarray(one)
        np.testing.assert_array_equal(got[:, lane], one[:, 0])
        others = np.delete(got, lane, axis=1)
        assert (others == 7).all()
    # the lane is an argument of the compiled write: another lane compiles
    # nothing new
    eng._scatter_lane(lane + 1, want)
    compiled = eng._write_lane._cache_size()
    eng._scatter_lane(lane + 2, want)
    assert eng._write_lane._cache_size() == compiled
    for got, one in zip(jax.tree_util.tree_leaves(eng.caches),
                        jax.tree_util.tree_leaves(want)):
        for i in (lane, lane + 1, lane + 2):
            np.testing.assert_array_equal(np.asarray(got)[:, i],
                                          np.asarray(one)[:, 0])


@pytest.mark.parametrize("arch", CACHE_ARCHS)
def test_scatter_raises_on_a_leaf_without_a_declared_batch_axis(arch):
    cfg, eng = _engine(arch)
    _, one = eng._prefill_one(eng.params, jnp.zeros((1, 5), jnp.int32))
    layout = eng.cache_layout
    pos, leaves = next(iter(layout.items()))
    name = next(iter(leaves))
    for bad in ({**leaves, name: None},
                {k: v for k, v in leaves.items() if k != name},
                {**leaves, name: "weights"}):
        eng.cache_layout = {**layout, pos: bad}
        with pytest.raises(ValueError, match="no declared kind"):
            eng._scatter_lane(0, one)
    # a leaf that does not hold one lane on the batch axis
    eng.cache_layout = layout
    two = jax.tree_util.tree_map(lambda a: jnp.concatenate([a, a], 1), one)
    with pytest.raises(ValueError, match="does not fit lane axis 1"):
        eng._scatter_lane(0, two)


@pytest.mark.parametrize("S", [65, 80, 100, 130, 200])
def test_selective_scan_of_any_length_equals_the_recurrence(S):
    """A length that is not a multiple of the chunk is padded inside the
    scan; the outputs, and the state it hands on, are the unpadded ones."""
    from repro.kernels import ref
    from repro.models.ssm import selective_scan

    rng = np.random.default_rng(S)
    Bb, d, N = 2, 16, 8
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    u, B, C, D, h0 = f(Bb, S, d), f(Bb, S, N), f(Bb, S, N), f(d), f(Bb, d, N)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (Bb, S, d)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (d, N)), jnp.float32)
    y, h = selective_scan(u, dt, A, B, C, D, h0=h0)
    ye, he = ref.ssm_scan_ref(u, dt, A, B, C, D, h0=h0)
    assert y.shape == (Bb, S, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("S", [65, 80, 130])
def test_mamba_prefill_hands_on_the_unpadded_state(S):
    """The state a prefill of ``S`` tokens hands to decoding equals the one
    that the same tokens give one at a time."""
    from repro.models import ssm

    cfg = get_smoke_config("jamba2_3b")
    p = ssm.ssm_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, cfg.d_model)
                          ).astype(jnp.bfloat16)
    y, state = ssm.ssm_apply(p, x, cfg, return_state=True)
    one = jax.jit(lambda xt, st: ssm.ssm_apply(p, xt, cfg, state=st))
    st = ssm.init_ssm_state(cfg, 2)
    ys = []
    for t in range(S):
        yt, st = one(x[:, t:t + 1], st)
        ys.append(yt)
    np.testing.assert_array_equal(np.asarray(state["conv"]),
                                  np.asarray(st["conv"]))
    np.testing.assert_allclose(np.asarray(state["h"]), np.asarray(st["h"]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(y, np.float32),
        np.asarray(jnp.concatenate(ys, axis=1), np.float32), atol=0.05)
