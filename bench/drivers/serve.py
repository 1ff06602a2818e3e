"""Serving cells: a dense model answers an open-loop stream of requests
through ``ServingEngine.submit`` / ``ServingEngine.step``.

Set-up makes the weights on the device from the seed in one jitted call,
builds the engine, and warms every prompt length the window offers, every
lane and the decode step. The window then offers the mix's requests at
their due times. A request is timed from its due time on the client side:
a token counts once the ``step()`` that produced it returns. After the
window no new request arrives; those due in it are served to the end and
enter the tails. Then the program's state is freed and a sample of the
finished requests (the longest among them) is held to the float32
reference.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.counts import Dense
from bench.reference import dense

#: program fields set from a configuration file's published keys
_PUBLISHED = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "d_ff": "intermediate_size", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rope_fraction": "partial_rotary_factor",
    "norm_eps": "rms_norm_eps",
}


def model_config(doc: dict):
    """The program's config for this file: its named repo config with every
    published size set from the file (the file, not the repo, is the
    yardstick)."""
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


def program_params(cfg, sh: dense.Shapes, seed: int):
    """The program's parameter tree, made on the device in one call."""
    from repro.models import build_model

    def make(key):
        layers = jax.vmap(lambda l: dense.layer_weights(sh, key, l))(
            jnp.arange(sh.layers))
        emb, final = dense.embed_weights(sh, key)
        pad = cfg.padded_vocab - sh.vocab
        return {
            "embed": {"tok_embed": jnp.pad(emb, ((0, pad), (0, 0)))},
            "stack": {"pos00": {
                "mixer_norm": {"scale": layers["attn_norm"]},
                "mixer": {"wq": dense.interleave_rope(layers["wq"],
                                                      sh.rope_dims),
                          "wk": dense.interleave_rope(layers["wk"],
                                                      sh.rope_dims),
                          "wv": layers["wv"], "wo": layers["wo"]},
                "ffn_norm": {"scale": layers["ffn_norm"]},
                "ffn": {"w_gate": layers["w_gate"], "w_up": layers["w_up"],
                        "w_down": layers["w_down"]}},
                "final_norm": {"scale": final}}}

    key = dense.root_key(seed)
    want = build_model(cfg).param_specs()
    got = jax.eval_shape(make, key)
    if jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), got) != \
            jax.tree_util.tree_map(lambda s: (s.shape, s.dtype), want):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree_util.tree_structure(want)}")
    return jax.block_until_ready(jax.jit(make)(key))


def _prompt(rng, n, vocab):
    return rng.integers(0, vocab, n).tolist()


def build(doc: dict, prompt_lens, seed: int, mark=None):
    """Set-up: weights from the seed, the engine, and a warm-up that
    compiles each of ``prompt_lens``, fills every lane and runs the decode
    step."""
    from repro.serving import ServeRequest, ServingEngine

    cfg, changed = model_config(doc)
    sh = dense.Shapes.of(doc)
    eng = doc["engine"]
    params = program_params(cfg, sh, seed)
    if mark:
        mark("weights")
    engine = ServingEngine(cfg, params, lanes=eng["lanes"],
                           max_len=eng["max_len"])
    rng = np.random.default_rng([seed, 2])
    lens = list(prompt_lens)
    engine.run([ServeRequest(prompt=_prompt(rng, lens[i % len(lens)],
                                            sh.vocab), max_new_tokens=2)
                for i in range(max(len(lens), 2 * eng["lanes"]))])
    return cfg, changed, sh, engine


def make_requests(plan, seed: int, vocab: int):
    from repro.serving import ServeRequest

    rng = np.random.default_rng([seed, 5])
    return [ServeRequest(prompt=_prompt(rng, p, vocab), max_new_tokens=o)
            for _, p, o in plan]


@dataclasses.dataclass
class Served:
    t0: float
    first: list
    last: list
    got: list
    late: list
    in_window: int
    backlog_at_close: int = 0


def offer(engine, reqs, plan, seconds: float, window, rec) -> Served:
    """Submit each request at its due time, step the engine, and time every
    token as the client sees it; then serve what is left to the end."""
    n = len(reqs)
    pc = time.perf_counter
    with window as w:
        out = Served(w.t0, [0.0] * n, [0.0] * n, [0] * n, [], 0)
        first, last, got = out.first, out.last, out.got
        end = w.t0 + seconds
        due = [w.t0 + d for d, _, _ in plan]
        k, live, closed = 0, [], False
        while True:
            now = pc()
            while k < n and due[k] <= now:
                engine.submit(reqs[k])
                out.late.append(now - due[k])
                live.append(k)
                k += 1
            if not closed and now > end:
                closed = True
                out.backlog_at_close = len(engine.pending)
            if engine.pending or engine.active_mask.any():
                engine.step()
                t = pc()
                still = []
                for i in live:
                    c = len(reqs[i].output)
                    if c != got[i]:
                        if got[i] == 0:
                            first[i] = t
                        if t <= end:
                            out.in_window += c - got[i]
                        got[i], last[i] = c, t
                    if not reqs[i].done_time:
                        still.append(i)
                live = still
            elif k < n:
                with rec.span("idle"):
                    time.sleep(max(due[k] - pc(), 0.0))
            else:
                break
    return out


def tails(served: Served, reqs, plan) -> dict:
    """Client-side times of the requests that got all their tokens."""
    ok = [i for i in range(len(reqs)) if served.got[i]
          == reqs[i].max_new_tokens]
    ttft = [(served.first[i] - served.t0 - plan[i][0]) * 1e3 for i in ok]
    tpot = [(served.last[i] - served.first[i]) * 1e3 / (served.got[i] - 1)
            for i in ok]
    late = np.asarray(served.late or [0.0]) * 1e3
    out = {"ok": ok, "generator_late_ms": {
        "p50": float(np.percentile(late, 50)),
        "p90": float(np.percentile(late, 90)), "max": float(late.max())}}
    for name, v in (("ttft", ttft), ("tpot", tpot)):
        for q in (50, 90):
            out[f"{name}_p{q}_ms"] = float(np.percentile(v, q)) if ok \
                else None
    return out


def run(ctx):
    doc, mix, seed = ctx.config, ctx.traffic, ctx.seed
    plan = traffic.requests(mix, ctx.seconds, seed)
    cfg, changed, sh, engine = build(
        doc, traffic.prompt_lengths(mix, ctx.seconds), seed, ctx.mark)
    reqs = make_requests(plan, seed, sh.vocab)
    ctx.setup_done()
    ctx.log(config_changes={k: list(v) for k, v in changed.items()},
            requests_due=len(plan))

    rec = ctx.recorder()
    if ctx.trace:
        _instrument(engine, rec)
    steps0 = engine.steps
    served = offer(engine, reqs, plan, ctx.seconds, ctx.window(), rec)
    ctx.read_memory()

    t = tails(served, reqs, plan)
    ok = t["ok"]
    ctx.log(generator_late_ms=t["generator_late_ms"], requests=len(reqs),
            finished=len(ok), backlog_at_close=served.backlog_at_close,
            decode_calls=engine.steps - steps0,
            output_tokens_per_s=served.in_window / ctx.seconds,
            **{k: t[k] for k in ("ttft_p90_ms", "tpot_p90_ms")})
    ctx.attempted, ctx.failed = len(reqs), len(reqs) - len(ok)
    ctx.end_to_end(ttft_p50_ms=t["ttft_p50_ms"], tpot_p50_ms=t["tpot_p50_ms"])
    ctx.observe(shapes=Dense.of(cfg), ttft_p90_ms=t["ttft_p90_ms"])

    # ------------------------------------------------------- correctness
    t_check = time.perf_counter()
    sample = _sample(reqs, ok, mix.get("check_sample", 8), seed)
    seqs = [(reqs[i].prompt, reqs[i].output) for i in sample]
    del engine
    gc.collect()
    gaps = dense.token_gaps(sh, seed, seqs, control=ctx.control) \
        if seqs else []
    worst = {k: max((float(g[k].max()) for g in gaps), default=0.0)
             for k in (("gap", "control_gap") if ctx.control else ("gap",))}
    ctx.compare("max_logit_gap",
                worst["control_gap" if ctx.control else "gap"],
                doc["limits"]["max_logit_gap"])
    ctx.log(checked_requests=len(sample),
            checked_tokens=int(sum(len(g["gap"]) for g in gaps)),
            max_gaps=worst, check_s=time.perf_counter() - t_check)
    return ctx


def _instrument(engine, rec) -> None:
    """Traced runs only: a host span round each ``step`` and each admission,
    and with each span what its device programs computed: the prompt
    length of every prefill in an ``admit`` span, the context of every
    active lane in a ``step`` span's decode. Both spans block until their
    programs have run, so each program lies inside its span. These wrap the
    engine's private ``_admit``, ``_prefill_one`` and ``_decode``; if they
    are renamed, the metrics that read them go silent.
    """
    admit, prefill = engine._admit, engine._prefill_one
    step, decode = engine.step, engine._decode
    admits, steps = rec.records["admit"], rec.records["step"]

    def timed_admit():
        before = len(engine.pending)
        admits.append([])
        with rec.span("admit"):
            admit()
        rec.count("admitted", before - len(engine.pending))

    def recorded_prefill(params, tokens):
        admits[-1].append(int(tokens.shape[1]))
        return prefill(params, tokens)

    def timed_step():
        steps.append([])
        with rec.span("step"):
            return step()

    def recorded_decode(params, caches, tokens, positions):
        steps[-1].append((np.asarray(positions)[engine.active_mask] + 1)
                         .tolist())
        return decode(params, caches, tokens, positions)

    engine._admit, engine._prefill_one = timed_admit, recorded_prefill
    engine.step, engine._decode = timed_step, recorded_decode


def _sample(reqs, ok, k, seed):
    """``k`` finished requests drawn from the seed, the one with the most
    tokens (prompt and output) always among them."""
    if not ok:
        return []
    longest = max(ok, key=lambda i: len(reqs[i].prompt) + len(reqs[i].output))
    rest = [i for i in ok if i != longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[j] for j in sorted(pick)]
