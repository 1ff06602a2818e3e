"""Chip smoke: drive the system's main paths once on a TPU and check them.

  python chip_smoke.py              # one chip: phases a, b and c
  python chip_smoke.py --chips 4    # the sharded serving path only

Phases, each printing one JSON line with its counts, wall seconds, compile
seconds and the devices' ``peak_bytes_in_use``:

  a. scheduler -> device. The paper's cluster (P = 1408 slots) runs a
     Table-9-shaped task set of 4 tasks per slot through ``Scheduler`` with
     ``JaxDispatchExecutor``: once task by task, once aggregated (mimo) into
     P bundles. Every task's checksum must equal plain ``jax.numpy``'s.
  b. serving. Full-width Gemma-2B (``get_config("gemma_2b")``, random
     weights from ``--seed``) answers 16 requests through
     ``ServingEngine.run``; every generated token is checked against a
     teacher-forced ``model.forward``.
  c. kernels. The four Pallas kernels at real widths must compile for the
     chip (``tpu_custom_call`` in the HLO) and match their ``kernels/ref.py``
     oracles.
  --chips 4: codeqwen1.5-7b prefill/decode steps sharded over
     (data=1, model=4) answer greedy requests; a depth-cut copy runs on four
     chips and on one, and their logits must agree.

Wall times and tasks/s are host-clock observations, not metrics. When JAX
finds no TPU the script exits non-zero before any phase; a failed check
raises. Only a passing run prints the last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.core import (  # noqa: E402
    FAMILIES, Job, JobState, MultilevelConfig, ResourceManager, Scheduler,
    aggregate)
from repro.core.executor import JaxDispatchExecutor  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    build_decode_step, build_prefill_step)
from repro.models import build_model  # noqa: E402
from repro.serving import ServeRequest, ServingEngine  # noqa: E402

#: a reference top-2 logit margin above this decides the greedy token even
#: under bf16 rounding (logits are bf16; |logit| <~ 8 has an ulp of 2^-5)
MARGIN_TOL = 0.125
#: 4-chip vs 1-chip logits: max |diff| over the max |logit| (bf16 matmuls
#: reduced in a different order)
LOGIT_RTOL = 0.05


class SmokeError(AssertionError):
    """A check of the smoke run failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# --------------------------------------------------------------- measuring
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Counts XLA compiles (persistent-cache hits included) and the seconds
    spent tracing, lowering and compiling while the meter is entered."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._on_duration = self._duration
        self._on_event = self._event

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == _COMPILE_EVENTS[-1]

    def _event(self, event: str, **_) -> None:
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def __enter__(self) -> "CompileMeter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def peak_bytes():
    """``peak_bytes_in_use`` of every local device (None where the backend
    keeps no statistics, as the CPU does)."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


def run_phase(name: str, fn, *args, **kw) -> dict:
    t0 = time.perf_counter()
    with CompileMeter() as meter:
        out = fn(*args, **kw)
    line = {"phase": name, **out,
            "wall_s": time.perf_counter() - t0,
            "compiles": meter.compiles, "compile_s": meter.seconds,
            "cache_hits": meter.cache_hits,
            "peak_bytes_in_use": peak_bytes()}
    print(json.dumps(line), flush=True)
    return line


# ------------------------------------------------ a. scheduler -> device
def task_program(n: int, iters: int):
    """One device task: ``iters`` rounds of x <- ((x @ w) mod 7) - 3 on an
    [n, n] bf16 matrix seeded by the task index; returns sum(x).

    Every value is a small integer and products accumulate in f32, so each
    step is exact and any correct evaluation order gives the same checksum.
    """
    def task(w, index):
        r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        x = ((r * 3 + c * 5 + index) % 7 - 3).astype(jnp.bfloat16)
        for _ in range(iters):
            y = jnp.dot(x, w, preferred_element_type=jnp.float32)
            x = (y.astype(jnp.int32) % 7 - 3).astype(jnp.bfloat16)
        return jnp.sum(x.astype(jnp.int32))
    return task


def _schedule(P: int, job: Job):
    rm = ResourceManager()
    rm.add_nodes(P, slots=1)
    ex = JaxDispatchExecutor()
    sched = Scheduler(rm, profile=FAMILIES["inproc"], executor=ex)
    t0 = time.perf_counter()
    sched.submit(job)
    sched.run()
    wall = time.perf_counter() - t0
    check(job.state is JobState.COMPLETED, f"{job.name} ended {job.state}")
    check(not ex.errors, f"{len(ex.errors)} payloads failed, first: "
          f"{next(iter(ex.errors.values()), None)!r}")
    return ex.results, wall


def phase_scheduler(*, P: int = 1408, tasks_per_slot: int = 4, n: int = 2048,
                    iters: int = 8, seed: int = 0, ref_chunk: int = 4) -> dict:
    n_tasks = P * tasks_per_slot
    task = task_program(n, iters)
    prog = jax.jit(task)
    w = (jax.random.randint(jax.random.PRNGKey(seed), (n, n), -3, 4)
         .astype(jnp.bfloat16))
    prog(w, 0).block_until_ready()          # compile outside the timed runs
    payloads = [functools.partial(prog, w, i) for i in range(n_tasks)]

    direct = Job.array(n_tasks, payloads=payloads, name="smoke-direct")
    results, direct_wall = _schedule(P, direct)
    got_direct = np.array([int(results[(direct.job_id, i)])
                           for i in range(n_tasks)])

    raw = Job.array(n_tasks, payloads=payloads, name="smoke-mimo")
    bundled = aggregate(raw, P, MultilevelConfig(mode="mimo"))
    check(bundled.n_tasks == P, f"{bundled.n_tasks} bundles, not {P}")
    results, mimo_wall = _schedule(P, bundled)
    got_mimo = np.array([int(c) for b in range(bundled.n_tasks)
                         for c in results[(bundled.job_id, b)]])

    # plain jax.numpy: the same task body run op by op (not jitted), batched.
    # Eager batched ops hold large temporaries: on a v5e a chunk of 64 tasks
    # peaked at 16.7 GB of HBM, a chunk of 4 at 0.42 GB.
    t0 = time.perf_counter()
    batched = jax.vmap(task, in_axes=(None, 0))
    want = np.concatenate([
        np.asarray(batched(w, jnp.arange(s, min(s + ref_chunk, n_tasks))))
        for s in range(0, n_tasks, ref_chunk)])
    ref_wall = time.perf_counter() - t0
    check(np.array_equal(got_direct, want),
          f"direct: {int(np.sum(got_direct != want))} checksums differ")
    check(np.array_equal(got_mimo, want),
          f"mimo: {int(np.sum(got_mimo != want))} checksums differ")
    return {"P": P, "tasks": n_tasks, "bundles": bundled.n_tasks,
            "task_matrix": n, "task_iters": iters,
            "checksums_equal": n_tasks,
            "host_direct_wall_s": direct_wall,
            "host_direct_tasks_per_s": n_tasks / direct_wall,
            "host_mimo_wall_s": mimo_wall,
            "host_mimo_tasks_per_s": n_tasks / mimo_wall,
            "host_reference_wall_s": ref_wall}


# --------------------------------------------------------- b. serving
def teacher_forced_check(model, params, reqs, *, batch: int,
                         margin_tol: float = MARGIN_TOL) -> dict:
    """Hold every generated token to ``model.forward`` over prompt + output.

    At each generated position the engine's token must be the reference
    argmax wherever the reference top-2 margin exceeds ``margin_tol``, and
    its reference logit must lie within ``margin_tol`` of the top everywhere.
    """
    L = max(len(r.prompt) + len(r.output) for r in reqs)

    @jax.jit
    def stats(params, toks, nxt):
        logits = model.forward(params, toks)[0].astype(jnp.float32)
        top2 = jax.lax.top_k(logits, 2)[0]
        chosen = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        return (jnp.argmax(logits, axis=-1), top2[..., 0] - top2[..., 1],
                top2[..., 0] - chosen)

    positions = decisive = 0
    worst_gap = 0.0
    for s in range(0, len(reqs), batch):
        chunk = reqs[s:s + batch]
        toks = np.zeros((batch, L + 1), np.int32)
        for b, r in enumerate(chunk):
            seq = list(r.prompt) + list(r.output)
            toks[b, :len(seq)] = seq
        am, margin, gap = (np.asarray(x) for x in stats(
            params, jnp.asarray(toks[:, :L]), jnp.asarray(toks[:, 1:])))
        for b, r in enumerate(chunk):
            p, m = len(r.prompt), len(r.output)
            sl = slice(p - 1, p - 1 + m)
            out = np.asarray(r.output)
            sure = margin[b, sl] > margin_tol
            bad = np.nonzero(sure & (am[b, sl] != out))[0]
            check(bad.size == 0, f"request {r.request_id}: engine token != "
                  f"reference argmax at generated positions {bad.tolist()}")
            g = float(np.max(gap[b, sl]))
            check(g <= margin_tol, f"request {r.request_id}: engine token "
                  f"{g} below the reference top logit")
            positions += m
            decisive += int(np.sum(sure))
            worst_gap = max(worst_gap, g)
    return {"checked_positions": positions, "decisive_positions": decisive,
            "max_gap_to_top": worst_gap, "margin_tol": margin_tol}


def phase_serving(cfg: ModelConfig, *, lanes: int = 8, max_len: int = 512,
                  n_requests: int = 16, prompt_lens=(16, 64),
                  max_new: int = 16, seed: int = 0) -> dict:
    model = build_model(cfg)
    # one compiled init: eager init holds every layer and its stacked copy
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    engine = ServingEngine(cfg, params, lanes=lanes, max_len=max_len)
    rng = np.random.default_rng(seed)

    def make(n):
        lens = rng.permutation([prompt_lens[i % len(prompt_lens)]
                                for i in range(n)])
        return [ServeRequest(prompt=rng.integers(0, cfg.vocab_size,
                                                 int(L)).tolist(),
                             max_new_tokens=max_new) for L in lens]

    # warm-up: every lane and every prompt length once
    warm = make(max(lanes, len(prompt_lens)))
    with CompileMeter() as warm_meter:
        engine.run(warm)
    reqs = make(n_requests)
    with CompileMeter() as meter:
        stats = engine.run(reqs)
    for r in warm + reqs:
        check(len(r.output) == max_new,
              f"request {r.request_id}: {len(r.output)} tokens, not {max_new}")
    verdict = teacher_forced_check(model, params, warm + reqs, batch=lanes)
    return {"arch": cfg.name, "n_layers": cfg.n_layers,
            "d_model": cfg.d_model, "vocab": cfg.vocab_size,
            "lanes": lanes, "max_len": max_len,
            "requests": len(reqs), "warmup_requests": len(warm),
            "prompt_lens": sorted(set(len(r.prompt) for r in reqs)),
            "tokens": sum(len(r.output) for r in reqs),
            "decode_steps": stats["decode_steps"],
            "tokens_per_dispatch": stats["tokens_per_dispatch"],
            "host_wall_s": stats["wall_s"],
            "warmup_compiles": warm_meter.compiles,
            "compiles_after_warmup": meter.compiles, **verdict}


# --------------------------------------------------------- c. kernels
#: real widths: Gemma-2B attention (prefill of 2048 and of 12 tokens),
#: Granite-MoE expert GEMM, Jamba's Mamba scan, xLSTM-1.3B's sLSTM heads
CHIP_KERNELS = {
    "flash": ((1, 2048, 8, 1, 256), (1, 12, 8, 1, 256)),   # B,S,Hq,Hkv,hd
    "expert_gemm": ((32, 512, 1024, 512),),               # E,M,K,N
    "ssm_scan": ((1, 2048, 8192, 16),),                   # B,S,d_inner,N
    "slstm_scan": ((1, 512, 4, 512),),                    # B,S,H,dh
}


def _kernel_cases(shapes, key):
    """(name, kernel, ref, args, tolerance per output leaf, None for a leaf
    left unchecked) per case; inputs are bf16 as served and
    tolerances are tests/test_kernels.py's."""
    bf = jnp.bfloat16
    k = iter(jax.random.split(key, 64))
    normal = lambda shape, dt=bf: jax.random.normal(next(k), shape).astype(dt)
    t2, t5 = dict(atol=2e-2, rtol=2e-2), dict(atol=5e-2, rtol=5e-2)
    for B, S, Hq, Hkv, hd in shapes["flash"]:
        args = (normal((B, S, Hq, hd)), normal((B, S, Hkv, hd)),
                normal((B, S, Hkv, hd)))
        yield (f"flash_attention_S{S}", ops.flash_attention,
               ref.flash_attention_ref, args, [t2])
    for E, M, K, N in shapes["expert_gemm"]:
        yield (f"expert_gemm_E{E}", ops.expert_gemm, ref.expert_gemm_ref,
               (normal((E, M, K)), normal((E, K, N))), [t5])
    for B, S, d, N in shapes["ssm_scan"]:
        dt = jax.random.uniform(next(k), (B, S, d), minval=1e-3,
                                maxval=0.1).astype(bf)
        A = -jax.random.uniform(next(k), (d, N), minval=0.5, maxval=2.0)
        args = (normal((B, S, d)), dt, A, normal((B, S, N)),
                normal((B, S, N)), normal((d,), jnp.float32))
        yield (f"ssm_scan_d{d}", ops.ssm_scan, ref.ssm_scan_ref, args,
               [t2, dict(atol=1e-2, rtol=1e-2)])
    for B, S, H, dh in shapes["slstm_scan"]:
        r = normal((4, H, dh, dh), jnp.float32) * (0.2 * dh ** -0.5)
        zeros = jnp.zeros((B, H, dh), jnp.float32)
        args = (normal((B, S, 4, H * dh)), r, zeros, zeros,
                jnp.full((B, H, dh), -1e30, jnp.float32), zeros)
        yield (f"slstm_scan_H{H}_dh{dh}", ops.slstm_scan, ref.slstm_scan_ref,
               args, [t2, None, None, None, t2])


def phase_kernels(*, shapes=CHIP_KERNELS, seed: int = 0) -> dict:
    on_tpu = jax.default_backend() == "tpu"
    done = {}
    for name, kern, oracle, args, tols in _kernel_cases(
            shapes, jax.random.PRNGKey(seed)):
        compiled = kern.lower(*args).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        check(mosaic or not on_tpu, f"{name}: no tpu_custom_call in its HLO")
        got = jax.tree_util.tree_leaves(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree_util.tree_leaves(jax.jit(oracle)(*args))
        for g, w, tol in zip(got, want, tols):
            if tol is not None:
                np.testing.assert_allclose(np.asarray(g, np.float32),
                                           np.asarray(w, np.float32), **tol,
                                           err_msg=name)
        done[name] = {"tpu_custom_call": mosaic,
                      "shape": list(np.shape(args[0]))}
    return {"kernels": done}


# ----------------------------------------------- --chips 4: sharded serving
def _placement(tree, devices) -> dict:
    """Bytes of ``tree`` held by each device; every leaf must span all of
    ``devices``."""
    devs = set(devices)
    per = {d.id: 0 for d in devices}
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        on = x.sharding.device_set
        check(on == devs, f"leaf {x.shape} on {sorted(d.id for d in on)}")
        total += x.nbytes
        for s in x.addressable_shards:
            per[s.device.id] += s.data.nbytes
    return {"total_bytes": total, "bytes_per_device": per}


def greedy_sharded(cfg: ModelConfig, mesh, prompts: np.ndarray,
                   new_tokens: int, *, seed: int, forced=None) -> dict:
    """Prefill + ``new_tokens`` greedy decode steps with the repo's sharded
    step builders on ``mesh``. ``forced`` [B, new_tokens] feeds those tokens
    instead of the model's own (teacher forcing, for comparing meshes)."""
    B, S = prompts.shape
    prefill = build_prefill_step(
        cfg, mesh, ShapeConfig("smoke_prefill", "prefill", S, B),
        pad_heads=False)
    decode = build_decode_step(
        cfg, mesh, ShapeConfig("smoke_decode", "decode", S + new_tokens, B),
        pad_heads=False)
    params = jax.jit(build_model(cfg).init,
                     out_shardings=prefill.in_shardings[0])(
        jax.random.PRNGKey(seed))
    grow = jax.jit(lambda caches: jax.tree_util.tree_map(
        lambda x: jnp.pad(x, [(0, 0), (0, 0), (0, new_tokens)]
                          + [(0, 0)] * (x.ndim - 3)), caches),
        out_shardings=decode.in_shardings[2])
    logits, caches = prefill.jit()(params, {"tokens": jnp.asarray(prompts)})
    caches = grow(caches)
    step = decode.jit()
    out = [np.asarray(logits, np.float32)[:, :cfg.vocab_size]]
    toks = []
    for i in range(new_tokens):
        tok = forced[:, i] if forced is not None else np.argmax(out[-1], -1)
        toks.append(tok)
        logits, caches = step(params, jnp.asarray(tok[:, None], jnp.int32),
                              caches, jnp.int32(S + i))
        out.append(np.asarray(logits, np.float32)[:, :cfg.vocab_size])
    devices = list(mesh.devices.flat)
    return {"logits": np.stack(out), "tokens": np.stack(toks, 1),
            "params": _placement(params, devices),
            "caches": _placement(caches, devices)}


def phase_sharded(cfg: ModelConfig, *, cut_layers: int, batch: int = 4,
                  prompt_len: int = 16, new_tokens: int = 8,
                  seed: int = 0) -> dict:
    devs = jax.devices()[:4]
    check(len(devs) == 4, f"{len(devs)} devices; the sharded path needs 4")
    mesh4 = make_mesh((1, 4), ("data", "model"), devices=devs)
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32)

    full = greedy_sharded(cfg, mesh4, prompts, new_tokens, seed=seed)
    check(np.all(np.isfinite(full["logits"])), "non-finite logits")
    for what in ("params", "caches"):
        p = full[what]
        check(max(p["bytes_per_device"].values()) <= 0.5 * p["total_bytes"],
              f"{what} are not split over the 4 devices: {p}")

    cut = dataclasses.replace(cfg, n_layers=cut_layers)
    on4 = greedy_sharded(cut, mesh4, prompts, new_tokens, seed=seed)
    on1 = greedy_sharded(cut, mesh1, prompts, new_tokens, seed=seed,
                         forced=on4["tokens"])
    scale = float(np.max(np.abs(on1["logits"])))
    err = float(np.max(np.abs(on4["logits"] - on1["logits"]))) / scale
    check(err <= LOGIT_RTOL, f"4-chip vs 1-chip logits differ by {err} of "
          f"their scale (limit {LOGIT_RTOL})")
    return {"arch": cfg.name, "n_layers": cfg.n_layers,
            "cut_layers": cut_layers, "mesh": {"data": 1, "model": 4},
            "batch": batch, "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "devices": [d.id for d in devs],
            "full_tokens": full["tokens"].tolist(),
            "full_param_bytes": full["params"],
            "full_cache_bytes": full["caches"],
            "cut_rel_logit_err": err, "logit_rtol": LOGIT_RTOL,
            "cut_argmax_agree": float(np.mean(
                np.argmax(on4["logits"], -1) == np.argmax(on1["logits"], -1)))}


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serving path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); no phase ran", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({"device": device}), flush=True)
    if args.chips == 4:
        run_phase("sharded_serving", phase_sharded,
                  get_config("codeqwen15_7b"), cut_layers=8, seed=args.seed)
    else:
        run_phase("scheduler_device", phase_scheduler, seed=args.seed)
        run_phase("serving", phase_serving, get_config("gemma_2b"),
                  seed=args.seed)
        run_phase("kernels", phase_kernels, seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
