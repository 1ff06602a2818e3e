"""Training driver: config-driven, fault-tolerant, restartable.

Usage (CPU-scale example):
  PYTHONPATH=src python -m repro.launch.train --arch phi4_mini_3_8b \
      --smoke --steps 100 --batch 8 --seq 128 --ckpt-dir /tmp/run1

On a real pod the same driver runs under the production mesh
(--mesh single|multi); on this CPU container it uses the host mesh.
Restart is automatic: if the checkpoint dir has a committed step, training
resumes from it (bit-exact thanks to the counter-seeded data pipeline).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import RunConfig, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.data import SyntheticTokens, TokenPipeline
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import build_train_step, rules_for
from repro.models import build_model
from repro.models.model import FRONTEND_TOKENS
from repro.optim import AdamW, cosine_schedule


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4_mini_3_8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="host", choices=["host", "single", "multi"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    run = RunConfig(model=cfg, seq_len=args.seq, global_batch=args.batch,
                    learning_rate=args.lr, total_steps=args.steps)

    model = build_model(cfg)
    rules = rules_for(mesh, cfg, shape)
    built = build_train_step(cfg, mesh, shape, run=run, rules=rules)
    step_fn = built.jit()

    nf = FRONTEND_TOKENS.get(cfg.frontend, 0)
    source = SyntheticTokens(cfg.vocab_size, args.seq, args.batch,
                             frontend_dim=cfg.frontend_dim if nf else 0,
                             frontend_tokens=nf)

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    params = model.init(jax.random.PRNGKey(run.seed))
    opt = AdamW(learning_rate=cosine_schedule(
        run.learning_rate, run.warmup_steps, run.total_steps))
    state = {"params": params, "opt": opt.init(params)}
    if mgr is not None and mgr.latest_step() is not None:
        state, extra = mgr.restore(state)
        start_step = int(extra.get("step", mgr.latest_step()))
        print(f"[restore] resumed from step {start_step}")

    pipe = TokenPipeline(source, mesh=None, start_step=start_step)
    t0 = time.time()
    losses = []
    for _ in range(start_step, args.steps):
        step, batch = next(pipe)
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            rate = (step + 1 - start_step) / (time.time() - t0)
            print(f"step {step + 1:5d}  loss {losses[-1]:.4f}  "
                  f"ce {float(metrics['ce']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}  {rate:.2f} it/s",
                  flush=True)
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, state, extra={"step": step + 1})
    if mgr is not None:
        mgr.save(args.steps, state, extra={"step": args.steps})
        mgr.wait()
    pipe.close()
    if len(losses) > 20:
        first = float(np.mean(losses[:10]))
        last = float(np.mean(losses[-10:]))
        print(f"[done] loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
