"""Serving driver: continuous-batching engine over a model config.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma_2b --smoke \
      --requests 32 --lanes 8

Without ``--smoke`` the full published config is served.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import ServeRequest, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma_2b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    # one compiled init: eager init holds every layer and its stacked copy
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(cfg, params, lanes=args.lanes,
                           max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    reqs = [ServeRequest(
        prompt=list(rng.integers(0, cfg.vocab_size, args.prompt_len)),
        max_new_tokens=args.max_new) for _ in range(args.requests)]
    stats = engine.run(reqs)
    print("== serving stats ==")
    for k, v in stats.items():
        print(f"  {k}: {v:.4f}" if isinstance(v, float) else f"  {k}: {v}")
    print(f"  (multilevel scheduling: {stats['tokens_per_dispatch']:.2f} "
          f"tasks aggregated per dispatch)")


if __name__ == "__main__":
    main()
