"""Find a serving cell's knee: the highest offered rate whose backlog does
not grow over a window. Run once when a serving cell is defined; the
cell's traffic file then fixes its rate (about 0.8 of the knee).

  python3 bench/sweep.py --workload serve.phi4mini.chat --seed 7 \\
      --seconds 30 --rates 1.5 2 2.5 3

One process: set-up once, then for each rate a window of the cell's mix
at that rate, served to the end before the next. Prints one JSON line per
rate: offered and finished requests, the queue left waiting when the
window closed, the drain after it, tokens per second and the tails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import run, traffic
    from bench.drivers import serve

    spec = run.load_spec()
    cell, doc, mix = run.resolve(spec, args.workload)
    run.setup_jax()
    run.check_chip(cell["chips"])
    mixes = [dict(mix, arrivals=dict(mix["arrivals"], rate=rate))
             for rate in args.rates]
    lens = sorted({n for m in mixes
                   for n in traffic.prompt_lengths(m, args.seconds)})
    _, _, sh, engine = serve.build(doc, lens, args.seed)
    rec = run.Recorder(False)
    for k, (rate, m) in enumerate(zip(args.rates, mixes)):
        plan = traffic.requests(m, args.seconds, args.seed + k)
        reqs = serve.make_requests(plan, args.seed + k, sh.vocab)
        served = serve.offer(engine, reqs, plan, args.seconds,
                             run.timed_window(), rec)
        t = serve.tails(served, reqs, plan)
        drain = max(served.last) - served.t0 - args.seconds
        print(json.dumps({
            "rate": rate, "requests": len(reqs), "finished": len(t["ok"]),
            "backlog_at_close": served.backlog_at_close,
            "drain_s": drain,
            "output_tokens_per_s": served.in_window / args.seconds,
            "offered_tokens_per_s": sum(o for _, _, o in plan)
            / args.seconds,
            **{k: t[k] for k in ("ttft_p50_ms", "ttft_p90_ms",
                                 "tpot_p50_ms", "tpot_p90_ms")}}),
            flush=True)
    return 0


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    if sys.path and Path(sys.path[0]).resolve() == root / "bench":
        del sys.path[0]
    sys.path[:0] = [str(root), str(root / "src")]
    sys.exit(main())
