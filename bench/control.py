"""The controls that the limits of ``correct`` are set against.

  python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one run of the cell with the plain reference computed one
precision below the configuration's put in the program's place: task
checksums accumulated in bf16 instead of f32; for a served model, at each
served position, the token that a float8 computation of the same model
puts first. Each line gives the numbers compared and their limits; every
control run should come out not correct. Serving runs also log the sound
program's reading of the same seed (``max_gaps.gap``), so one process
gives both readings a limit is set from. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import run

    spec = run.load_spec()
    cell, doc, mix = run.resolve(spec, args.workload)
    run.setup_jax()
    run.check_chip(cell["chips"])
    for seed in args.seeds:
        out = run.run_cell(spec, cell, doc, mix, seed=seed,
                           seconds=args.seconds, trace=False, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    if sys.path and Path(sys.path[0]).resolve() == root / "bench":
        del sys.path[0]
    sys.path[:0] = [str(root), str(root / "src")]
    sys.exit(main())
