"""Model FLOPs of the decode steps (``bench/counts.py``: one token for each
active lane, attending over its occupied positions) over their device time
times the bf16 peak, in %."""
from bench import readers


def read(obs):
    runs = readers.within(obs, readers.DECODE, "step")
    if not runs:
        return None
    flops = sum(obs["shapes"].decode_flops(c) for _, c in runs)
    secs = sum(t for t, _ in runs)
    return 100.0 * flops / (secs * readers.peak(obs)["bf16_flops_per_s"])
