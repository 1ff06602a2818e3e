"""Model FLOPs of the prefill programs (``bench/counts.py``, from each
prompt's length) over their device time times the bf16 peak, in %."""
from bench import readers


def read(obs):
    runs = readers.within(obs, readers.PREFILL, "admit")
    if not runs:
        return None
    flops = sum(obs["shapes"].prefill_flops(s) for _, s in runs)
    secs = sum(t for t, _ in runs)
    return 100.0 * flops / (secs * readers.peak(obs)["bf16_flops_per_s"])
