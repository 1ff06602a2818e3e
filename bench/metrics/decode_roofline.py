"""The decode steps' least time over their device time, in %. A step's
least time is the larger of its FLOPs over the bf16 peak and its bytes
(weights once, keys and values of the occupied positions) over the HBM
bandwidth (``bench/counts.py``)."""
from bench import counts, readers


def read(obs):
    runs = readers.within(obs, readers.DECODE, "step")
    if not runs:
        return None
    sh, pk = obs["shapes"], readers.peak(obs)
    least = sum(counts.least_seconds(sh.decode_flops(c), sh.decode_bytes(c),
                                     pk) for _, c in runs)
    return 100.0 * least / sum(t for t, _ in runs)
