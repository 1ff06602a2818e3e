"""Mean device time of one decode program (``_decode_fn``), in ms: every
run in the trace, none matched to the host."""
from bench import readers
from bench import trace as tr


def read(obs):
    ev = readers.device_programs(obs)
    secs = tr.time_in(ev, readers.DECODE) if ev is not None else []
    if not secs:
        return None
    return sum(secs) / len(secs) * 1e3
