"""Mean device idle time between two consecutive decode programs, in ms,
leaving out the pairs between which the client waited with no lane
active (profiler trace)."""
from bench import readers
from bench import trace as tr


def read(obs):
    ev = readers.device_programs(obs)
    if ev is None:
        return None
    idle = [(s, e) for n, s, e in obs["trace"].spans if n == "idle"]
    gaps = tr.gaps_between(ev, readers.DECODE, exclude=idle)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-6
