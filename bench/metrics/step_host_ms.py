"""Host time of one decode step outside the wait for its tokens, in ms:
``engine.prepare`` (the token batch) + ``engine.decode`` (the jitted
call) + ``engine.retire`` (appending tokens, freeing lanes), over the
decode steps of the capture."""
from bench import program_spans as ps

PARTS = ("engine.prepare", "engine.decode", "engine.retire")


def read(obs):
    cap = ps.last_capture(obs)
    steps = cap.count("engine.decode") if cap else 0
    if not steps:
        return None
    return sum(cap.seconds_in(p) for p in PARTS) / steps * 1e3
