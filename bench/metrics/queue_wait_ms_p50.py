"""Median time a request waits in ``ServingEngine.pending``, from its
submission to the start of its admission (span ``engine.queue``), over
the requests admitted in the capture, in ms."""
from bench import program_spans as ps


def read(obs):
    cap = ps.last_capture(obs)
    wait = ps.median(ps.seconds(cap, "engine.queue")) if cap else None
    return None if wait is None else wait * 1e3
