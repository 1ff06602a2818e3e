"""Host time between handoffs per task completed, in us: from the end of
each ``exec.wait`` to the start of the next ``exec.dispatch`` (scheduler
phases, event loop, completion callbacks, set submission). With a
blocking executor the device idles through all of it."""
from bench import program_spans as ps


def read(obs):
    cap, n = ps.last_capture(obs), ps.tasks(obs)
    if cap is None or not n or not cap.count(ps.WAIT):
        return None
    return ps.host_gaps_s(cap) / n * 1e6
