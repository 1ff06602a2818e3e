"""Mean number of tasks in flight on the executor over the capture, by
Little's law: total ``exec.inflight`` seconds (each task from the start of
its dispatch to the collection of its result) over the capture's seconds.
A blocking handoff reads just under 1, a window of depth w about w - 0.5."""
from bench import program_spans as ps

INFLIGHT = "exec.inflight"


def read(obs):
    cap = ps.last_capture(obs)
    if cap is None or not cap.count(INFLIGHT) or cap.seconds <= 0:
        return None
    return cap.seconds_in(INFLIGHT) / cap.seconds
