"""Host time in the executor's payload calls (span ``exec.dispatch``:
tracing and enqueueing the task's programs) per task completed, in us."""
from bench import program_spans as ps


def read(obs):
    cap, n = ps.last_capture(obs), ps.tasks(obs)
    if cap is None or not n or not cap.count(ps.DISPATCH):
        return None
    return cap.seconds_in(ps.DISPATCH) / n * 1e6
