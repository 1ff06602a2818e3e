"""Device idle time inside the executor's blocking handoffs per task
completed, in us: host time in ``exec.dispatch`` and ``exec.wait`` less
the device's busy time (profiler trace), all over the whole capture. With
a blocking executor every program runs inside a handoff, so this is the
launch lag and the sync lag together."""
from bench import program_spans as ps


def read(obs):
    cap, n = ps.last_capture(obs), ps.tasks(obs)
    if cap is None or not n or not cap.count(ps.WAIT):
        return None
    busy = ps.device_busy_s(obs)
    if busy is None:
        return None
    host = cap.seconds_in(ps.DISPATCH) + cap.seconds_in(ps.WAIT)
    return (host - busy) / n * 1e6
