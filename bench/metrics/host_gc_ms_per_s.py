"""Host time in Python's garbage collector (span ``host.gc``) per second
of the capture, in ms/s."""
from bench import program_spans as ps


def read(obs):
    cap = ps.last_capture(obs)
    if cap is None or cap.seconds <= 0:
        return None
    return cap.seconds_in("host.gc") / cap.seconds * 1e3
