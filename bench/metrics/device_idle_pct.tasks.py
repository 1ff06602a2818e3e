"""Share of the window in which no program ran on the device, in %
(profiler trace; task-set cells)."""
from bench import trace as tr


def read(obs):
    t = obs.get("trace")
    if t is None or not t.programs or t.window is None:
        return None
    busy = [tr.busy_ns(ev) for ev in t.device_programs()]
    span = t.window[1] - t.window[0]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
