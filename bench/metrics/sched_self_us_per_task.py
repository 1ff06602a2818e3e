"""Scheduler self time (``obs/profile.SelfProfiler``: admission, cycle,
dispatch and completion, executor excluded) per task completed, in us."""


def read(obs):
    n = obs.get("tasks_done")
    if not n or obs.get("sched_self_s") is None:
        return None
    return obs["sched_self_s"] / n * 1e6
