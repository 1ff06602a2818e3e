"""What the readers of the program's own spans share.

The program records its spans (``repro.obs.spans``) in memory while the
profile is captured; a traced run reads the record of that capture in the
process that ran the cell, on the program's clock. Each reader returns
None where there is nothing to read: no trace, a program without spans,
an empty record, or one that dropped spans.
"""
from __future__ import annotations

import statistics
from typing import List, Optional

from bench import trace as tr

DISPATCH, WAIT = "exec.dispatch", "exec.wait"


def last_capture(obs):
    """The program's record of the traced window, or None."""
    if obs.get("trace") is None:
        return None
    try:
        from repro.obs import spans
    except ImportError:                 # a program that records no spans
        return None
    cap = spans.last_capture()
    if cap is None or not cap.records or cap.dropped:
        return None
    return cap


def tasks(obs) -> Optional[int]:
    """Tasks the scheduler completed in the capture (task-set cells)."""
    return obs.get("tasks_done") or None


def seconds(cap, name: str) -> List[float]:
    """Seconds of each span named ``name``, in the order they ended."""
    return [(t1 - t0) * 1e-9 for _, _, t0, t1 in cap.of(name)]


def host_gaps_s(cap) -> float:
    """Host seconds from the end of each ``exec.wait`` to the start of the
    next ``exec.dispatch``: all the host does between two handoffs."""
    ev = sorted(((t0, t1, n) for n, _, t0, t1 in cap.records
                 if n in (DISPATCH, WAIT)))
    return sum(b[0] - a[1] for a, b in zip(ev, ev[1:])
               if a[2] == WAIT and b[2] == DISPATCH) * 1e-9


def device_busy_s(obs) -> Optional[float]:
    """Seconds in which some program ran on the device over the whole
    capture (mean over devices), not cut at the window as the spans are
    not."""
    t = obs["trace"]
    if not t.programs:
        return None
    busy = [tr.busy_ns(ev) for ev in t.programs.values()]
    return sum(busy) / len(busy) * 1e-9


def median(xs: List[float]) -> Optional[float]:
    return statistics.median(xs) if xs else None
