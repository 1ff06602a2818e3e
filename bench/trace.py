"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

``load`` keeps three kinds of event, all on the trace's one clock:
  * programs: each run of a compiled program on a device
    (line ``XLA Modules`` of a ``/device:`` plane);
  * ops: each operation inside them (line ``XLA Ops``);
  * spans: host spans the benchmark opened with
    ``jax.profiler.TraceAnnotation("bench:<name>")``.
The window is the ``bench:window`` span. Everything else is plain interval
arithmetic over ``(name, start_ns, end_ns)`` tuples, so it can be checked
without a chip.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, end_ns

SPAN_PREFIX = "bench:"
PROGRAM_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclass
class Trace:
    programs: Dict[str, List[Event]] = field(default_factory=dict)  # device
    ops: Dict[str, List[Event]] = field(default_factory=dict)       # device
    spans: List[Event] = field(default_factory=list)
    window: Optional[Tuple[float, float]] = None

    @property
    def window_s(self) -> Optional[float]:
        return None if self.window is None else \
            (self.window[1] - self.window[0]) * 1e-9

    def device_programs(self) -> List[List[Event]]:
        """Per device, its programs clipped to the window, by start."""
        return [clip(sorted(ev, key=lambda e: e[1]), self.window)
                for ev in self.programs.values()]


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{logdir}, expected one")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name == PROGRAM_LINE:
                dst = tr.programs.setdefault(plane.name, [])
            elif device and line.name == OP_LINE:
                dst = tr.ops.setdefault(plane.name, [])
            elif not device:
                dst = None
            else:
                continue
            for e in line.events:
                name = e.name
                if dst is None:
                    if not name.startswith(SPAN_PREFIX):
                        continue
                    name = name[len(SPAN_PREFIX):]
                    out = tr.spans
                else:
                    out = dst
                out.append((name, e.start_ns, e.start_ns + e.duration_ns))
    windows = [s for s in tr.spans if s[0] == "window"]
    if windows:
        tr.window = (windows[0][1], windows[0][2])
    return tr


# --------------------------------------------------------------- intervals
def clip(events: Sequence[Event],
         window: Optional[Tuple[float, float]]) -> List[Event]:
    if window is None:
        return list(events)
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def merge(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of the intervals, as disjoint sorted (start, end) pairs."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(e - s for s, e in merge(events))


def idle_gaps(events: Iterable[Event],
              window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The stretches of the window in which no event runs."""
    lo, hi = window
    out, t = [], lo
    for s, e in merge(events):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def named(events: Iterable[Event], key: str) -> List[Event]:
    """The events whose name holds ``key`` (a jitted function's name)."""
    return [ev for ev in events if key in ev[0]]


def _busy_before(merged: Sequence[Tuple[float, float]]):
    """B(t): busy time before instant t, over disjoint sorted intervals."""
    starts = [s for s, _ in merged]
    cum = [0.0]
    for s, e in merged:
        cum.append(cum[-1] + e - s)

    def before(t: float) -> float:
        i = bisect.bisect_right(starts, t)
        if i == 0:
            return 0.0
        s, e = merged[i - 1]
        return cum[i - 1] + min(t, e) - s
    return before


def gaps_between(events: Sequence[Event], key: str,
                 exclude: Sequence[Tuple[float, float]] = ()) -> List[float]:
    """Idle device time (ns) between each two consecutive runs of the
    programs named ``key``: the time between them less what other programs
    ran in it. Pairs whose interval meets one of ``exclude`` are left out."""
    before = _busy_before(merge(events))
    ex = merge(("", s, e) for s, e in exclude)
    ex_ends = [e for _, e in ex]
    runs = sorted(named(events, key), key=lambda e: e[1])
    out = []
    for a, b in zip(runs, runs[1:]):
        lo, hi = a[2], b[1]
        i = bisect.bisect_right(ex_ends, lo)
        if i < len(ex) and ex[i][0] < hi:
            continue
        out.append(max(hi - lo - (before(hi) - before(lo)), 0.0))
    return out


def innermost(spans: Sequence[Event], t: float) -> str:
    """Name of the shortest host span that holds instant ``t``."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and n != "window" and (best is None
                                              or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "none"


# ---------------------------------------------------------------- summary
def op_name(text: str) -> str:
    """An op's name from its HLO text: ``%fusion.3 = bf16[...] ...`` ->
    ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")



def device_summary(tr: Trace, top: int = 10) -> dict:
    """busy_s (mean over devices), the longest idle gaps named by what the
    host was doing, and the operations that took most device time."""
    per_dev = tr.device_programs()
    busy = [busy_ns(ev) * 1e-9 for ev in per_dev]
    gaps = []
    for ev in per_dev:
        gaps += idle_gaps(ev, tr.window)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[innermost(tr.spans, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:top]]
    raw: Dict[str, float] = collections.defaultdict(float)
    for ev in tr.ops.values():
        for n, s, e in clip(ev, tr.window):
            raw[n] += (e - s) * 1e-9
    tot: Dict[str, float] = collections.defaultdict(float)
    for n, secs in raw.items():
        tot[op_name(n)] += secs
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy) if busy else None,
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": idle}


def cut_at_loss(tr: Trace, seconds: float) -> Optional[float]:
    """Set the window to ``seconds`` from the ``bench:window`` span's start,
    or shorter where the device trace lost its tail.

    The profiler keeps a bounded number of device events and drops the
    rest: a device that ran no program in the last second before the host
    closed its last span has lost them. The window then ends with the
    device's last program, and the seconds it covers are returned; None
    when nothing was lost.
    """
    lo = tr.window[0]
    hi = lo + seconds * 1e9
    ends = [e for ev in tr.programs.values() for _, _, e in ev]
    host = [e for n, _, e in tr.spans if n != "window"]
    tr.window = (lo, hi)
    if not ends or not host or max(host) - max(ends) < 1e9 \
            or max(ends) >= hi:
        return None
    tr.window = (lo, max(ends))
    return (max(ends) - lo) * 1e-9


def census(tr: Trace, top: int = 8) -> dict:
    """How many runs of each program, and host spans of each name, the
    trace holds, and where the device's last program ended, in seconds
    from the window's start: a check that the trace lost nothing."""
    progs: Dict[str, int] = collections.Counter()
    last = None
    for ev in tr.programs.values():
        for n, _, e in ev:
            progs[n[:60]] += 1
            last = e if last is None else max(last, e)
    lo = tr.window[0] if tr.window else None
    return {"programs": dict(progs.most_common(top)),
            "spans": dict(collections.Counter(n for n, _, _ in tr.spans)),
            "last_program_end_s": None if last is None or lo is None
            else (last - lo) * 1e-9}


def time_in(events: Sequence[Event], key: str) -> List[float]:
    """Device seconds of each run of the programs named ``key``, by start."""
    return [(e - s) * 1e-9 for _, s, e in
            sorted(named(events, key), key=lambda x: x[1])]

