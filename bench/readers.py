"""What the per-layer metric readers share: the device's runs of a named
program in a traced window, and each run matched to what the host recorded
for it by the host span that holds it."""
from __future__ import annotations

import bisect
from typing import List, Optional, Tuple

from bench import trace as tr

#: jitted functions the reduction matches in the trace's program names
DECODE = "_decode_fn"
PREFILL = "_prefill_fn"


def device_programs(obs) -> Optional[list]:
    """Programs of the first device, whole (not cut at the window), by
    start; None without a device trace."""
    t = obs.get("trace")
    if t is None or not t.programs:
        return None
    return sorted(next(iter(t.programs.values())), key=lambda e: e[1])


def within(obs, key: str, span: str) -> Optional[List[Tuple[float, object]]]:
    """(device seconds, host record) for each run of program ``key`` that
    lies in a host span named ``span``.

    The host keeps one record list per such span, in order
    (``records[span]``), with one item for each program the span ran. A
    program belongs to the span that holds its midpoint; a span whose
    count of programs differs from its count of items is left out, so a
    trace that lost events loses those spans and no more. None when there
    is no trace, no record, or the trace holds another number of spans than
    the host opened.
    """
    ev = device_programs(obs)
    recs = obs.get("records", {}).get(span)
    if ev is None or not recs:
        return None
    spans = sorted((s, e) for n, s, e in obs["trace"].spans if n == span)
    if len(spans) != len(recs):
        return None
    runs = tr.named(ev, key)
    mids = [(s + e) / 2 for _, s, e in runs]
    out = []
    for (lo, hi), items in zip(spans, recs):
        i, j = bisect.bisect_left(mids, lo), bisect.bisect_right(mids, hi)
        if j - i == len(items):
            out += [((e - s) * 1e-9, item)
                    for (_, s, e), item in zip(runs[i:j], items)]
    return out


def peak(obs) -> dict:
    from bench.peaks import peaks
    return peaks(obs["device_kind"])
