"""Program spans: named, keyed intervals at the program's layer boundaries,
recorded only while a JAX profile is being captured.

  with span("exec.wait", task.key):
      jax.block_until_ready(out)
  mark("engine.queue", req.submit_time, key=req.request_id)

The gate is the profiler's own: ``TraceMe.is_enabled()``, true while
``jax.profiler.start_trace`` (or a remote profiler capture) is running.
With no capture a span costs that one check and returns a shared no-op
context: no allocation, no timer.

While a capture is on, each span
  * opens ``jax.profiler.TraceAnnotation(name, key=...)``, so it lies in
    the profile beside the device's lines, on the device trace's clock;
  * appends ``(name, key, t0, t1)`` on :data:`clock` (``perf_counter_ns``)
    to the capture's in-memory :class:`Capture`, which keeps at most
    ``limit`` spans and counts the rest as dropped;
  * adds to the capture's per-name totals (count, ns): the counters.

A :func:`mark` is a span whose start was stamped earlier on :data:`clock`
(a request's submission); the profiler takes no back-dated events, so a
mark lives only in the in-memory record.

The first span seen after the capture turned on starts a new record; the
capture is seen to end by the next span, or :func:`last_capture`, that
finds the profiler off. While a capture is on, a ``gc.callbacks`` hook
records every collection as a ``host.gc`` span keyed by its generation.

Spans are meant for the thread that drives the program (the scheduler's
event loop, the serving loop); a record is not locked.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["Capture", "span", "mark", "last_capture", "clock"]

#: the span clock, in ns; the serving engine stamps requests on it too
clock = time.perf_counter_ns

#: spans one capture keeps in memory; later ones only reach the totals
LIMIT = 1 << 20

Record = Tuple[str, object, int, int]        # name, key, t0_ns, t1_ns


class Capture:
    """The spans of one profile capture, in the order they ended."""

    def __init__(self, limit: int = LIMIT):
        self.limit = limit
        self.start_ns = clock()
        self.records: List[Record] = []
        self.dropped = 0
        #: name -> [count, ns], every span of the capture, dropped or not
        self.totals: Dict[str, List[int]] = {}

    def add(self, name: str, key, t0: int, t1: int) -> None:
        tot = self.totals.get(name)
        if tot is None:
            tot = self.totals[name] = [0, 0]
        tot[0] += 1
        tot[1] += t1 - t0
        if len(self.records) < self.limit:
            self.records.append((name, key, t0, t1))
        else:
            self.dropped += 1

    def of(self, name: str) -> Iterator[Record]:
        return (r for r in self.records if r[0] == name)

    def count(self, name: str) -> int:
        return self.totals.get(name, (0, 0))[0]

    def seconds_in(self, name: str) -> float:
        return self.totals.get(name, (0, 0))[1] * 1e-9

    @property
    def seconds(self) -> float:
        """From the capture's first span to the end of its last."""
        if not self.records:
            return 0.0
        return (max(r[3] for r in self.records) - self.start_ns) * 1e-9


class _State:
    live = False            # a capture was on at the last look
    capture: Optional[Capture] = None
    gc_t0: Optional[int] = None
    gc_trace = None


_st = _State()


def _gate_before_jax() -> bool:
    """No profile can be on before JAX is imported; once it is, the gate
    becomes the profiler's own check."""
    global _on, _annotation
    if "jax" not in sys.modules:
        return False
    import jax.profiler
    from jax._src.lib import _profiler

    _annotation = jax.profiler.TraceAnnotation
    _on = _profiler.TraceMe.is_enabled
    return _on()


_on = _gate_before_jax
_annotation = None


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "key", "t0", "tm")

    def __init__(self, name: str, key):
        self.name, self.key = name, key

    def __enter__(self):
        self.tm = _annotation(self.name) if self.key is None else \
            _annotation(self.name, key=self.key)
        self.tm.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, *exc):
        t1 = clock()
        self.tm.__exit__(*exc)
        _st.capture.add(self.name, self.key, self.t0, t1)
        return False


def span(name: str, key=None):
    """A context that records the block as span ``name`` while a profile
    is being captured, and does nothing otherwise."""
    if not _on():
        if _st.live:
            _close()
        return _OFF
    if not _st.live:
        _open()
    return _Span(name, key)


def mark(name: str, t0: int, key=None) -> None:
    """Record span ``name`` from ``t0`` (on :data:`clock`) to now, while a
    profile is being captured."""
    if not _on():
        if _st.live:
            _close()
        return
    if not _st.live:
        _open()
    _st.capture.add(name, key, t0, clock())


def last_capture() -> Optional[Capture]:
    """The record of the capture in progress, or of the last one."""
    if _st.live and not _on():
        _close()
    return _st.capture


def _open() -> None:
    _st.capture = Capture()
    _st.live = True
    _st.gc_t0 = None
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def _close() -> None:
    _st.live = False
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if _st.live and _on():
            _st.gc_trace = _annotation("host.gc", key=info["generation"])
            _st.gc_trace.__enter__()
            _st.gc_t0 = clock()
    elif _st.gc_t0 is not None:
        t1 = clock()
        _st.gc_trace.__exit__(None, None, None)
        _st.capture.add("host.gc", info["generation"], _st.gc_t0, t1)
        _st.gc_t0 = _st.gc_trace = None
