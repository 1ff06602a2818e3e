"""Observability plane: program spans, flight recorder, metrics registry,
self-profiler, live dashboard.

Five layers, all zero-cost when not attached (the engine's observation
hooks are None-checked; an unobserved run pays one comparison per event
and nothing else; a span outside a profile capture, one check):

* :func:`span` / :func:`mark` (``spans.py``) — named, keyed intervals at
  the executor's, the scheduler's and the serving engine's boundaries,
  recorded only while a JAX profile is captured: into the profile beside
  the device's lines, and into an in-memory record of the capture
  (:func:`last_capture`) that the benchmark's readers use.
* :class:`FlightRecorder` (``trace.py``) — bounded ring-buffer structured
  event trace of the full task lifecycle, bit-identical between the wave
  and per-event dispatch paths, exportable as Chrome-trace JSON.
* :class:`Registry` (``registry.py``) — named counters / gauges /
  histograms / series unifying the engine's scattered metric state;
  ``MetricsTap`` is a thin view over one.
* :class:`SelfProfiler` (``profile.py``) — wall-clock phase timers on the
  span clock attributing the scheduler's *own* CPU time to admission /
  policy cycle / dispatch / completion / heartbeat sweep (the paper's t_s,
  measured, not modeled — see ``benchmarks/self_latency.py``); each phase
  is also a ``sched.<phase>`` span.
* :class:`Dashboard` (``dashboard.py``) — terminal renderer (and static
  HTML report) streaming registry series during long runs.
"""
from repro.obs.dashboard import Dashboard
from repro.obs.profile import SelfProfiler
from repro.obs.registry import Counter, Gauge, Histogram, Registry
from repro.obs.spans import last_capture, mark, span
from repro.obs.trace import FlightRecorder

__all__ = [
    "FlightRecorder", "Registry", "Counter", "Gauge", "Histogram",
    "SelfProfiler", "Dashboard", "span", "mark", "last_capture",
]
