"""The readers of the program's own spans, on records built by hand."""
import sys

import pytest

from bench import run
from bench import trace as tr
from repro.obs import spans

US = 1000  # ns

READERS = ["exec_dispatch_us_per_task", "exec_handoff_us_per_task",
           "host_gap_us_per_task", "host_gc_ms_per_s", "queue_wait_ms_p50",
           "step_host_ms"]


def _task_capture():
    """Two tasks handed off by a blocking executor, a collection between."""
    cap = spans.Capture()
    cap.start_ns = 0
    for n, k, t0, t1 in [
            ("exec.dispatch", (1, 0), 0, 100), ("exec.wait", (1, 0), 100, 1100),
            ("sched.completion", None, 1120, 1200),
            ("host.gc", 0, 1200, 1300),
            ("exec.dispatch", (1, 1), 1500, 1600),
            ("exec.wait", (1, 1), 1600, 2600)]:
        cap.add(n, k, t0 * US, t1 * US)
    return cap


def _engine_capture():
    cap = spans.Capture()
    for rid, wait_ms in ((1, 1), (2, 10), (3, 3)):
        cap.add("engine.queue", rid, 0, wait_ms * 1000 * US)
    for step in range(2):
        for n, us in (("engine.prepare", 100), ("engine.decode", 500),
                      ("engine.sync", 20000), ("engine.retire", 200)):
            cap.add(n, step, 0, us * US)
    return cap


def _obs(**kw):
    t = tr.Trace(programs={"/device:TPU:0": [
        ("jit_task(1)", 50 * US, 1050 * US),
        ("jit_task(1)", 1550 * US, 2550 * US)]})
    return dict({"device_kind": "TPU v5 lite", "seconds": 1.0,
                 "trace": t}, **kw)


@pytest.fixture
def record(monkeypatch):
    def use(cap):
        monkeypatch.setattr(spans, "last_capture", lambda: cap)
    return use


def test_task_readers_add_up_to_the_device_idle(record):
    record(_task_capture())
    obs = _obs(tasks_done=2)
    got = {n: run.reader(n)(obs) for n in READERS[:4]}
    assert got["exec_dispatch_us_per_task"] == pytest.approx(100)
    # 2200 us in handoffs, 2000 us of it busy on the device
    assert got["exec_handoff_us_per_task"] == pytest.approx(100)
    assert got["host_gap_us_per_task"] == pytest.approx(200)
    # the device idles 600 us from the first dispatch to the last wait
    assert got["exec_handoff_us_per_task"] + got["host_gap_us_per_task"] \
        == pytest.approx(600 / 2)
    assert got["host_gc_ms_per_s"] == pytest.approx(100 / 2600 * 1e3)


def test_serving_readers(record):
    record(_engine_capture())
    obs = _obs()
    assert run.reader("queue_wait_ms_p50")(obs) == pytest.approx(3.0)
    assert run.reader("step_host_ms")(obs) == pytest.approx(0.8)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(record, name):
    read = run.reader(name)
    full = _task_capture() if name in READERS[:4] else _engine_capture()
    record(full)
    assert read(_obs(tasks_done=2)) is not None
    assert read({"device_kind": "TPU v5 lite", "seconds": 1.0}) is None
    record(spans.Capture())                        # an empty record
    assert read(_obs(tasks_done=2)) is None
    record(None)                                   # no capture at all
    assert read(_obs(tasks_done=2)) is None
    dropped = full
    dropped.dropped = 1
    record(dropped)
    assert read(_obs(tasks_done=2)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_none(monkeypatch, name):
    """The parent of the change that adds the spans has no such module:
    its traced runs leave these metrics out, and raise nothing."""
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr("repro.obs.spans", raising=False)
    assert run.reader(name)(_obs(tasks_done=2)) is None
