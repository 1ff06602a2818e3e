"""The reader of the executor's in-flight marks, on records built by hand."""
import sys

import pytest

from bench import run
from bench import trace as tr
from repro.obs import spans

US = 1000  # ns
NAME = "exec_inflight_mean"


def _capture():
    """Three tasks through a window of two over 3000 us: in flight 1000,
    2000 and 1500 us, so 4500 us of tasks in 3000 us, 1.5 at a time."""
    cap = spans.Capture()
    cap.start_ns = 0
    for n, k, t0, t1 in [
            ("exec.dispatch", (1, 0), 0, 100),
            ("exec.dispatch", (1, 1), 500, 600),
            ("exec.wait", (1, 0), 600, 1000),
            ("exec.inflight", (1, 0), 0, 1000),
            ("exec.dispatch", (1, 2), 1500, 1600),
            ("exec.wait", (1, 1), 1600, 2500),
            ("exec.inflight", (1, 1), 500, 2500),
            ("exec.wait", (1, 2), 2500, 3000),
            ("exec.inflight", (1, 2), 1500, 3000)]:
        cap.add(n, k, t0 * US, t1 * US)
    return cap


def _obs(**kw):
    t = tr.Trace(programs={"/device:TPU:0": [
        ("jit_task(1)", 50 * US, 1050 * US)]})
    return dict({"device_kind": "TPU v5 lite", "seconds": 1.0,
                 "trace": t, "tasks_done": 3}, **kw)


@pytest.fixture
def record(monkeypatch):
    def use(cap):
        monkeypatch.setattr(spans, "last_capture", lambda: cap)
    return use


def test_the_marks_give_the_mean_in_flight(record):
    record(_capture())
    assert run.reader(NAME)(_obs()) == pytest.approx(1.5)


def test_a_blocking_handoff_reads_under_one(record):
    cap = spans.Capture()
    cap.start_ns = 0
    for i, t0 in enumerate((0, 1200)):
        cap.add("exec.inflight", (1, i), t0 * US, (t0 + 1000) * US)
    record(cap)
    assert run.reader(NAME)(_obs()) == pytest.approx(2000 / 2200)


@pytest.mark.parametrize("case", ["no trace", "empty", "no capture",
                                  "dropped", "no marks"])
def test_nothing_to_read_is_none(record, case):
    read = run.reader(NAME)
    obs = _obs()
    cap = _capture()
    if case == "no trace":
        record(cap)
        obs = {"device_kind": "TPU v5 lite", "seconds": 1.0}
    elif case == "empty":
        record(spans.Capture())
    elif case == "no capture":
        record(None)
    elif case == "dropped":
        cap.dropped = 1
        record(cap)
    else:                       # a blocking executor that records no mark
        blocking = spans.Capture()
        for r in cap.records:
            if r[0] != "exec.inflight":
                blocking.add(*r)
        record(blocking)
    assert read(obs) is None


def test_a_program_without_spans_reads_none(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs.spans", None)
    monkeypatch.delattr("repro.obs.spans", raising=False)
    assert run.reader(NAME)(_obs()) is None
