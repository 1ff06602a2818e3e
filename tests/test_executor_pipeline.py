"""``JaxDispatchExecutor``'s window of tasks in flight: the same results,
errors and completions as a blocking handoff (``InlineExecutor`` waiting on
each output), with each ``done`` an event that fires once, after its own
output is ready, and nothing left in flight when the scheduler stops."""
import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Job, JobState, MultilevelConfig, ResourceManager,
                        Scheduler, aggregate)
from repro.core.executor import InlineExecutor, JaxDispatchExecutor
from repro.core.resources import NodeState
from repro.core.scheduler import SchedulerConfig
from repro.obs import spans

F = jax.jit(lambda x, i: jnp.sum(x * i))
X = jnp.arange(64, dtype=jnp.float32)


class Boom(RuntimeError):
    pass


class Blocking(InlineExecutor):
    """The blocking handoff: each task's output is waited for inside
    ``run``, which then calls ``done``."""
    _finish = staticmethod(JaxDispatchExecutor._finish)


@pytest.fixture
def window(request, monkeypatch):
    monkeypatch.setattr(JaxDispatchExecutor, "window", request.param)
    return request.param


def _engine(nodes=4, ex=None, config=None, rm=None):
    if rm is None:
        rm = ResourceManager()
        rm.add_nodes(nodes, slots=1)
    ex = JaxDispatchExecutor() if ex is None else ex
    return Scheduler(rm, executor=ex, config=config), ex


def _job(n, mimo, nodes=4):
    job = Job.array(n, payloads=[lambda i=i: F(X, i) for i in range(n)])
    return aggregate(job, nodes, MultilevelConfig(mode="mimo")) \
        if mimo else job


def _with_payloads(n, make):
    """A job array of ``n`` tasks whose payloads ``make(task)`` builds."""
    job = Job.array(n, payloads=[None] * n)
    for t in job.tasks:
        t.payload = make(t)
    return job


def _values(results):
    return {k: [float(np.asarray(x)) for x in v] if isinstance(v, list)
            else float(np.asarray(v)) for k, v in results.items()}


def _raise():
    raise Boom("dispatch")


def _run(ex, mimo, n=24):
    """Three sets through one engine, one task of the last failing at
    dispatch; results, errors, completions and the sets' ends keyed by
    (the set's place, task index), as job ids differ between runs."""
    s, ex = _engine(ex=ex)
    seen = []
    s.on_complete = lambda task, ok: seen.append((task.key, ok))
    jobs = [_job(n, mimo) for _ in range(3)]
    jobs[-1].tasks[2].payload = _raise
    for job in jobs:
        s.submit(job)
    s.run()
    place = {j.job_id: i for i, j in enumerate(jobs)}

    def at(key):
        return place[key[0]], key[1]
    return ({at(k): v for k, v in _values(ex.results).items()},
            {at(k): repr(e) for k, e in ex.errors.items()},
            sorted((at(k), ok) for k, ok in seen),
            [j.state for j in jobs])


@pytest.mark.parametrize("window", [1, 3, 8], indirect=True)
@pytest.mark.parametrize("mimo", [False, True], ids=["direct", "mimo"])
def test_results_and_errors_match_a_blocking_handoff(window, mimo):
    ref = _run(Blocking(), mimo)
    got = _run(JaxDispatchExecutor(), mimo)
    assert got == ref
    results, errors, seen, states = got
    assert errors == {(2, 2): repr(Boom("dispatch"))}
    assert len(seen) == 3 * (4 if mimo else 24)
    assert len(results) == len(seen) - 1
    assert [ok for k, ok in seen if k == (2, 2)] == [False]
    assert states[:2] == [JobState.COMPLETED] * 2


@pytest.mark.parametrize("window", [1, 2, 4], indirect=True)
def test_each_done_fires_once_after_its_output_is_ready(window):
    s, ex = _engine()
    outs, fired = {}, []

    def make(t):
        def payload():
            assert ex.inflight < window       # the window has room
            outs[t.key] = out = F(X, t.index)
            return out
        return payload
    job = _with_payloads(16, make)

    def on_complete(task, ok):
        assert ok and outs[task.key].is_ready()
        assert task.key in ex.results
        fired.append(task.key)
    s.on_complete = on_complete
    s.submit(job)
    s.run()
    assert sorted(fired) == sorted(t.key for t in job.tasks)
    assert len(fired) == len(set(fired))
    assert ex.inflight == 0


@pytest.mark.parametrize("window", [3], indirect=True)
def test_the_window_fills_and_the_run_ends_with_nothing_in_flight(window):
    s, ex = _engine(nodes=8)
    depth = []

    def make(t):
        def payload():
            depth.append(ex.inflight)
            return F(X, t.index)
        return payload
    job = _with_payloads(20, make)
    s.submit(job)
    s.run()
    assert max(depth) == 2 and ex.inflight == 0
    assert job.state is JobState.COMPLETED


def test_without_a_loop_nothing_is_dispatched():
    ex = JaxDispatchExecutor()
    job = Job.array(1, payloads=[lambda: F(X, 1)])
    with pytest.raises(RuntimeError, match="bind a loop"):
        ex.run(job.tasks[0], lambda ok: None)
    assert ex.inflight == 0 and not ex.results and not ex.errors


def _counted(n, calls):
    """A job array of ``n`` tasks whose payloads count their calls."""
    def make(t):
        def payload():
            calls[t.key] += 1
            return F(X, t.index)
        return payload
    return _with_payloads(n, make)


def _bounded(limit=2000):
    """An ``on_cycle`` / ``on_sweep`` hook that stops a loop which does not
    end, so that the test fails instead of hanging."""
    ticks = [0]

    def tick(*_):
        ticks[0] += 1
        if ticks[0] > limit:
            raise RuntimeError("the event loop does not end")
    return tick


@pytest.mark.parametrize("window", [3, 8], indirect=True)
@pytest.mark.parametrize("periodic", ["heartbeat", "speculative"])
def test_periodic_events_see_every_task_complete_once(window, periodic):
    """A heartbeat sweep and a speculative re-check re-arm themselves while
    jobs are active, so the loop's heap never runs dry; the window is
    settled before either judges running tasks. With heartbeats counted
    only from completions, a task held in the window would otherwise leave
    its node silent, to be marked down; a speculative check would clone it
    as a straggler."""
    rm = ResourceManager(heartbeat_timeout=0.05)
    rm.add_nodes(4, slots=1)
    rm.external_heartbeats = True
    config = (SchedulerConfig(heartbeat_interval=0.01)
              if periodic == "heartbeat"
              else SchedulerConfig(speculative=True))
    s, ex = _engine(rm=rm, config=config)
    s.on_cycle = s.on_sweep = _bounded()
    calls = collections.Counter()
    job = _counted(24, calls)
    s.submit(job)
    s.run()
    assert job.state is JobState.COMPLETED and job.n_clones == 0
    assert sorted(calls) == sorted(t.key for t in job.tasks)
    assert set(calls.values()) == {1}
    assert all(n.state is NodeState.UP for n in rm.nodes.values())
    assert ex.inflight == 0
    assert _values(ex.results) == {t.key: float(np.sum(X * t.index))
                                   for t in job.tasks}


@pytest.mark.parametrize("window", [3], indirect=True)
def test_a_horizon_stops_the_run_with_nothing_in_flight(window):
    s, ex = _engine()
    calls = collections.Counter()
    job = _counted(24, calls)
    s.submit(job)
    s.run(until=0.0015)
    assert ex.inflight == 0 and 0 < len(calls) < 24
    assert s.completed == len(calls)
    s.run()
    assert job.state is JobState.COMPLETED and set(calls.values()) == {1}
    assert ex.inflight == 0


@pytest.mark.parametrize("window", [4], indirect=True)
@pytest.mark.parametrize("where", ["dispatch", "finish"])
def test_a_failure_is_recorded_against_its_own_task(window, where,
                                                     monkeypatch):
    s, ex = _engine()
    bad = 5
    job = Job.array(12, payloads=[lambda i=i: F(X, i) for i in range(12)])
    if where == "dispatch":
        def raise_():
            raise Boom("dispatch")
        job.tasks[bad].payload = raise_
    else:
        marked = {}
        job.tasks[bad].payload = lambda: marked.setdefault("out", F(X, bad))
        real = JaxDispatchExecutor._finish

        def finish(out):
            if out is marked.get("out"):
                raise Boom("device")
            return real(out)
        monkeypatch.setattr(JaxDispatchExecutor, "_finish",
                            staticmethod(finish))
    seen = {}
    s.on_complete = lambda task, ok: seen.setdefault(task.key, ok)
    s.submit(job)
    s.run()
    key = job.tasks[bad].key
    assert list(ex.errors) == [key] and isinstance(ex.errors[key], Boom)
    assert seen[key] is False and key not in ex.results
    assert all(seen[t.key] for t in job.tasks if t.index != bad)
    assert _values(ex.results) == {
        t.key: float(np.sum(X * t.index)) for t in job.tasks
        if t.index != bad}


@pytest.mark.parametrize("window", [4], indirect=True)
def test_a_completion_superseded_by_a_node_failure_is_dropped(window):
    s, ex = _engine(nodes=2)
    job = Job.array(1, payloads=[None], max_restarts=1)
    task = job.tasks[0]
    calls = []

    def payload():
        calls.append(task.attempts)
        if len(calls) == 1:     # the node dies with the first attempt queued
            s.loop.at(s.loop.now, s.fail_node, task.node_id)
        return F(X, len(calls))
    task.payload = payload
    ends = []
    real_end = s._task_end
    s._task_end = lambda t, ok: (ends.append((t.attempts, ok)),
                                 real_end(t, ok))
    s.submit(job)
    s.run()
    assert calls == [1, 2]
    # both outputs were collected; only the second attempt's counts
    assert ends == [(2, True)]
    assert job.state is JobState.COMPLETED and ex.inflight == 0
    assert float(np.asarray(ex.results[task.key])) == float(np.sum(X * 2))


@contextlib.contextmanager
def _capture(logdir):
    jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.mark.parametrize("window", [3], indirect=True)
@pytest.mark.parametrize("mimo", [False, True], ids=["direct", "mimo"])
def test_dispatch_wait_and_inflight_spans_are_keyed_per_task(tmp_path,
                                                             window, mimo):
    s, ex = _engine(nodes=2)
    job = _job(8, mimo, nodes=2)
    with _capture(tmp_path):
        s.submit(job)
        s.run()
    cap = spans.last_capture()
    keys = sorted(t.key for t in job.tasks)
    by = {n: {r[1]: r[2:] for r in cap.of(n)}
          for n in ("exec.dispatch", "exec.wait", "exec.inflight")}
    for n, recs in by.items():
        assert sorted(recs) == keys and cap.count(n) == len(keys), n
    for k in keys:
        (d0, d1), (w0, w1), (i0, i1) = (by[n][k] for n in by)
        assert i0 <= d0 <= d1 <= w0 <= w1 <= i1
