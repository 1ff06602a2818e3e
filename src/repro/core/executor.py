"""Job-execution backends (paper §1 "job execution function").

  SimExecutor     virtual time (the engine schedules end events directly).
  ThreadExecutor  real wall-clock execution of Python payloads on a worker
                  pool — used to measure *real* dispatch overheads.
  JaxDispatchExecutor  payloads are jitted JAX computations, enqueued on
                  the device through a bounded window of tasks in flight;
                  a task completes once its own output is ready.  Measures
                  real JAX dispatch latency t_s, and demonstrates multilevel
                  scheduling as dispatch aggregation (DESIGN.md §2).

Real-time use drives the same EventLoop with wall-deadline semantics: the
engine's virtual `now` tracks wall time via the rt runtime's pump
(src/repro/rt/runtime.py).

Thread-safety contract: worker threads never touch engine state.  A
completing payload enqueues its ``done`` callback on a thread-safe
completion queue; the callback only runs once the queue is *drained on the
event loop* — either by the loop itself (``bind_loop`` registers a drain
source the Scheduler wires up automatically) or by an explicit ``pump()``
from whatever thread owns the engine.  The rt runtime reuses the same
primitive for transport messages.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

from repro.core.job import Task
from repro.core.scheduler import Executor
from repro.obs.spans import clock, mark, span

#: queue sentinel that wakes a blocked worker ``get()`` at shutdown
_STOP = object()


class ThreadExecutor(Executor):
    """Runs task payloads on a pool of worker threads ("slots").

    Payload exceptions are never swallowed: the exception object is
    recorded in ``errors[task.key]`` and the task completes with
    ``ok=False`` (the engine's retry lifecycle sees a failed attempt).

    ``done`` callbacks are marshaled through ``_completions`` and run on
    the thread that drains it (the event loop via :meth:`bind_loop`, or a
    :meth:`pump`/:meth:`drain` caller) — never on a worker thread.  Pass
    ``marshal=False`` to restore the legacy fire-from-worker-thread
    behaviour (only safe when the callback is itself thread-safe).
    """

    #: fallback poll period while blocked waiting for completions (only
    #: reached if a payload outlives it; keeps the drain loop interruptible)
    _POLL_S = 1.0

    def __init__(self, workers: int = 4, marshal: bool = True):
        self._q: "queue.Queue" = queue.Queue()
        self._completions: "queue.Queue" = queue.Queue()
        self._threads: List[threading.Thread] = []
        self._stop = False
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._outstanding = 0          # run() called, done() not yet fired
        self._marshal = marshal
        self._loop = None
        self.results: Dict[Tuple[int, int], object] = {}
        self.errors: Dict[Tuple[int, int], BaseException] = {}
        for _ in range(workers):
            th = threading.Thread(target=self._worker, daemon=True)
            th.start()
            self._threads.append(th)

    # ------------------------------------------------------------ workers
    def _worker(self):
        while True:
            item = self._q.get()       # blocking; _STOP wakes us at shutdown
            if item is _STOP:
                self._q.task_done()
                break
            task, done = item
            ok = True
            try:
                if task.payload is not None:
                    self.results[task.key] = task.payload()
                elif task.duration:
                    time.sleep(task.duration)
            except BaseException as exc:    # noqa: BLE001 — recorded, not lost
                ok = False
                self.errors[task.key] = exc
            if self._marshal:
                self._completions.put((done, ok))
            else:
                done(ok)
                with self._idle:
                    self._outstanding -= 1
                    if self._outstanding == 0:
                        self._idle.notify_all()
            self._q.task_done()

    # ------------------------------------------------------------- submit
    def run(self, task: Task, done: Callable[[bool], None]) -> None:
        with self._lock:
            self._outstanding += 1
        self._q.put((task, done))

    # ---------------------------------------------------------- completion
    def pump(self, block: bool = False, timeout: Optional[float] = None) -> int:
        """Fire ready ``done`` callbacks on the *calling* thread.

        Returns the number fired.  ``block=True`` waits up to ``timeout``
        for the first completion when none is ready.
        """
        n = 0
        while True:
            try:
                done, ok = self._completions.get(
                    block=block and n == 0, timeout=timeout)
            except queue.Empty:
                break
            done(ok)
            with self._idle:
                self._outstanding -= 1
                if self._outstanding == 0:
                    self._idle.notify_all()
            n += 1
        return n

    def bind_loop(self, loop) -> None:
        """Register the completion queue as a drain source on ``loop``.

        The Scheduler calls this automatically for executors that expose
        it: when the loop's heap runs dry with payloads still in flight,
        the source blocks for the next completion and schedules its
        ``done`` at the loop's current instant — completions are *events*,
        serialized with every other engine state change.
        """
        if self._loop is loop:
            return
        self._loop = loop
        loop.add_source(self._drain_source)

    def _drain_source(self) -> bool:
        loop = self._loop
        scheduled = 0
        while True:
            try:
                done, ok = self._completions.get_nowait()
            except queue.Empty:
                break
            loop.at(loop.now, self._fire, done, ok)
            scheduled += 1
        if scheduled:
            return True
        with self._lock:
            outstanding = self._outstanding
        if outstanding <= 0 or self._stop:
            return False               # nothing in flight: let the loop end
        # work in flight but nothing ready: block for the next completion
        # (bounded poll so a wedged payload cannot make the loop unkillable)
        try:
            done, ok = self._completions.get(timeout=self._POLL_S)
        except queue.Empty:
            # re-check outstanding on the next poll round without advancing
            # virtual time
            loop.at(loop.now, _noop)
            return True
        loop.at(loop.now, self._fire, done, ok)
        return True

    def _fire(self, done: Callable[[bool], None], ok: bool) -> None:
        done(ok)
        with self._idle:
            self._outstanding -= 1
            if self._outstanding == 0:
                self._idle.notify_all()

    # ------------------------------------------------------------ teardown
    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted payload ran *and* its completion was
        fired (pumping from this thread while waiting)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                if self._outstanding <= 0:
                    return
            self.pump(block=True, timeout=0.05)
            if deadline is not None and time.monotonic() > deadline:
                with self._lock:
                    left = self._outstanding
                raise TimeoutError(
                    f"drain: {left} payloads still outstanding")

    def shutdown(self, join: bool = True, timeout: float = 5.0) -> None:
        """Stop the pool deterministically.

        A ``_STOP`` sentinel per thread wakes blocked ``get()``s (the old
        poll-flag shutdown left threads parked for up to their poll
        period); ``join=True`` then joins every worker.  Queued-but-unrun
        payloads are discarded; already-marshaled completions remain
        pumpable via :meth:`pump`/:meth:`drain`.
        """
        self._stop = True
        for _ in self._threads:
            self._q.put(_STOP)
        if join:
            for th in self._threads:
                th.join(timeout)
        self._threads = [t for t in self._threads if t.is_alive()]

    @property
    def outstanding(self) -> int:
        with self._lock:
            return self._outstanding


def _noop() -> None:
    """Scheduled by the drain source's poll fallback (no state change)."""


class InlineExecutor(Executor):
    """Runs payloads synchronously in the event loop (deterministic tests).

    While a profile is being captured, the payload call is span
    ``exec.dispatch`` (for a JAX payload: tracing and enqueueing its
    programs) and the wait for its result ``exec.wait``, both keyed by the
    task's ``(job, index)``.

    A payload's exception is recorded in ``errors[task.key]`` and the task
    completes with ``ok=False``; interrupts and exits propagate.
    """

    def __init__(self):
        self.results: Dict[Tuple[int, int], object] = {}
        self.errors: Dict[Tuple[int, int], BaseException] = {}

    def run(self, task: Task, done: Callable[[bool], None]) -> None:
        ok = True
        key = task.key
        try:
            if task.payload is not None:
                with span("exec.dispatch", key):
                    out = task.payload()
                with span("exec.wait", key):
                    self.results[key] = self._finish(out)
        except Exception as exc:            # noqa: BLE001 — recorded
            ok = False
            self.errors[key] = exc
        done(ok)

    @staticmethod
    def _finish(out):
        return out


class JaxDispatchExecutor(InlineExecutor):
    """Payloads are JAX computations, run through a bounded window of tasks
    in flight on the device.

    ``run`` calls the payload (span ``exec.dispatch``), which with JAX's
    asynchronous dispatch only enqueues its programs, and returns with the
    task in flight. Once ``window`` tasks are in flight, counting the one
    just dispatched, the oldest is retired: its output is waited for
    (``_finish``, span ``exec.wait``) and kept in ``results[task.key]``, or
    a device-side error is recorded in ``errors[task.key]`` against the
    task that raised it; only then is its ``done`` scheduled, as an event
    at the loop's current instant. A task is therefore never reported
    complete before its own output is ready.

    Completions are events on the loop that :meth:`bind_loop` binds (the
    Scheduler does so), and ``run`` refuses to start without one. When the
    loop's heap runs dry with tasks in flight, the drain source registered
    there retires the oldest; :meth:`settle` retires them all, for the
    Scheduler to call before anything that judges running tasks by their
    age (heartbeat sweeps, speculation) and when a horizon stops its loop.
    So no run of the Scheduler ends with work on the device.

    While a profile is captured, each retirement also records the mark
    ``exec.inflight``, from the start of the task's dispatch to the moment
    its result is collected.
    """

    #: most tasks in flight at once, the one being dispatched included:
    #: the smallest depth at which the direct task-set cell stops gaining
    #: on a v5e (PERF.md §6; 51 s runs, two seeds a depth: 1,157–1,175
    #: tasks/s at 2, 1,196–1,205 at 3 and at 4)
    window = 3

    def __init__(self):
        super().__init__()
        self._loop = None
        #: (task, done, output, dispatch start on the span clock), oldest first
        self._inflight: Deque[tuple] = collections.deque()

    @property
    def inflight(self) -> int:
        """Tasks dispatched whose outputs have not been collected yet."""
        return len(self._inflight)

    def run(self, task: Task, done: Callable[[bool], None]) -> None:
        if self._loop is None:
            raise RuntimeError("JaxDispatchExecutor completes tasks as "
                               "events: bind a loop first (a Scheduler "
                               "does)")
        key = task.key
        t0 = clock()
        try:
            with span("exec.dispatch", key):
                out = task.payload()
        except Exception as exc:            # noqa: BLE001 — recorded
            self.errors[key] = exc
            self._loop.at(self._loop.now, done, False)
            return
        self._inflight.append((task, done, out, t0))
        if len(self._inflight) >= self.window:
            self._retire()

    def bind_loop(self, loop) -> None:
        """Complete tasks as events on ``loop``, and retire the oldest task
        in flight whenever its heap runs dry."""
        if self._loop is loop:
            return
        self._loop = loop
        loop.add_source(self._drain_source)

    def settle(self) -> bool:
        """Retire every task in flight, oldest first, so that their
        completions are the next events at the loop's current instant.
        True if there were any."""
        if not self._inflight:
            return False
        while self._inflight:
            self._retire()
        return True

    def _drain_source(self) -> bool:
        if not self._inflight:
            return False
        self._retire()
        return True

    def _retire(self) -> None:
        task, done, out, t0 = self._inflight.popleft()
        key = task.key
        ok = True
        try:
            with span("exec.wait", key):
                self.results[key] = self._finish(out)
        except Exception as exc:            # noqa: BLE001 — recorded
            ok = False
            self.errors[key] = exc
        mark("exec.inflight", t0, key=key)
        self._loop.at(self._loop.now, done, ok)

    @staticmethod
    def _finish(out):
        import jax

        return jax.block_until_ready(out)
