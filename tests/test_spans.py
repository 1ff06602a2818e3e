"""Program spans (``obs/spans.py``): recorded only while a JAX profile is
captured, one record a capture, on the same clock as the profile's copy,
at the executor's, the scheduler's and the serving engine's boundaries."""
import contextlib
import gc
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Job, MultilevelConfig, ResourceManager, Scheduler,
                        aggregate)
from repro.core.executor import JaxDispatchExecutor
from repro.obs import SelfProfiler, spans

from test_obs import _run_jobs, _small_engine
from test_wavepath import engine_signature


@contextlib.contextmanager
def capture(logdir):
    jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _names(cap):
    return [r[0] for r in cap.records]


def test_the_gate_is_the_profilers_own_check():
    """The private path the gate reads: pinned, so a JAX upgrade that
    moves it fails here and not in silence."""
    from jax._src.lib import _profiler

    assert issubclass(jax.profiler.TraceAnnotation, _profiler.TraceMe)
    assert _profiler.TraceMe.is_enabled() is False
    spans.span("warm")                        # binds the gate
    assert spans._on is _profiler.TraceMe.is_enabled


def test_capture_gate_and_one_record_per_capture(tmp_path):
    off = spans.span("before")
    records = []
    for i in range(2):
        with capture(tmp_path / str(i)):
            assert spans.span("probe") is not off
            with spans.span("a", i):
                pass
            records.append(spans.last_capture())
        assert spans.span("after") is off
        assert spans.last_capture() is records[-1]
    assert records[0] is not records[1]
    assert [r.records[0][:2] for r in records] == [("a", 0), ("a", 1)]


def test_off_spans_record_nothing(tmp_path):
    with capture(tmp_path):
        with spans.span("on"):
            pass
    before = spans.last_capture()
    n = len(before.records)
    # one shared no-op context, whatever the name: nothing is allocated
    assert spans.span("x", 1) is spans.span("y")
    with spans.span("off", 7):
        spans.mark("off.mark", spans.clock() - 1000)
    gc.collect()
    assert spans.last_capture() is before and len(before.records) == n
    assert spans._on_gc not in gc.callbacks


def test_nested_spans_and_counters(tmp_path):
    with capture(tmp_path):
        with spans.span("outer"):
            for k in range(3):
                with spans.span("inner", k):
                    time.sleep(0.001)
        spans.mark("waited", spans.clock() - 2_000_000, key="r1")
    cap = spans.last_capture()
    assert _names(cap) == ["inner"] * 3 + ["outer", "waited"]
    (_, _, o0, o1), = cap.of("outer")
    inner = list(cap.of("inner"))
    assert [r[1] for r in inner] == [0, 1, 2]
    assert all(o0 <= t0 <= t1 <= o1 for _, _, t0, t1 in inner)
    assert cap.count("inner") == 3
    assert cap.seconds_in("inner") >= 0.003
    # the outer span's self time: what its children leave of it
    self_s = (o1 - o0) * 1e-9 - cap.seconds_in("inner")
    assert 0 <= self_s < cap.seconds_in("outer")
    (_, key, w0, w1), = cap.of("waited")
    assert key == "r1" and w1 - w0 >= 2_000_000


def test_a_full_record_counts_what_it_drops():
    cap = spans.Capture(limit=2)
    for i in range(5):
        cap.add("s", i, 10 * i, 10 * i + 3)
    assert [r[1] for r in cap.records] == [0, 1] and cap.dropped == 3
    assert cap.count("s") == 5 and cap.totals["s"] == [5, 15]


def test_span_and_its_profile_copy_agree(tmp_path):
    from jax.profiler import ProfileData

    with capture(tmp_path):
        for ms in (1, 4, 9):
            with spans.span("probe", ms):
                time.sleep(ms * 1e-3)
    mine = {k: t1 - t0 for _, k, t0, t1 in spans.last_capture().of("probe")}
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    theirs = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "probe":
                    key = next(v for k, v in e.stats if k == "key")
                    theirs[int(key)] = e.duration_ns
    assert set(theirs) == set(mine) == {1, 4, 9}
    for k in mine:
        assert abs(mine[k] - theirs[k]) < 50_000, (k, mine[k], theirs[k])


def test_gc_collections_are_spans_while_capturing(tmp_path):
    with capture(tmp_path):
        with spans.span("start"):
            pass
        assert spans._on_gc in gc.callbacks
        gc.collect()
    cap = spans.last_capture()
    assert spans._on_gc not in gc.callbacks
    gcs = list(cap.of("host.gc"))
    assert gcs and gcs[-1][1] == 2 and gcs[-1][3] > gcs[-1][2]


@pytest.mark.parametrize("mimo", [False, True])
def test_executor_spans_for_a_task_and_a_bundle(tmp_path, mimo):
    f = jax.jit(lambda x, i: x * i)
    x = jnp.ones((8,), jnp.float32)
    f(x, 1).block_until_ready()
    rm = ResourceManager()
    rm.add_nodes(1, slots=1)
    ex = JaxDispatchExecutor()
    s = Scheduler(rm, executor=ex)
    n = 4 if mimo else 1
    job = Job.array(n, payloads=[lambda i=i: f(x, i) for i in range(n)])
    with capture(tmp_path):
        if mimo:
            job = aggregate(job, 1, MultilevelConfig(mode="mimo"))
        s.submit(job)
        s.run()
    cap = spans.last_capture()
    key = (job.job_id, 0)
    assert [r[1] for r in cap.of("exec.dispatch")] == [key]
    assert [r[1] for r in cap.of("exec.wait")] == [key]
    (_, _, d0, d1), = cap.of("exec.dispatch")
    (_, _, w0, w1), = cap.of("exec.wait")
    assert d1 <= w0
    assert cap.count("multilevel.aggregate") == int(mimo)
    got = ex.results[key]
    assert [float(np.asarray(r)[0]) for r in (got if mimo else [got])] \
        == [float(i) for i in range(n)]


def test_profiler_reports_unchanged_inside_a_capture(tmp_path):
    """The profiler counts and samples the same calls, and the engine does
    the same things, with the spans on; each sampled call is one
    ``sched.<phase>`` span, and a phase's self time fits in its spans."""
    def run(traced):
        s = _small_engine()
        prof = SelfProfiler().attach(s)
        with capture(tmp_path) if traced else contextlib.nullcontext():
            jobs = _run_jobs(s, n_jobs=8, seed=4)
        rep = prof.report()
        return engine_signature(s, jobs), rep

    sig0, rep0 = run(False)
    sig1, rep1 = run(True)
    assert sig0 == sig1
    cap = spans.last_capture()
    for phase in rep0:
        assert rep1[phase]["calls"] == rep0[phase]["calls"]
        assert rep1[phase]["sampled"] == rep0[phase]["sampled"]
        assert cap.count("sched." + phase) == rep1[phase]["sampled"]
        assert 0.0 <= rep1[phase]["self_s"] <= \
            cap.seconds_in("sched." + phase) + 1e-9
    assert abs(sum(p["fraction"] for p in rep1.values()) - 1.0) < 1e-9


def test_engine_spans_for_each_request_and_step(tmp_path):
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serving import ServeRequest, ServingEngine

    cfg = get_smoke_config("phi4_mini_3_8b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, lanes=2, max_len=32)
    rng = np.random.default_rng(6)

    def reqs(k):
        return [ServeRequest(prompt=list(rng.integers(0, cfg.vocab_size, 5)),
                             max_new_tokens=3) for _ in range(k)]
    eng.run(reqs(2))                              # compiles
    rs = reqs(3)
    with capture(tmp_path):
        stats = eng.run(rs)
    cap = spans.last_capture()
    ids = [r.request_id for r in rs]
    queue = {k: (t0, t1) for _, k, t0, t1 in cap.of("engine.queue")}
    admit = {k: (t0, t1) for _, k, t0, t1 in cap.of("engine.admit")}
    assert sorted(queue) == sorted(admit) == ids
    for r in rs:
        assert queue[r.request_id][0] == r.submit_time
        assert queue[r.request_id][1] <= admit[r.request_id][0]
        a0, a1 = admit[r.request_id]
        assert a0 <= r.first_token_time <= a1 <= r.done_time
    for part in ("engine.prefill", "engine.scatter", "engine.first_token"):
        for _, k, t0, t1 in cap.of(part):
            assert admit[k][0] <= t0 <= t1 <= admit[k][1]
    steps = stats["decode_steps"] - 1
    n = [r[1] for r in cap.of("engine.decode")]
    assert n == list(range(steps - len(n) + 1, steps + 1)) and n
    for part in ("engine.prepare", "engine.sync", "engine.retire"):
        assert [r[1] for r in cap.of(part)] == n
    assert 0 < stats["mean_latency_s"] < stats["wall_s"]


@pytest.mark.parametrize("arch", ["phi4_mini_3_8b", "jamba2_3b"])
def test_engine_lane_writes_by_cache_kind(tmp_path, arch):
    """``engine.scatter.kv`` and, in a stack with recurrent layers,
    ``engine.scatter.state``, once per admission, inside its
    ``engine.scatter`` and keyed by the request's id."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.serving import ServeRequest, ServingEngine

    cfg = get_smoke_config(arch)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, lanes=2, max_len=32)
    rng = np.random.default_rng(7)

    def reqs(k):
        return [ServeRequest(prompt=list(rng.integers(0, cfg.vocab_size, 5)),
                             max_new_tokens=2) for _ in range(k)]
    eng.run(reqs(2))                              # compiles
    rs = reqs(3)
    with capture(tmp_path):
        eng.run(rs)
    cap = spans.last_capture()
    scatter = {k: (t0, t1) for _, k, t0, t1 in cap.of("engine.scatter")}
    assert sorted(scatter) == [r.request_id for r in rs]
    kinds = ["kv", "state"] if cfg.family == "hybrid" else ["kv"]
    for kind in ("kv", "state"):
        got = {k: (t0, t1) for _, k, t0, t1
               in cap.of(f"engine.scatter.{kind}")}
        assert sorted(got) == (sorted(scatter) if kind in kinds else [])
        for k, (t0, t1) in got.items():
            assert scatter[k][0] <= t0 <= t1 <= scatter[k][1]
