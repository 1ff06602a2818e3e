"""The reduction from a profiler trace to per-layer numbers."""
import jax
import jax.numpy as jnp
import pytest

from bench import trace as tr

MS = 1e6  # ns


def _ev(name, start_ms, end_ms):
    return (name, start_ms * MS, end_ms * MS)


def test_merge_and_busy_take_the_union():
    ev = [_ev("a", 0, 2), _ev("b", 1, 3), _ev("c", 5, 6)]
    assert tr.merge(ev) == [(0, 3 * MS), (5 * MS, 6 * MS)]
    assert tr.busy_ns(ev) == 4 * MS


def test_idle_gaps_cover_the_rest_of_the_window():
    ev = [_ev("a", 1, 2), _ev("b", 4, 5)]
    gaps = tr.idle_gaps(ev, (0, 6 * MS))
    assert gaps == [(0, MS), (2 * MS, 4 * MS), (5 * MS, 6 * MS)]
    assert sum(e - s for s, e in gaps) + tr.busy_ns(ev) == 6 * MS


def test_clip_cuts_events_at_the_window():
    ev = [_ev("a", 0, 2), _ev("b", 3, 9), _ev("c", 10, 11)]
    assert tr.clip(ev, (1 * MS, 5 * MS)) == [_ev("a", 1, 2), _ev("b", 3, 5)]


def test_gaps_between_subtract_other_programs_and_skip_excluded():
    ev = [_ev("jit__decode_fn(1)", 0, 10), _ev("jit_scatter(2)", 11, 13),
          _ev("jit__decode_fn(1)", 15, 25), _ev("jit__decode_fn(1)", 26, 36),
          _ev("jit__decode_fn(1)", 50, 60)]
    # 15 - 10 less 2 of scatter; 26 - 25; then 50 - 36, unless excluded
    assert tr.gaps_between(ev, "_decode_fn") == [3 * MS, 1 * MS, 14 * MS]
    assert tr.gaps_between(ev, "_decode_fn",
                           exclude=[(40 * MS, 45 * MS)]) == [3 * MS, 1 * MS]
    assert tr.time_in(ev, "_decode_fn") == [0.01] * 4


def test_innermost_names_the_shortest_enclosing_span():
    spans = [_ev("window", 0, 100), _ev("scheduler", 0, 50),
             _ev("executor", 10, 20)]
    assert tr.innermost(spans, 15 * MS) == "executor"
    assert tr.innermost(spans, 30 * MS) == "scheduler"
    assert tr.innermost(spans, 70 * MS) == "none"


def test_device_summary_on_a_built_trace():
    t = tr.Trace(
        programs={"/device:TPU:0": [_ev("jit_task(1)", 1, 4),
                                    _ev("jit_task(1)", 6, 7)]},
        ops={"/device:TPU:0": [
            _ev("%fusion.3 = bf16[8]{0} fusion(...)", 1, 3),
            _ev("%copy.1 = bf16[8]{0} copy(...)", 3, 4),
            _ev("%fusion.3 = bf16[8]{0} fusion(...)", 6, 7)]},
        spans=[_ev("window", 0, 10), _ev("scheduler", 0, 10),
               _ev("executor", 4, 6)],
        window=(0, 10 * MS))
    s = tr.device_summary(t)
    assert s["busy_s"] == pytest.approx(0.004)
    assert s["device_ops"] == [["fusion.3", pytest.approx(0.003)],
                               ["copy.1", pytest.approx(0.001)]]
    assert [g[0] for g in s["idle_gaps"]] == ["scheduler", "executor",
                                              "scheduler"]
    assert [g[1] for g in s["idle_gaps"]] == pytest.approx(
        [0.003, 0.002, 0.001])


def test_load_reads_the_bench_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:step"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("not-ours"):
            pass
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in t.spans]
    assert names.count("step") == 3 and "not-ours" not in names
    lo, hi = t.window
    assert all(lo <= s <= e <= hi for n, s, e in t.spans if n == "step")
    # the CPU has no device plane: device metrics find nothing to read
    assert t.programs == {}


# ------------------------------------------------ programs matched to spans
class _Shapes:
    def decode_flops(self, ctx):
        return 1e9 * len(ctx)

    def decode_bytes(self, ctx):
        return 1e9 * sum(ctx)


def _serving_obs(records, decodes, spans):
    t = tr.Trace(programs={"/device:TPU:0": decodes}, spans=spans,
                 window=(0, 100 * MS))
    return {"trace": t, "records": records, "shapes": _Shapes(),
            "device_kind": "TPU v5 lite"}


def test_within_pairs_each_program_with_its_span_and_skips_a_short_one():
    from bench import readers

    spans = [_ev("step", 0, 10), _ev("step", 20, 30), _ev("step", 40, 50)]
    progs = [_ev("jit__decode_fn(1)", 2, 8), _ev("jit__decode_fn(1)", 22, 26),
             _ev("jit__prefill_fn(3)", 41, 42)]   # the third lost its decode
    obs = _serving_obs({"step": [[[5]], [[6, 7]], [[8]]]}, progs, spans)
    assert readers.within(obs, readers.DECODE, "step") == [
        (pytest.approx(0.006), [5]), (pytest.approx(0.004), [6, 7])]
    # a trace with another number of spans than the host opened: nothing
    obs["records"]["step"].append([[9]])
    assert readers.within(obs, readers.DECODE, "step") is None


def test_decode_readers_on_a_built_trace():
    from bench import run

    spans = [_ev("step", 0, 10), _ev("step", 20, 30)]
    progs = [_ev("jit__decode_fn(1)", 2, 6), _ev("jit__decode_fn(1)", 22, 28)]
    obs = _serving_obs({"step": [[[100]], [[100, 300]]]}, progs, spans)
    assert run.reader("decode_ms")(obs) == pytest.approx(5.0)
    # 3 GFLOP over 10 ms at 197 TFLOP/s
    assert run.reader("decode_mfu")(obs) == pytest.approx(
        100 * 3e9 / (0.010 * 197e12))
    # bytes bind: (100 + 400) GB at 819 GB/s over 10 ms
    assert run.reader("decode_roofline")(obs) == pytest.approx(
        100 * (100e9 + 400e9) / 819e9 / 0.010)


@pytest.mark.parametrize("last_s, host_s, want", [
    (95, 99, None),            # the device ran past the window: nothing lost
    (40, 40.5, None),          # host and device stopped together
    (40, 99, 40.0),            # the host went on long after the device
])
def test_cut_at_loss_ends_the_window_where_the_device_trace_stops(
        last_s, host_s, want):
    t = tr.Trace(programs={"/device:TPU:0": [_ev("jit_f(1)", 1, last_s * 1e3)]},
                 spans=[_ev("window", 0, 200e3), _ev("step", 0, host_s * 1e3)],
                 window=(0, 200e9))
    lost = tr.cut_at_loss(t, 90.0)
    assert lost == (None if want is None else pytest.approx(want))
    assert t.window == (0, (want or 90.0) * 1e9)
