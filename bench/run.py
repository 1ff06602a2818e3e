"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Everything a cell is made of is found by name, so a cell, configuration,
traffic mix or per-layer metric is added by adding files and entries:

  BENCHMARK.json           the cell: its config, traffic and chips
  bench/configs/<config>.json   sizes as run, "driver", and "smoke": the
                                keys it overrides at the tests' CPU size
  bench/traffic/<cell>.json     the mix, and its "smoke" keys
  bench/drivers/<driver>.py     runs a cell of that kind: run(ctx)
  bench/metrics/<metric>.py     read(obs) -> number, or None when the run
                                left it nothing to read

A served model of a new architecture needs no edit of these files either:

  bench/configs/<config>.json   "driver": "serve", "model": "<model>", "smoke"
  bench/models/<model>.py       program_config(doc) -> (cfg, changed),
                                shapes(doc) (with .vocab),
                                program_params(cfg, sh, seed),
                                counts(cfg) (prefill_flops, decode_flops,
                                decode_bytes), token_gaps(sh, seed, seqs,
                                control=...)
  bench/reference/<model>.py    its plain reference, which imports nothing
                                of the program
  bench/traffic/<cell>.json     with "smoke"
  BENCHMARK.json           the config and the cell; the cell's name added
                           to the "workloads" of each end-to-end and
                           per-layer metric it reports, and new metric
                           files for what the architecture adds

The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for. Set-up (weights and data from the seed,
compiles, warm-up) is timed as ``setup_s``; the window then runs for
``--seconds`` with compiles counted (there should be none). With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window and from the spans the benchmark puts round the program's calls.
Every run checks what the timed path produced against a plain reference.
The last line of standard output is the result; each number compared is
printed beside its limit as the last lines of standard error and, under
``checks``, last in the result.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: traces are written here and deleted once read
OUT_DIR = ROOT / ".bench_out"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Recorder:
    """Host spans round the program's calls, in traced runs only.

    ``span(name)`` adds the block's host seconds to ``totals[name]`` and
    writes it into the profiler trace as ``bench:<name>``; in untraced runs
    it does nothing.
    """

    def __init__(self, on: bool):
        self.on = on
        self.totals = collections.defaultdict(float)
        self.counts = collections.defaultdict(int)
        self.records = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:" + name):
            yield
        self.totals[name] += time.perf_counter() - t0

    def count(self, name: str, n: int = 1) -> None:
        self.counts[name] += n


class Window:
    def __init__(self):
        self.t0 = 0.0


@contextlib.contextmanager
def timed_window():
    """A window with nothing but its start time (no meter, no trace)."""
    w = Window()
    w.t0 = time.perf_counter()
    yield w


class Context:
    """What a driver is given, and what it hands back."""

    def __init__(self, cell: dict, config: dict, traffic: dict, *,
                 seed: int, seconds: float, trace: bool,
                 control: bool = False):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        #: put the lower-precision control in the program's place
        #: (``bench/control.py``); the benchmark's own runs never do
        self.control = control
        self.setup_s = None
        self.compiles_in_window = None
        self.memory_peak_bytes = None
        self.attempted = self.failed = 0
        self.metrics = {}
        self.obs = {}
        self.checks = {}
        self.trace_dir = None
        self._recorder = None
        #: set-up's marks, seconds from process start, logged with setup_s
        self.marks = {"driver": time.perf_counter() - T_START}

    # ------------------------------------------------------------ driver
    def log(self, **kw) -> None:
        print(json.dumps({"cell": self.cell["name"], **kw}), flush=True)

    def mark(self, name: str) -> None:
        self.marks[name] = time.perf_counter() - T_START

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START
        self.log(setup_s=self.setup_s, setup_marks=self.marks)

    def recorder(self) -> Recorder:
        self._recorder = Recorder(self.trace)
        return self._recorder

    @contextlib.contextmanager
    def window(self):
        """The measured window and the drain after it: compiles counted,
        and in traced runs the profiler on."""
        import jax

        from bench.meter import CompileMeter

        if self.trace:
            self.trace_dir = OUT_DIR / f"trace-{os.getpid()}"
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # no per-call Python events
            opts.host_tracer_level = 1          # annotations, not runtime
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        w = Window()
        try:
            with CompileMeter() as meter, \
                    jax.profiler.TraceAnnotation("bench:window"):
                w.t0 = time.perf_counter()
                yield w
        finally:
            if self.trace:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                self.log(trace_stop_s=time.perf_counter() - t)
        self.compiles_in_window = meter.compiles

    def read_memory(self) -> None:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        peaks = [s.get("peak_bytes_in_use") for s in stats]
        self.memory_peak_bytes = max((p for p in peaks if p is not None),
                                     default=None)

    def end_to_end(self, **values) -> None:
        self.metrics.update(values)

    def observe(self, **values) -> None:
        self.obs.update(values)

    def compare(self, name: str, value: float, limit: float) -> None:
        """A number held to its limit: the run is correct only if every
        one is at or under it."""
        self.checks[name] = {"value": value, "limit": limit}


# ----------------------------------------------------------------- lookup
def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str, bench: Path = BENCH):
    """The cell named ``workload`` with its config and traffic files."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; cells: {sorted(cells)}")
    cell = cells[workload]
    config = json.loads((bench / "configs" / f"{cell['config']}.json")
                        .read_text())
    traffic = json.loads((bench / "traffic" / f"{cell['name']}.json")
                         .read_text())
    return cell, config, traffic


def metrics_of(spec: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if cell in m.get("workloads", [cell])]


def reader(name: str):
    """``read`` of ``bench/metrics/<name>.py`` (a name may hold dots)."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -------------------------------------------------------------------- run
def setup_jax() -> None:
    """The compile cache at its fixed place in the checkout, every program
    kept in it. Call before JAX is first imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def check_chip(chips: int) -> None:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool,
             control: bool = False) -> dict:
    """Run one cell on the devices JAX has, and return the result line."""
    import jax

    ctx = Context(cell, config, traffic, seed=seed, seconds=seconds,
                  trace=trace, control=control)
    driver = importlib.import_module(f"bench.drivers.{config['driver']}")
    driver.run(ctx)
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    ok = (ctx.failed == 0 and ctx.compiles_in_window == 0
          and all(c["value"] <= c["limit"] for c in ctx.checks.values()))
    checks = dict(ctx.checks, compiles_in_window={
        "value": ctx.compiles_in_window, "limit": 0})
    out = {"correct": bool(ok), "attempted": ctx.attempted,
           "failed": ctx.failed}
    if trace:
        metrics, extra = _per_layer(spec, ctx, device)
        out.update(metrics=metrics, device=device, **extra)
    else:
        values = dict(ctx.metrics, setup_s=ctx.setup_s)
        out.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(spec, cell["name"], "end_to_end")
            if values.get(m["name"]) is not None}, device=device)
    out["checks"] = checks
    return out


def _per_layer(spec, ctx, device):
    from bench import trace as tr

    obs = dict(ctx.obs, device_kind=device["kind"], seconds=ctx.seconds)
    rec = ctx._recorder
    if rec is not None:
        obs.update(spans=dict(rec.totals), counts=dict(rec.counts),
                   records=dict(rec.records))
    extra = {}
    if ctx.trace_dir is not None:
        t0 = time.perf_counter()
        path = tr.find_xplane(str(ctx.trace_dir))
        size = os.path.getsize(path)
        t = tr.load(path)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        ctx.log(trace_bytes=size, trace_read_s=time.perf_counter() - t0)
        if t.window is not None:
            lost = tr.cut_at_loss(t, ctx.seconds)
            ctx.log(trace_events=tr.census(t), trace_lost_after_s=lost)
        obs["trace"] = t
        if t.programs:
            summ = tr.device_summary(t)
            device.update(busy_s=summ["busy_s"], window_s=t.window_s)
            extra["breakdown"] = {"device_ops": summ["device_ops"],
                                  "idle_gaps": summ["idle_gaps"]}
    metrics = {}
    for m in metrics_of(spec, ctx.cell["name"], "per_layer"):
        v = reader(m["name"])(obs)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics, extra


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_spec()
    cell, config, traffic = resolve(spec, args.workload)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    setup_jax()
    try:
        check_chip(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}; no run", file=sys.stderr)
        return 2
    out = run_cell(spec, cell, config, traffic, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the script's own directory would shadow modules such as ``trace``
    if sys.path and Path(sys.path[0]).resolve() == BENCH:
        del sys.path[0]
    sys.exit(main())
