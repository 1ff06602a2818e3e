"""BENCHMARK.json keeps to the benchmark's contract, every cell resolves
to its files by name, and every cell runs end to end at a smoke size."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from smoke import shrink

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRIC_KEYS = {"name", "unit", "better", "source"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_paths_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in SPEC["paths"])
            assert (ROOT / w).is_file()


def test_run_seconds_fits_the_full_check_with_24_cells():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    used = {c["config"] for c in SPEC["workloads"]}
    files = set()
    assert 1 <= len(SPEC["configs"]) <= 24
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        doc = json.loads((ROOT / c["file"]).read_text())
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert sorted(c["reduced"]) == sorted(doc["reduced"])
        assert doc["source"] == c["source"]
        assert (ROOT / "bench/drivers" / f"{doc['driver']}.py").is_file()


def test_workloads_resolve_to_their_files():
    names = set()
    configs = {c["name"] for c in SPEC["configs"]}
    assert 1 <= len(CELLS) <= 24
    four = 0
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k])
        assert w["name"] not in names and w["config"] in configs
        names.add(w["name"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        four += w["chips"] == 4
        cell, doc, mix = run.resolve(SPEC, w["name"])
        assert cell is w and isinstance(doc, dict) and isinstance(mix, dict)
    assert four <= max(1, len(CELLS) // 2)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_metrics_names_units_and_what_they_move():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    seen = set()
    assert 1 <= len(e2e) <= 16 and "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "workloads" not in e2e["setup_s"]
    assert 1 <= len(SPEC["per_layer"]) <= 128
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            reported = [x["name"] for x in run.metrics_of(SPEC, cell,
                                                          "end_to_end")]
            assert m["moves"] in reported
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for cell in CELLS:
        e = [x["name"] for x in run.metrics_of(SPEC, cell, "end_to_end")]
        assert "setup_s" in e and len(e) >= 2
        assert run.metrics_of(SPEC, cell, "per_layer")


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_a_reader_with_nothing_to_read_returns_none(name):
    assert run.reader(name)({"device_kind": "TPU v5 lite",
                             "seconds": 1.0}) is None


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_end_to_end_at_a_smoke_size(cell, trace):
    c, doc, mix = run.resolve(SPEC, cell)
    doc, mix = shrink(doc, mix)
    out = run.run_cell(SPEC, c, doc, mix, seed=2 ** 31 + 7, seconds=1.0,
                       trace=bool(trace))
    assert out["correct"], out
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in run.metrics_of(SPEC, cell, kind)}
    assert set(out["metrics"]) <= names
    if not trace:
        assert set(out["metrics"]) == names
    for m in out["metrics"].values():
        assert m["value"] is not None and m["unit"]
    assert not (ROOT / ".bench_out" / f"trace-{os.getpid()}").exists()


def _bench(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_run_and_no_result():
    p = _bench(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
