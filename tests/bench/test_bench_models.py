"""A served architecture is added by files alone: a configuration names its
module in ``bench/models/`` with ``"model"``, and every configuration and
traffic file carries its own smoke sizes."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.drivers import serve
from smoke import at_smoke

ROOT = Path(__file__).resolve().parents[2]
#: what ``bench/drivers/serve.py`` takes from a model module
FUNCTIONS = ("program_config", "shapes", "program_params", "counts",
             "token_gaps")
DATA = sorted(str(p.relative_to(ROOT)) for d in ("configs", "traffic")
              for p in (ROOT / "bench" / d).glob("*.json"))
SERVED = [p for p in DATA if p.startswith("bench/configs/")
          and json.loads((ROOT / p).read_text())["driver"] == "serve"]


@pytest.mark.parametrize("path", SERVED)
def test_a_served_configuration_names_a_module_with_the_five_functions(
        path):
    doc = json.loads((ROOT / path).read_text())
    assert (ROOT / "bench/models" / f"{doc['model']}.py").is_file()
    mod = serve.model(doc)
    for name in FUNCTIONS:
        assert callable(getattr(mod, name)), name
    assert isinstance(doc["smoke"], dict)


@pytest.mark.parametrize("path", DATA)
def test_every_data_file_sets_only_its_own_keys_at_smoke_size(path):
    doc = json.loads((ROOT / path).read_text())
    small = at_smoke(doc, path)
    for k, v in doc["smoke"].items():
        if k == "why":
            continue
        assert k in doc, f"{path}: smoke sets {k!r}, which the file lacks"
        want = {**doc[k], **v} if isinstance(v, dict) else v
        assert small[k] == want


def test_a_file_without_smoke_sizes_fails_with_its_path():
    with pytest.raises(KeyError, match="bench/traffic/t9.none.json"):
        at_smoke({"rounds": 8}, "bench/traffic/t9.none.json")


#: run in a copy of the benchmark: resolve, shrink and run the added cell
_RUN_ADDED_CELL = """
import json, sys
from pathlib import Path
root = Path.cwd()
sys.path[:0] = [str(root), str(root / "tests/bench"), sys.argv[1]]
from bench import run
from smoke import shrink
spec = run.load_spec()
cell, doc, mix = run.resolve(spec, "serve.stub.chat")
doc, mix = shrink(cell, doc, mix)
out = run.run_cell(spec, cell, doc, mix, seed=2 ** 31 + 13, seconds=1.0,
                   trace=False)
print(json.dumps({"out": out, "layers": doc["num_hidden_layers"],
                  "check_sample": mix["check_sample"],
                  "served_by": sorted(m for m in sys.modules
                                      if m.startswith("bench.models."))}))
"""


def test_a_new_served_architecture_is_added_by_files_alone(tmp_path):
    """A copy of the benchmark gains, as new files and new entries only, a
    configuration whose model is a module of its own (here one that
    re-exports ``bench/models/dense.py``), with its own smoke sizes, and a
    cell for it. The cell resolves, shrinks to those sizes and runs correct
    through that module."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests/bench").mkdir(parents=True)
    shutil.copy(ROOT / "tests/bench/smoke.py", tmp_path / "tests/bench")
    bench = tmp_path / "bench"
    (bench / "models/stub.py").write_text(
        "from bench.models.dense import (  # noqa: F401\n"
        "    counts, program_config, program_params, shapes, token_gaps)\n")
    doc = json.loads((bench / "configs/phi4-mini-3.8b.json").read_text())
    doc.update(model="stub", smoke=dict(doc["smoke"], num_hidden_layers=3))
    (bench / "configs/stub-dense.json").write_text(json.dumps(doc))
    mix = json.loads((bench / "traffic/serve.phi4mini.chat.json")
                     .read_text())
    mix["smoke"] = dict(mix["smoke"], check_sample=3)
    (bench / "traffic/serve.stub.chat.json").write_text(json.dumps(mix))

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "stub-dense", "source": doc["source"],
        "file": "bench/configs/stub-dense.json", "reduced": doc["reduced"],
        "why": "the dense model under a module of its own"})
    spec["workloads"].append({
        "name": "serve.stub.chat", "config": "stub-dense",
        "traffic": "serve.stub.chat", "chips": 1,
        "why": "the chat mix on the stub model"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "serve.phi4mini.chat" in m.get("workloads", []):
            m["workloads"].append("serve.stub.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-c", _RUN_ADDED_CELL, str(ROOT / "src")],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert (got["layers"], got["check_sample"]) == (3, 3)
    assert got["served_by"] == ["bench.models.dense", "bench.models.stub"]
    out = got["out"]
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}
