"""The comparison that decides ``correct`` fails a broken timed path.

Each test skips the harness's look for a chip, drives the rest of a run at
a smoke size with one fault planted underneath, and sees ``correct`` come
out false. The controls (the reference one precision lower in the
program's place, ``bench/control.py``) are checked here too."""
import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import run
from smoke import shrink

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
#: every mix of each configuration, whether or not a cell runs it yet
CELLS = {"t9.direct.1ms": "table9-p1408", "t9.mimo.1ms": "table9-p1408",
         "serve.phi4mini.chat": "phi4-mini-3.8b"}
TASKS = [c for c, cfg in CELLS.items() if cfg == "table9-p1408"]
SERVE = [c for c, cfg in CELLS.items() if cfg == "phi4-mini-3.8b"]


def _run(cell, *, control=False, matrix=None):
    c = {"name": cell, "config": CELLS[cell], "traffic": cell, "chips": 1}
    doc = json.loads((ROOT / f"bench/configs/{c['config']}.json").read_text())
    mix = json.loads((ROOT / f"bench/traffic/{cell}.json").read_text())
    doc, mix = shrink(doc, mix)
    if matrix:
        doc = dict(doc, task_matrix=matrix)
    return run.run_cell(SPEC, c, doc, mix, seed=2 ** 31 + 5, seconds=0.5,
                        trace=False, control=control)


@pytest.mark.parametrize("cell", TASKS)
def test_sound_task_runs_are_correct(cell):
    assert _run(cell)["correct"]


@pytest.mark.parametrize("cell", TASKS)
def test_task_control_accumulating_in_bf16_is_not_correct(cell):
    # at n = 512 the row sums pass 256, where bf16 stops being exact
    out = _run(cell, control=True, matrix=512)
    assert not out["correct"] and out["checks"]["wrong_checksums"]["value"]


@pytest.mark.parametrize("cell", TASKS)
def test_half_the_tasks_left_out_is_not_correct(cell, monkeypatch):
    from repro.core.executor import JaxDispatchExecutor

    real = JaxDispatchExecutor.run

    def half(self, task, done):
        if task.index % 2:
            done(True)                  # reported done, never run
        else:
            real(self, task, done)
    monkeypatch.setattr(JaxDispatchExecutor, "run", half)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["missing_or_failed_tasks"]["value"] > 0


@pytest.mark.parametrize("cell", TASKS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        cell, monkeypatch):
    from repro.core.executor import JaxDispatchExecutor

    real = JaxDispatchExecutor._finish
    monkeypatch.setattr(JaxDispatchExecutor, "_finish", staticmethod(
        lambda out: real(out) + 1 if not isinstance(out, list)
        else [x + 1 for x in real(out)]))
    out = _run(cell)
    assert not out["correct"] and out["checks"]["wrong_checksums"]["value"]


@pytest.mark.parametrize("cell", SERVE)
def test_sound_serving_runs_are_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("cell", SERVE)
def test_serving_control_in_float8_is_not_correct(cell):
    out = _run(cell, control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", SERVE)
def test_a_token_altered_where_it_is_produced_is_not_correct(
        cell, monkeypatch):
    from repro.serving import ServingEngine

    real = ServingEngine._decode_fn

    def altered(self, params, caches, tokens, positions):
        tok, caches = real(self, params, caches, tokens, positions)
        return (tok + 1) % self.cfg.vocab_size, caches
    monkeypatch.setattr(ServingEngine, "_decode_fn", altered)
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("cell", SERVE)
def test_a_decode_step_that_keeps_its_state_is_not_correct(
        cell, monkeypatch):
    from repro.serving import ServingEngine

    real = ServingEngine._decode_fn

    def frozen(self, params, caches, tokens, positions):
        tok, _ = real(self, params, caches, tokens, positions)
        return tok, caches              # the cache never takes the token
    monkeypatch.setattr(ServingEngine, "_decode_fn", frozen)
    assert not _run(cell)["correct"]
