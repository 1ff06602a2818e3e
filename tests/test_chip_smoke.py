"""CPU rehearsal of chip_smoke.py: its phase functions, with the same checks,
at smoke sizes (tiny P, smoke model configs, small kernel shapes), plus the
entry point's refusal to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_smoke_config
from repro.core import Job

ROOT = Path(__file__).resolve().parent.parent


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_chip_smoke()

SMALL_KERNELS = {
    "flash": ((1, 128, 4, 1, 64), (1, 12, 4, 1, 64)),
    "expert_gemm": ((2, 64, 128, 64),),
    "ssm_scan": ((1, 48, 64, 8),),
    "slstm_scan": ((1, 32, 2, 16),),
}


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    return env


def test_scheduler_phase_checksums_direct_and_mimo():
    out = cs.phase_scheduler(P=8, tasks_per_slot=4, n=32, iters=2,
                             ref_chunk=8)
    assert out["tasks"] == 32 and out["bundles"] == 8
    assert out["checksums_equal"] == 32


def test_scheduler_phase_fails_on_a_recorded_payload_error():
    def boom():
        raise ValueError("device payload failed")

    job = Job.array(4, payloads=[boom] * 4, name="failing")
    with pytest.raises(cs.SmokeError):
        cs._schedule(2, job)


def test_serving_phase_matches_teacher_forced_forward():
    out = cs.phase_serving(get_smoke_config("gemma_2b"), lanes=4, max_len=96,
                           n_requests=6, prompt_lens=(16, 64), max_new=6)
    assert out["requests"] == 6 and out["tokens"] == 36
    assert out["prompt_lens"] == [16, 64]
    assert out["checked_positions"] == (4 + 6) * 6
    assert out["compiles_after_warmup"] == 0


def test_teacher_forced_check_catches_a_wrong_token():
    cfg = get_smoke_config("gemma_2b")
    import jax

    model = cs.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = cs.ServingEngine(cfg, params, lanes=2, max_len=32)
    req = cs.ServeRequest(prompt=list(range(1, 9)), max_new_tokens=4)
    engine.run([req])
    cs.teacher_forced_check(model, params, [req], batch=2, margin_tol=0.0)
    req.output[2] = (req.output[2] + 1) % cfg.vocab_size
    with pytest.raises(cs.SmokeError):
        cs.teacher_forced_check(model, params, [req], batch=2, margin_tol=0.0)


def test_kernel_phase_matches_ref_oracles():
    out = cs.phase_kernels(shapes=SMALL_KERNELS)
    assert sorted(out["kernels"]) == sorted([
        "flash_attention_S128", "flash_attention_S12", "expert_gemm_E2",
        "ssm_scan_d64", "slstm_scan_H2_dh16"])


def test_sharded_phase_on_four_cpu_devices():
    code = (
        "import jax; jax.config.update('jax_num_cpu_devices', 4)\n"
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('cs', {str(ROOT / 'chip_smoke.py')!r})\n"
        "cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)\n"
        "from repro.configs import get_smoke_config\n"
        "out = cs.phase_sharded(get_smoke_config('codeqwen15_7b'),"
        " cut_layers=1, new_tokens=3)\n"
        "assert out['devices'] == [0, 1, 2, 3], out\n"
        "assert len(out['full_param_bytes']['bytes_per_device']) == 4\n"
        "print('SHARDED_OK', out['cut_rel_logit_err'])\n")
    proc = subprocess.run([sys.executable, "-c", code], env=_cpu_env(),
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED_OK" in proc.stdout


def test_entry_exits_nonzero_without_a_tpu():
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=_cpu_env(), cwd=ROOT, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and '"phase"' not in proc.stdout
    assert "no TPU" in proc.stderr
