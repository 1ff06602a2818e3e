"""Compile the main path's device programs for a described, unattached TPU
v5e: the four Pallas kernels at real widths and the full-width Gemma-2B
decode step. Nothing runs; the chip's compiler accepts or refuses each
program, as it would on the chip.

The topology is described inside a module-scoped fixture, never at import,
in a ``skipif`` or in ``parametrize``: only one process may load the TPU
library, and every test worker imports every test file. Keep all such
compiles in this one file, so that a single worker loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.moe_gemm import expert_gemm
from repro.kernels.slstm_scan import slstm_scan_fwd
from repro.kernels.ssm_scan import ssm_scan_fwd
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_decode_step

HBM_BYTES = 16 * 10**9          # one v5e chip

# kernels/ops.py picks interpret mode from the default backend, which is the
# CPU here, so the kernels themselves are compiled, not their ops wrappers.
# Real widths: Gemma-2B attention (prefill of 2048 and of 12 tokens),
# Granite-MoE expert GEMM, Jamba's Mamba scan, xLSTM-1.3B's sLSTM heads
KERNELS = {
    "flash_attention_S2048": (flash_attention_fwd, [
        ((1, 2048, 8, 256), jnp.bfloat16), ((1, 2048, 1, 256), jnp.bfloat16),
        ((1, 2048, 1, 256), jnp.bfloat16)]),
    "flash_attention_S12": (flash_attention_fwd, [
        ((1, 12, 8, 256), jnp.bfloat16), ((1, 12, 1, 256), jnp.bfloat16),
        ((1, 12, 1, 256), jnp.bfloat16)]),
    "expert_gemm": (expert_gemm, [
        ((32, 512, 1024), jnp.bfloat16), ((32, 1024, 512), jnp.bfloat16)]),
    "ssm_scan": (ssm_scan_fwd, [
        ((1, 2048, 8192), jnp.bfloat16), ((1, 2048, 8192), jnp.bfloat16),
        ((8192, 16), jnp.float32), ((1, 2048, 16), jnp.bfloat16),
        ((1, 2048, 16), jnp.bfloat16), ((8192,), jnp.float32)]),
    "slstm_scan": (slstm_scan_fwd, [
        ((1, 512, 4, 2048), jnp.bfloat16), ((4, 4, 512, 512), jnp.float32)]
        + [((1, 4, 512), jnp.float32)] * 4),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep any cache out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name]
    specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gemma_2b_decode_step_fits_one_v5e(topo):
    cfg = get_config("gemma_2b")
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    built = build_decode_step(cfg, mesh,
                              ShapeConfig("decode", "decode", 512, 8))
    mem = built.lower().compile().memory_analysis()
    assert mem.argument_size_in_bytes > 4.5e9      # the bf16 weights are there
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
