"""Jit'd dispatch wrappers for the Pallas kernels.

On TPU the kernels lower to Mosaic. On the CPU backend they run in
interpret mode (the kernel body executes as plain XLA ops), so the same call
sites validate in CPU tests; any other backend is refused rather than
silently interpreted. Forward-only by design: training uses the XLA paths
(chunked attention / chunked scan), serving and prefill use the kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.moe_gemm import expert_gemm as _expert_gemm
from repro.kernels.ssm_scan import ssm_scan_fwd


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for TPU and interpret on CPU; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 512,
                    block_k: int = 512):
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_d",))
def ssm_scan(u, dt, A, B, C, D, h0=None, block_d: int = 512):
    return ssm_scan_fwd(u, dt, A, B, C, D, h0=h0, block_d=block_d,
                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def expert_gemm(x, w, block_m: int = 256, block_n: int = 256,
                block_k: int = 512):
    return _expert_gemm(x, w, block_m=block_m, block_n=block_n,
                        block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk_t",))
def slstm_scan(pre, r_all, c0, n0, m0, h0, chunk_t: int = 256):
    from repro.kernels.slstm_scan import slstm_scan_fwd

    return slstm_scan_fwd(pre, r_all, c0, n0, m0, h0, chunk_t=chunk_t,
                          interpret=_interpret())
