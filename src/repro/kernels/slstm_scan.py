"""Pallas TPU fused sLSTM scan (forward).

The roofline analysis (EXPERIMENTS.md §Perf #1) showed the XLA sLSTM path is
catastrophically memory-bound: every timestep round-trips the recurrent
weights R (16 MB) and ~a dozen [B, d] gate buffers through HBM —
~50 MB/step -> petabytes per train step at 4096 steps x 24 layers.

This kernel is the TPU-native fix: R, the (c, n, m, h) state and all gate
temporaries live in VMEM for the whole sequence; HBM traffic collapses to
the streamed preactivations (read once) and the h outputs (written once) —
the same SRAM-residency idea as the xLSTM paper's fused CUDA kernel, mapped
to the TPU memory hierarchy.

Grid: (heads, time-chunks), time innermost so VMEM scratch carries the state
across chunks; per-head R blocks are grid-invariant along t (Mosaic skips
the re-fetch). Layout is head-major, pre as [H, S, B, 4*dh]: a block is
(1, chunk_t, B, 4*dh), whose last two dims are whole array dims, and a step
reads one [B, 4*dh] row at a dynamic index on an untiled dim. The four
gates' recurrent weights of a head are concatenated as [dh, 4*dh], so
within a chunk a fori_loop steps the recurrence with one [B, dh] x
[dh, 4*dh] matmul per step on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_CHUNK_T = 256


def _kernel(pre_ref, r_ref, c0_ref, n0_ref, m0_ref, h0_ref,
            hs_ref, cT_ref, nT_ref, mT_ref, hT_ref,
            c_s, n_s, m_s, h_s, *, chunk: int, nt: int, dh: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _load():
        c_s[...] = c0_ref[0].astype(jnp.float32)
        n_s[...] = n0_ref[0].astype(jnp.float32)
        m_s[...] = m0_ref[0].astype(jnp.float32)
        h_s[...] = h0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)         # [dh, 4*dh]

    def step(t, _):
        pre = pre_ref[0, t].astype(jnp.float32) + jax.lax.dot_general(
            h_s[...], r, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [B, 4*dh]
        i_t = pre[:, 0 * dh:1 * dh]
        f_t = pre[:, 1 * dh:2 * dh]
        z_t = jnp.tanh(pre[:, 2 * dh:3 * dh])
        o_t = jax.nn.sigmoid(pre[:, 3 * dh:4 * dh])
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m_s[...], i_t)
        scale = jnp.exp(logf + m_s[...] - m_new)
        inp = jnp.exp(i_t - m_new)
        c = c_s[...] * scale + inp * z_t
        n = n_s[...] * scale + inp
        h_new = o_t * c / jnp.maximum(n, 1e-6)
        c_s[...] = c
        n_s[...] = n
        m_s[...] = m_new
        h_s[...] = h_new
        hs_ref[0, t] = h_new.astype(hs_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ti == nt - 1)
    def _store():
        cT_ref[0] = c_s[...]
        nT_ref[0] = n_s[...]
        mT_ref[0] = m_s[...]
        hT_ref[0] = h_s[...]


def slstm_scan_fwd(pre, r_all, c0, n0, m0, h0, *,
                   chunk_t: int = DEFAULT_CHUNK_T, interpret: bool = False):
    """pre: [B,S,4,d] preactivations; r_all: [4,H,dh,dh];
    c0/n0/m0/h0: [B,H,dh]. Returns (hs [B,S,d], (cT,nT,mT,hT) [B,H,dh]).
    """
    B, S, four, d = pre.shape
    _, H, dh, _ = r_all.shape
    assert four == 4 and H * dh == d, (pre.shape, r_all.shape)
    chunk_t = min(chunk_t, S)
    assert S % chunk_t == 0
    nt = S // chunk_t
    pre_h = (pre.reshape(B, S, 4, H, dh).transpose(3, 1, 0, 2, 4)
             .reshape(H, S, B, 4 * dh))
    r_cat = r_all.transpose(1, 2, 0, 3).reshape(H, dh, 4 * dh)
    states = [x.swapaxes(0, 1) for x in (c0, n0, m0, h0)]   # [H,B,dh]

    kernel = functools.partial(_kernel, chunk=chunk_t, nt=nt, dh=dh)
    state_spec = pl.BlockSpec((1, B, dh), lambda h, t: (h, 0, 0))
    state_shape = jax.ShapeDtypeStruct((H, B, dh), jnp.float32)
    hs, *final = pl.pallas_call(
        kernel,
        grid=(H, nt),
        in_specs=[
            pl.BlockSpec((1, chunk_t, B, 4 * dh), lambda h, t: (h, t, 0, 0)),
            pl.BlockSpec((1, dh, 4 * dh), lambda h, t: (h, 0, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, chunk_t, B, dh), lambda h, t: (h, t, 0, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_shape=[jax.ShapeDtypeStruct((H, S, B, dh), pre.dtype)]
        + [state_shape] * 4,
        scratch_shapes=[_vmem((B, dh), jnp.float32) for _ in range(4)],
        interpret=interpret,
    )(pre_h, r_cat, *states)
    hs = hs.transpose(2, 1, 0, 3).reshape(B, S, d)
    return hs, tuple(x.swapaxes(0, 1) for x in final)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
