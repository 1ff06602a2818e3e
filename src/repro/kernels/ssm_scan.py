"""Pallas TPU selective-scan kernel (Mamba-1 forward).

TPU adaptation of the CUDA selective-scan (DESIGN.md §2): the CUDA kernel
keeps h in registers/SRAM and walks time sequentially per thread block; here
each grid cell owns a (batch, d_inner-block) tile, keeps the state in
registers/VMEM, and walks time sequentially — every step is an [N, bd]
VPU-wide elementwise update plus a sublane reduction against C_t. HBM
traffic is exactly u/dt/B/C read once and y written once (the jnp fallback
spills chunk states to HBM).

Layout, chosen so that Mosaic can prove every load aligned:
  * the state is held transposed, [N, bd]: d_inner on lanes, N on sublanes,
    so dt_t/u_t rows broadcast over sublanes and B_t/C_t columns over lanes;
  * u/dt are read in row slabs of ``ROW`` steps, one [ROW, bd] load at a
    sublane offset that is a multiple of ROW (the bf16 sublane tile), and
    the steps of a slab are unrolled with static row indices;
  * B^T/C^T are read in lane slabs of ``LANE`` steps, one [N, LANE] load at
    a lane offset that is a multiple of LANE; step t's column is picked out
    of the slab with a lane mask and a lane reduction.
S is zero-padded up to a multiple of LANE. A padded step has dt = u = 0,
so dA = 1 and the update adds 0: h is carried through unchanged.

Grid: (B, d_inner/block_d). Time stays inside the kernel so the state never
leaves the core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_D = 512
ROW = 16            # sublane tile of bf16 (and a multiple of f32's 8)
LANE = 128          # lane tile


def _kernel(u_ref, dt_ref, A_ref, BT_ref, CT_ref, D_ref, h0_ref,
            y_ref, h_out_ref, *, n_lane_slabs: int, lane: int):
    A = A_ref[...].astype(jnp.float32)              # [N, bd]
    D = D_ref[...].astype(jnp.float32)              # [1, bd]

    def lane_slab(s, h):
        t0 = pl.multiple_of(s * lane, lane)
        Bt = BT_ref[0, :, pl.ds(t0, lane)].astype(jnp.float32)  # [N, lane]
        Ct = CT_ref[0, :, pl.ds(t0, lane)].astype(jnp.float32)  # [N, lane]
        col = jax.lax.broadcasted_iota(jnp.int32, Bt.shape, 1)

        def row_slab(j, h):
            r0 = pl.multiple_of(t0 + j * ROW, ROW)
            u = u_ref[0, pl.ds(r0, ROW), :].astype(jnp.float32)    # [ROW, bd]
            dt = dt_ref[0, pl.ds(r0, ROW), :].astype(jnp.float32)  # [ROW, bd]
            ys = []
            for i in range(ROW):
                sel = col == j * ROW + i
                B_i = jnp.sum(jnp.where(sel, Bt, 0.0), axis=1, keepdims=True)
                C_i = jnp.sum(jnp.where(sel, Ct, 0.0), axis=1, keepdims=True)
                dt_i = dt[i:i + 1]                                  # [1, bd]
                u_i = u[i:i + 1]
                h = h * jnp.exp(dt_i * A) + (dt_i * u_i) * B_i      # [N, bd]
                ys.append(jnp.sum(h * C_i, axis=0, keepdims=True) + u_i * D)
            y_ref[0, pl.ds(r0, ROW), :] = jnp.concatenate(ys).astype(
                y_ref.dtype)
            return h

        return jax.lax.fori_loop(0, lane // ROW, row_slab, h)

    h = jax.lax.fori_loop(0, n_lane_slabs, lane_slab,
                          h0_ref[0].astype(jnp.float32))
    h_out_ref[0] = h


def ssm_scan_fwd(u, dt, A, B, C, D, h0=None, *,
                 block_d: int = DEFAULT_BLOCK_D, interpret: bool = False):
    """u, dt: [Bb,S,d]; A: [d,N]; B,C: [Bb,S,N]; D: [d]; h0: [Bb,d,N] or None.

    Returns (y [Bb,S,d], h_last [Bb,d,N] fp32).
    """
    Bb, S, d = u.shape
    N = A.shape[1]
    block_d = min(block_d, d)
    assert d % block_d == 0, (d, block_d)
    nd = d // block_d
    # one lane slab covers a short sequence whole (a block dim equal to the
    # array dim is always legal); longer ones are cut into LANE-wide slabs
    lane = LANE if S > LANE else -(-S // ROW) * ROW
    Sp = -(-S // lane) * lane
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        u, dt, B, C = (jnp.pad(x, pad) for x in (u, dt, B, C))
    h0T = (jnp.zeros((Bb, N, d), jnp.float32) if h0 is None
           else h0.astype(jnp.float32).swapaxes(1, 2))

    kernel = functools.partial(_kernel, n_lane_slabs=Sp // lane,
                               lane=lane)
    y, hT = pl.pallas_call(
        kernel,
        grid=(Bb, nd),
        in_specs=[
            pl.BlockSpec((1, Sp, block_d), lambda b, di: (b, 0, di)),   # u
            pl.BlockSpec((1, Sp, block_d), lambda b, di: (b, 0, di)),   # dt
            pl.BlockSpec((N, block_d), lambda b, di: (0, di)),          # A^T
            pl.BlockSpec((1, N, Sp), lambda b, di: (b, 0, 0)),          # B^T
            pl.BlockSpec((1, N, Sp), lambda b, di: (b, 0, 0)),          # C^T
            pl.BlockSpec((1, block_d), lambda b, di: (0, di)),          # D
            pl.BlockSpec((1, N, block_d), lambda b, di: (b, 0, di)),    # h0^T
        ],
        out_specs=[
            pl.BlockSpec((1, Sp, block_d), lambda b, di: (b, 0, di)),   # y
            pl.BlockSpec((1, N, block_d), lambda b, di: (b, 0, di)),    # h^T
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, Sp, d), u.dtype),
            jax.ShapeDtypeStruct((Bb, N, d), jnp.float32),
        ],
        interpret=interpret,
    )(u, dt, A.T, B.swapaxes(1, 2), C.swapaxes(1, 2), D.reshape(1, d), h0T)
    return y[:, :S], hT.swapaxes(1, 2)
