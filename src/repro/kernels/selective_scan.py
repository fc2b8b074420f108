"""Pallas TPU selective-scan (Mamba-1) kernel.

TPU adaptation: the recurrence h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t
is element-wise in the d_inner dimension, so we tile d_inner into
(block_d) VMEM lanes (multiples of 128 for the VPU) and keep the hidden
state h (block_d, st) resident in VMEM scratch while streaming the time
axis in (block_t) chunks on the innermost sequential grid axis. No
inter-chip traffic: d_inner is the natural shard dim.

Grid: (B, num_d_blocks, num_t_chunks); within a chunk the kernel runs a
fori_loop over time steps (VPU element-wise ops + a (block_d x st) @ (st)
contraction folded into an elementwise-multiply-reduce).

Validated in interpret mode against repro.kernels.ref.ref_selective_scan.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
                 h_ref, *, block_t: int, num_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...].astype(jnp.float32)             # (bd, st)
    D = d_ref[...].astype(jnp.float32)             # (1, bd)

    def step(t, h):
        xt = x_ref[0, t, :].astype(jnp.float32)    # (bd,)
        dtt = dt_ref[0, t, :].astype(jnp.float32)  # (bd,)
        bt = b_ref[0, t, :].astype(jnp.float32)    # (st,)
        ct = c_ref[0, t, :].astype(jnp.float32)    # (st,)
        da = jnp.exp(dtt[:, None] * A)             # (bd, st)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=-1) + xt * D[0]
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ti == num_t - 1)
    def _final():
        hout_ref[0] = h


def selective_scan_fwd(x, dt, A, Bc, Cc, D, *, block_d: int = 256,
                       block_t: int = 128, interpret: bool
                       ) -> Tuple[jax.Array, jax.Array]:
    """x, dt: (B,S,di); Bc,Cc: (B,S,st); A: (di,st); D: (di,).
    Returns (y: (B,S,di), h_final: (B,di,st) f32). ``interpret=True``
    runs the kernel body as jnp (any backend); ``False`` compiles it
    with Mosaic (TPU only)."""
    B, S, di = x.shape
    st = A.shape[-1]
    bd = min(block_d, di)
    while di % bd:
        bd //= 2
    bt = min(block_t, S)
    while S % bt:
        bt //= 2
    nd, nt = di // bd, S // bt

    kernel = functools.partial(_scan_kernel, block_t=bt, num_t=nt)
    d2 = D.reshape(1, di)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),   # x
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),   # dt
            pl.BlockSpec((bd, st), lambda b, d, t: (d, 0)),         # A
            pl.BlockSpec((1, bt, st), lambda b, d, t: (b, t, 0)),   # B
            pl.BlockSpec((1, bt, st), lambda b, d, t: (b, t, 0)),   # C
            pl.BlockSpec((1, bd), lambda b, d, t: (0, d)),          # D
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bd), lambda b, d, t: (b, t, d)),   # y
            pl.BlockSpec((1, bd, st), lambda b, d, t: (b, d, 0)),   # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), x.dtype),
            jax.ShapeDtypeStruct((B, di, st), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, st), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bc, Cc, d2)
    return y, h
