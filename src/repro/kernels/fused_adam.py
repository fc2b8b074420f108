"""Pallas fused-Adam kernel — the optimizer-step hot spot GreedySnake
offloads to the CPU (cpu_adam). On a TPU host-offload design the same
fused update runs as a single element-wise kernel over (8,128)-tiled
f32 vectors: one pass reads (p, m, v, g) and writes (p', m', v', lowp')
— 16 bytes in / 14 out per element, exactly the stream the paper's SSD
bandwidth bound models.

Supports the α-partial update (§4.4) via [lo, hi) masking on the global
element index, so the early/late fractions are single kernel launches.

Validated in interpret mode against repro.kernels.ref.ref_adam.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES


def _adam_kernel(p_ref, m_ref, v_ref, g_ref, bc_ref, lim_ref,
                 p_out, m_out, v_out, lp_out, *,
                 lr: float, b1: float, b2: float, eps: float, wd: float,
                 block: int):
    i = pl.program_id(0)
    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    lo = lim_ref[0, 0]
    hi = lim_ref[0, 1]

    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g * g
    mhat = m2 / bc_ref[0, 0]
    vhat = v2 / bc_ref[0, 1]
    p2 = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)

    # α-partial masking on the global flat index
    rows = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    idx = i * block + rows * _LANES + cols
    sel = (idx >= lo) & (idx < hi)
    p_out[...] = jnp.where(sel, p2, p).astype(p_out.dtype)
    m_out[...] = jnp.where(sel, m2, m).astype(m_out.dtype)
    v_out[...] = jnp.where(sel, v2, v).astype(v_out.dtype)
    lp_out[...] = jnp.where(sel, p2, p).astype(lp_out.dtype)


def fused_adam(p, m, v, g, step, *, lo: int = 0, hi: int = -1,
               lr: float = 1e-3, b1: float = 0.9, b2: float = 0.95,
               eps: float = 1e-8, wd: float = 0.0,
               lowp_dtype=jnp.bfloat16, block_rows: int = 64,
               interpret: bool
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Flat f32 vectors p, m, v, g of length n. Updates elements [lo, hi)
    (hi=-1 => n), returning (p', m', v', lowp'). Padding to (8,128) tiles
    is handled here. ``interpret=True`` runs the kernel body as jnp (any
    backend); ``False`` compiles it with Mosaic (TPU only)."""
    n = p.size
    hi = n if hi < 0 else hi
    block = block_rows * _LANES
    pad = (-n) % block
    npad = n + pad

    def prep(x):
        return jnp.pad(x.reshape(-1), (0, pad)).reshape(npad // _LANES, _LANES)

    rows_per_block = block // _LANES
    grid = (npad // block,)
    # Adam's bias corrections 1 - b**t are computed here, not in the
    # kernel: Mosaic cannot lower a float power (math.powf)
    t = jnp.asarray(step, jnp.float32)
    bc = jnp.stack([1 - b1 ** t, 1 - b2 ** t]).reshape(1, 2)
    lim = jnp.asarray([lo, hi], jnp.int32).reshape(1, 2)

    kernel = functools.partial(_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                               wd=wd, block=block)
    vec_spec = pl.BlockSpec((rows_per_block, _LANES), lambda i: (i, 0))
    pair_spec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vec_spec] * 4 + [pair_spec, pair_spec],
        out_specs=[vec_spec] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((npad // _LANES, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((npad // _LANES, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((npad // _LANES, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((npad // _LANES, _LANES), lowp_dtype),
        ],
        interpret=interpret,
    )(prep(p), prep(m), prep(v), prep(g), bc, lim)
    return tuple(o.reshape(-1)[:n] for o in outs)
