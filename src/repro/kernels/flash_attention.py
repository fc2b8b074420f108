"""Pallas TPU flash-attention forward kernel.

TPU adaptation (not a CUDA port): the kernel tiles Q into (block_q, hd)
VMEM blocks and streams K/V through VMEM in (block_k, hd) tiles on the
innermost (sequential) grid axis, keeping the running max/denominator/
accumulator in VMEM scratch across those grid steps — the MXU sees
(block_q x hd) @ (hd x block_k) matmuls with both dims multiples of 128.
Grid: (B, H, num_q_blocks, num_k_blocks); the kv axis is the
fastest-varying (sequential on TPU), so scratch carries are legal.

Validated in interpret mode against repro.kernels.ref.ref_attention.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, scale: float, block_q: int, block_k: int,
                  num_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                        (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    corr = jnp.exp(m_prev - m_cur)
    l_cur = corr * l_prev + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur
    l_ref[...] = l_cur

    @pl.when(ki == num_k - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (acc_ref[...]
                             / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = 256, block_k: int = 512,
                        interpret: bool) -> jax.Array:
    """q, k, v: (B, H, S, hd) (pre-grouped; GQA callers repeat or group
    outside). Returns (B, H, S, hd) in q.dtype. ``interpret=True`` runs
    the kernel body as jnp (any backend); ``False`` compiles it with
    Mosaic (TPU only)."""
    B, H, Sq, hd = q.shape
    Skv = k.shape[2]
    bq = min(block_q, Sq)
    while Sq % bq:
        bq //= 2
    bk = min(block_k, Skv)
    while Skv % bk:
        bk //= 2
    nq, nk = Sq // bq, Skv // bk
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)

    kernel = functools.partial(_flash_kernel, causal=causal, scale=sc,
                               block_q=bq, block_k=bk, num_k=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
