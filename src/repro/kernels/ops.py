"""Jit'd public wrappers for the Pallas kernels.

The kernels compile with Mosaic on a TPU. On the CPU backend, where
Mosaic cannot run, they execute in interpret mode (the kernel body runs
as traced jnp). Any other backend compiles, so a kernel that cannot be
lowered there fails loudly instead of silently interpreting.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_adam import fused_adam
from repro.kernels.selective_scan import selective_scan_fwd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention_op(q, k, v, *, causal: bool = True):
    return flash_attention_fwd(q, k, v, causal=causal,
                               interpret=_interpret())


@jax.jit
def selective_scan_op(x, dt, A, Bc, Cc, D):
    return selective_scan_fwd(x, dt, A, Bc, Cc, D, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("lo", "hi", "lr"))
def fused_adam_op(p, m, v, g, step, *, lo: int = 0, hi: int = -1,
                  lr: float = 1e-3):
    return fused_adam(p, m, v, g, step, lo=lo, hi=hi, lr=lr,
                      interpret=_interpret())
