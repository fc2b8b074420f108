"""Kernel microbenchmarks: the Pallas kernels, through ``kernels/ops.py``,
validated against the jnp oracles, plus timing of the jitted oracle path.

``ops.py`` interprets the kernels on the CPU backend and compiles them
everywhere else, so on a TPU the same entry points run compiled kernels.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Reporter, time_call
from repro.kernels import ops, ref


def run(rep: Optional[Reporter] = None) -> None:
    rep = rep or Reporter()
    rep.section("kernels: allclose vs jnp oracles + oracle timing")
    key = jax.random.PRNGKey(0)

    # flash attention
    B, H, S, hd = 1, 4, 256, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (B, H, S, hd), jnp.float32) for i in range(3))
    o_k = ops.flash_attention_op(q, k, v, causal=True)
    o_r = ref.ref_attention(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(o_k - o_r)))
    t = time_call(jax.jit(lambda a, b, c: ref.ref_attention(a, b, c)), q, k, v)
    rep.add("kernels/flash_attention_maxerr", f"{err:.2e}",
            f"(B,H,S,hd)=({B},{H},{S},{hd}); oracle {t * 1e3:.1f} ms")

    # selective scan
    B2, S2, di, st = 2, 128, 64, 8
    x = jax.random.normal(jax.random.fold_in(key, 10), (B2, S2, di))
    dt = jax.nn.softplus(jax.random.normal(
        jax.random.fold_in(key, 11), (B2, S2, di))) * 0.1
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 12), (di, st)))
    Bc = jax.random.normal(jax.random.fold_in(key, 13), (B2, S2, st))
    Cc = jax.random.normal(jax.random.fold_in(key, 14), (B2, S2, st))
    D = jax.random.normal(jax.random.fold_in(key, 15), (di,))
    y_k, _ = ops.selective_scan_op(x, dt, A, Bc, Cc, D)
    y_r, _ = ref.ref_selective_scan(x, dt, A, Bc, Cc, D)
    err = float(jnp.max(jnp.abs(y_k - y_r)))
    rep.add("kernels/selective_scan_maxerr", f"{err:.2e}",
            f"(B,S,di,st)=({B2},{S2},{di},{st})")

    # fused adam (the paper's cpu_adam hot spot, incl. partial update)
    n = 1 << 14
    p = jax.random.normal(jax.random.fold_in(key, 20), (n,))
    g = jax.random.normal(jax.random.fold_in(key, 21), (n,))
    m = jnp.zeros((n,))
    vv = jnp.zeros((n,))
    p_k, m_k, v_k, lowp = ops.fused_adam_op(p, m, vv, g,
                                            jnp.asarray(1, jnp.int32))
    p_r, m_r, v_r = ref.ref_adam(p, m, vv, g, 1)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in ((p_k, p_r), (m_k, m_r), (v_k, v_r)))
    t = time_call(jax.jit(lambda *a: ref.ref_adam(*a, 1)), p, m, vv, g)
    rep.add("kernels/fused_adam_maxerr", f"{err:.2e}",
            f"n={n}; oracle {t * 1e6:.0f} us "
            f"({n * 4 * 4 / t / 1e9:.1f} GB/s state bw)")


if __name__ == "__main__":
    run()
