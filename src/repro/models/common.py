"""Shared model building blocks: norms, RoPE (with YaRN), init helpers."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with f32 accumulation, output in x.dtype."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rms_scale(d: int) -> jax.Array:
    # stored as zero-centered ("1 + scale" applied in rms_norm, gemma-style)
    return jnp.zeros((d,), jnp.float32)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,), f32."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature m(s, a) = 0.1 a ln s + 1 (1 for s <= 1)."""
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(dim: int, theta: float, factor: float, orig_max_pos: int,
               beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies (arXiv:2309.00071, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``), shape (dim // 2,), f32: the
    extrapolated frequencies above ``beta_fast`` rotations over the
    original context, the ``factor``-interpolated ones below
    ``beta_slow``, and a linear ramp between."""
    def corr_dim(rot):
        return (dim * math.log(orig_max_pos / (rot * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr_dim(beta_fast)), 0)
    hi = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    half = dim // 2
    f_extra = rope_freqs(dim, theta)
    f_inter = f_extra / factor
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    return f_inter * ramp + f_extra * (1.0 - ramp)


def rope_setup(cfg, dim: int, theta: float):
    """(inverse frequencies, cos/sin scale, softmax-scale factor) of a
    ``dim``-wide rotary under ``cfg``'s scaling: plain rope, or YaRN when
    ``cfg.rope_scaling_factor`` exceeds 1."""
    f = cfg.rope_scaling_factor
    if f <= 1.0:
        return rope_freqs(dim, theta), 1.0, 1.0
    inv = yarn_freqs(dim, theta, f, cfg.rope_original_max_pos,
                     cfg.rope_beta_fast, cfg.rope_beta_slow)
    m_all = yarn_mscale(f, cfg.rope_mscale_all_dim)
    return inv, yarn_mscale(f, cfg.rope_mscale) / m_all, m_all * m_all


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq=None, mscale: float = 1.0) -> jax.Array:
    """Rotary embedding, "split halves" convention (Llama/NeoX).

    x: (B, S, H, hd); positions: (1, S) or (B, S) int32. ``inv_freq``
    (hd/2,) replaces the plain frequencies of ``theta`` and ``mscale``
    scales cos and sin (YaRN, :func:`rope_setup`).
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta) if inv_freq is None else inv_freq
    ang = positions[..., None].astype(jnp.float32) * inv  # (B?, S, hd/2)
    ang = ang[:, :, None, :]  # broadcast over heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos_emb(seq: int, d: int, dtype=jnp.float32) -> jax.Array:
    """Classic transformer sinusoidal table (whisper-style), (seq, d)."""
    pos = np.arange(seq)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / (10_000.0 ** (2 * dim / d))
    tab = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(tab, dtype)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16) -> jax.Array:
    """Truncated-normal fan-in init (stddev = 1/sqrt(fan_in))."""
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> jax.Array:
    std = 1.0 / np.sqrt(d)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d), jnp.float32)).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def count_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
