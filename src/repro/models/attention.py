"""Attention variants: GQA (+qk-norm, RoPE), sliding-window, MLA.

Memory discipline: the (Sq x Skv) score matrix is never materialised for
long sequences. ``flash_attention`` is a chunked online-softmax with a
custom VJP (backward recomputes scores chunk-wise), so it is safe to use
under per-layer remat for train_4k and for 32k prefill. Sliding-window
layers use an exact banded implementation with linear FLOPs.

This module is also the pure-jnp oracle for ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (apply_rope, dense_init, init_rms_scale,
                                 rms_norm, rope_setup)

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Chunked flash attention (pure jnp, custom VJP)
# ---------------------------------------------------------------------------

def _mask(q_pos, kv_pos, causal: bool, window: Optional[int]):
    """(q, k) -> bool allowed. q_pos: (..., Sq), kv_pos: (..., Skv)."""
    m = jnp.ones((q_pos.shape[-1], kv_pos.shape[-1]), bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= (q_pos[:, None] - kv_pos[None, :]) < window
    return m


def _choose_chunk(s: int, target: int) -> int:
    if s <= target:
        return s
    c = target
    while s % c != 0:
        c //= 2
    return max(c, 1)


def _flash_fwd_impl(q, k, v, q0: int, causal: bool, window: Optional[int],
                    q_chunk: int, kv_chunk: int, scale: float):
    """Returns (out, lse). q: (B,Hk,G,Sq,hd); k,v: (B,Hk,Skv,hd)."""
    B, Hk, G, Sq, hd = q.shape
    Skv = k.shape[2]
    hv = v.shape[-1]
    nq = Sq // q_chunk
    nk = Skv // kv_chunk

    def per_q(args):
        qi, qc = args  # qc: (B,Hk,G,qc,hd)
        q_pos = q0 + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kj):
            acc, m, l = carry
            kc = jax.lax.dynamic_slice_in_dim(k, kj * kv_chunk, kv_chunk, 2)
            vc = jax.lax.dynamic_slice_in_dim(v, kj * kv_chunk, kv_chunk, 2)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            kv_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            msk = _mask(q_pos, kv_pos, causal, window)
            s = jnp.where(msk, s, _NEG_INF)
            m2 = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = corr * l + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p, vc.astype(jnp.float32))
            acc2 = acc * corr[..., None] + pv
            return (acc2, m2, l2), None

        acc0 = jnp.zeros((B, Hk, G, q_chunk, hv), jnp.float32)
        m0 = jnp.full((B, Hk, G, q_chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, G, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), jnp.arange(nk))
        l = jnp.maximum(l, 1e-30)
        out = acc / l[..., None]
        lse = m + jnp.log(l)
        return out, lse

    qs = jnp.moveaxis(q.reshape(B, Hk, G, nq, q_chunk, hd), 3, 0)
    outs, lses = jax.lax.map(per_q, (jnp.arange(nq), qs))
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Hk, G, Sq, hv)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, Hk, G, Sq)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, q0, causal, window, q_chunk, kv_chunk, scale):
    out, _ = _flash_fwd_impl(q, k, v, q0, causal, window, q_chunk, kv_chunk, scale)
    return out


def _flash_vjp_fwd(q, k, v, q0, causal, window, q_chunk, kv_chunk, scale):
    out, lse = _flash_fwd_impl(q, k, v, q0, causal, window, q_chunk, kv_chunk, scale)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(q0, causal, window, q_chunk, kv_chunk, scale, res, dout):
    q, k, v, out, lse = res
    B, Hk, G, Sq, hd = q.shape
    Skv = k.shape[2]
    nq = Sq // q_chunk
    nk = Skv // kv_chunk
    do32 = dout.astype(jnp.float32)
    # D_i = rowsum(dO * O)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # (B,Hk,G,Sq)

    def per_kv(carry, kj):
        dq_acc = carry  # (B,Hk,G,Sq,hd) f32
        kc = jax.lax.dynamic_slice_in_dim(k, kj * kv_chunk, kv_chunk, 2).astype(jnp.float32)
        vc = jax.lax.dynamic_slice_in_dim(v, kj * kv_chunk, kv_chunk, 2).astype(jnp.float32)
        kv_pos = kj * kv_chunk + jnp.arange(kv_chunk)

        def per_q(qcarry, qi):
            dq_acc, dk_acc, dv_acc = qcarry
            qc = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, 3).astype(jnp.float32)
            doc = jax.lax.dynamic_slice_in_dim(do32, qi * q_chunk, q_chunk, 3)
            lsec = jax.lax.dynamic_slice_in_dim(lse, qi * q_chunk, q_chunk, 3)
            dc = jax.lax.dynamic_slice_in_dim(delta, qi * q_chunk, q_chunk, 3)
            q_pos = qi * q_chunk + jnp.arange(q_chunk)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qc, kc) * scale
            msk = _mask(q_pos, kv_pos, causal, window)
            s = jnp.where(msk, s, _NEG_INF)
            p = jnp.exp(s - lsec[..., None])
            dv_acc = dv_acc + jnp.einsum("bhgqk,bhgqd->bhkd", p, doc)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", doc, vc)
            ds = p * (dp - dc[..., None]) * scale
            dq_c = jnp.einsum("bhgqk,bhkd->bhgqd", ds, kc)
            dq_acc = jax.lax.dynamic_update_slice_in_dim(
                dq_acc,
                jax.lax.dynamic_slice_in_dim(dq_acc, qi * q_chunk, q_chunk, 3) + dq_c,
                qi * q_chunk, 3)
            dk_acc = dk_acc + jnp.einsum("bhgqk,bhgqd->bhkd", ds, qc)
            return (dq_acc, dk_acc, dv_acc), None

        dk0 = jnp.zeros((B, Hk, kv_chunk, hd), jnp.float32)
        dv0 = jnp.zeros((B, Hk, kv_chunk, v.shape[-1]), jnp.float32)
        (dq_acc, dk_c, dv_c), _ = jax.lax.scan(per_q, (dq_acc, dk0, dv0), jnp.arange(nq))
        return dq_acc, (dk_c, dv_c)

    # q0 is static 0 in training (only decode uses q0>0, and decode has no vjp)
    dq0 = jnp.zeros((B, Hk, G, Sq, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(per_kv, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, Hk, Skv, hd)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, Hk, Skv, v.shape[-1])
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    q0: int = 0, q_chunk: int = 512, kv_chunk: int = 1024,
                    scale: Optional[float] = None):
    """q: (B,Hq,Sq,hd); k,v: (B,Hk,Skv,hd[v]). Returns (B,Hq,Sq,hdv).

    GQA is handled by grouping Hq into Hk groups (no K/V repeat).
    """
    B, Hq, Sq, hd = q.shape
    Hk = k.shape[1]
    assert Hq % Hk == 0, (Hq, Hk)
    G = Hq // Hk
    qc = _choose_chunk(Sq, q_chunk)
    kc = _choose_chunk(k.shape[2], kv_chunk)
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Hk, G, Sq, hd)
    out = _flash(qg, k, v, q0, causal, window, qc, kc, sc)
    return out.reshape(B, Hq, Sq, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Banded sliding-window attention (exact, linear FLOPs)
# ---------------------------------------------------------------------------

def banded_attention(q, k, v, *, window: int, scale: Optional[float] = None):
    """Causal sliding-window attention with block-banded compute.

    Requires Sq == Skv and Sq % window == 0 (callers fall back to
    flash_attention otherwise). Each query block of size w attends to
    [previous block, own block] with an exact mask.
    """
    B, Hq, S, hd = q.shape
    Hk = k.shape[1]
    G = Hq // Hk
    w = window
    nb = S // w
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)

    qb = q.reshape(B, Hk, G, nb, w, hd).astype(jnp.float32)
    kb = k.reshape(B, Hk, nb, w, hd).astype(jnp.float32)
    vb = v.reshape(B, Hk, nb, w, v.shape[-1]).astype(jnp.float32)
    # previous block of k/v (block -1 is zeros, masked out)
    kprev = jnp.pad(kb[:, :, :-1], ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
    vprev = jnp.pad(vb[:, :, :-1], ((0, 0), (0, 0), (1, 0), (0, 0), (0, 0)))
    kctx = jnp.concatenate([kprev, kb], axis=3)   # (B,Hk,nb,2w,hd)
    vctx = jnp.concatenate([vprev, vb], axis=3)

    s = jnp.einsum("bhgnqd,bhnkd->bhgnqk", qb, kctx) * sc  # (B,Hk,G,nb,w,2w)
    qpos = jnp.arange(w)[:, None]
    kpos = jnp.arange(2 * w)[None, :] - w
    allowed = (kpos <= qpos) & (qpos - kpos < w)  # within-band mask
    first = jnp.arange(nb) == 0                   # block 0 has no prev block
    no_prev = jnp.concatenate([jnp.zeros((w,), bool), jnp.ones((w,), bool)])
    msk = allowed[None] | jnp.zeros((nb, 1, 1), bool)
    msk = msk & (no_prev[None, None, :] | ~first[:, None, None])
    s = jnp.where(msk[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgnqk,bhnkd->bhgnqd", p, vctx)
    return o.reshape(B, Hq, S, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention (single query position)
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, *, kv_pos, pos, window: Optional[int] = None,
                     scale: Optional[float] = None):
    """q: (B,Hq,1,hd); k,v: (B,Hk,S,hd); kv_pos: (S,) int32 slot positions
    (-big for empty). pos: scalar current position. Returns (B,Hq,1,hdv)."""
    B, Hq, _, hd = q.shape
    Hk = k.shape[1]
    G = Hq // Hk
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Hk, G, hd).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, k.astype(jnp.float32)) * sc
    ok = kv_pos <= pos
    if window is not None:
        ok &= (pos - kv_pos) < window
    s = jnp.where(ok[None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, 1, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (qk-norm, RoPE, sliding window, KV/ring cache)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array        # (B, Hk, S_cache, hd)
    v: jax.Array        # (B, Hk, S_cache, hd)
    slot_pos: jax.Array  # (S_cache,) int32; -2**30 for empty slots


def gqa_init(key, cfg, dtype=jnp.bfloat16):
    d, Hq, Hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, Hq * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, Hk * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, Hk * hd), dtype=dtype),
        "wo": dense_init(ks[3], (Hq * hd, d), dtype=dtype),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = init_rms_scale(hd)
        p["k_norm"] = init_rms_scale(hd)
    return p


def gqa_apply(params, x, *, cfg, window: Optional[int], theta: float,
              cache: Optional[KVCache] = None, pos=None,
              mode: str = "train", causal: bool = True
              ) -> Tuple[jax.Array, Optional[KVCache]]:
    """x: (B,S,d). mode: train | prefill | decode.

    decode: x is (B,1,d), ``pos`` is the scalar position, cache is updated.
    prefill: returns a filled cache (cache arg provides the allocated bufs).
    """
    B, S, d = x.shape
    Hq, Hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, Hq, hd)
    k = (x @ params["wk"]).reshape(B, S, Hk, hd)
    v = (x @ params["wv"]).reshape(B, S, Hk, hd)
    if cfg.use_qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if mode == "decode":
        positions = jnp.full((1,), pos, jnp.int32)
    else:
        positions = jnp.arange(S, dtype=jnp.int32)
    q = apply_rope(q, positions[None, :], theta)
    k = apply_rope(k, positions[None, :], theta)
    q = q.transpose(0, 2, 1, 3)  # (B,Hq,S,hd)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    new_cache = None
    if mode == "decode":
        assert cache is not None
        s_cache = cache.k.shape[2]
        slot = pos % s_cache if window is not None else pos
        ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), slot, 2)
        cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), slot, 2)
        spos = jax.lax.dynamic_update_slice_in_dim(
            cache.slot_pos, jnp.full((1,), pos, jnp.int32), slot, 0)
        new_cache = KVCache(ck, cv, spos)
        o = decode_attention(q, ck, cv, kv_pos=spos, pos=pos, window=window)
    else:
        if causal and window is not None and S % window == 0 and S >= window:
            o = banded_attention(q, k, v, window=window)
        else:
            o = flash_attention(q, k, v, causal=causal, window=window)
        if mode == "prefill":
            assert cache is not None
            s_cache = cache.k.shape[2]
            if window is not None:
                # keep only the trailing `window` positions in the ring
                keep = min(window, S)
                tail_k = k[:, :, S - keep:, :]
                tail_v = v[:, :, S - keep:, :]
                tail_pos = jnp.arange(S - keep, S, dtype=jnp.int32)
                start = (S - keep) % s_cache
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache.k, tail_k.astype(cache.k.dtype), start, 2)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache.v, tail_v.astype(cache.v.dtype), start, 2)
                spos = jax.lax.dynamic_update_slice_in_dim(
                    cache.slot_pos, tail_pos, start, 0)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache.k, k.astype(cache.k.dtype), 0, 2)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache.v, v.astype(cache.v.dtype), 0, 2)
                spos = jax.lax.dynamic_update_slice_in_dim(
                    cache.slot_pos, jnp.arange(S, dtype=jnp.int32), 0, 0)
            new_cache = KVCache(ck, cv, spos)

    o = o.transpose(0, 2, 1, 3).reshape(B, S, Hq * hd)
    return (o @ params["wo"]).astype(x.dtype), new_cache


def gqa_cache_shape(cfg, batch: int, seq_len: int, window: Optional[int],
                    dtype=jnp.bfloat16) -> KVCache:
    """Allocate (or eval_shape) a KV cache. Sliding-window layers use a
    ring buffer of length `window` — the paper's memory frugality carried
    into serving."""
    s = min(window, seq_len) if window is not None else seq_len
    Hk, hd = cfg.num_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((batch, Hk, s, hd), dtype),
        v=jnp.zeros((batch, Hk, s, hd), dtype),
        slot_pos=jnp.full((s,), -(2 ** 30), jnp.int32),
    )


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    latent: jax.Array   # (B, S, kv_lora)
    k_rope: jax.Array   # (B, S, rope_dim)
    slot_pos: jax.Array  # (S,)


def mla_init(key, cfg, dtype=jnp.bfloat16):
    d, H = cfg.d_model, cfg.num_heads
    nope, rope, vhd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    return {
        "wq": dense_init(ks[0], (d, H * (nope + rope)), dtype=dtype),
        "w_dkv": dense_init(ks[1], (d, r + rope), dtype=dtype),
        "kv_norm": init_rms_scale(r),
        "w_uk": dense_init(ks[2], (r, H * nope), dtype=dtype),
        "w_uv": dense_init(ks[3], (r, H * vhd), dtype=dtype),
        "wo": dense_init(ks[4], (H * vhd, d), dtype=dtype),
    }


def mla_apply(params, x, *, cfg, theta: float, cache: Optional[MLACache] = None,
              pos=None, mode: str = "train") -> Tuple[jax.Array, Optional[MLACache]]:
    B, S, d = x.shape
    H = cfg.num_heads
    nope, rope, vhd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    # YaRN (when configured) sets the rope frequencies, scales cos/sin,
    # and multiplies the softmax scale by mscale_all_dim's m^2
    inv_freq, rot_scale, temp = rope_setup(cfg, rope, theta)
    scale = temp / math.sqrt(nope + rope)

    q = (x @ params["wq"]).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    dkv = x @ params["w_dkv"]  # (B,S,r+rope)
    latent = rms_norm(dkv[..., :r], params["kv_norm"], cfg.norm_eps)
    k_rope = dkv[..., r:]

    if mode == "decode":
        positions = jnp.full((1,), pos, jnp.int32)
    else:
        positions = jnp.arange(S, dtype=jnp.int32)
    q_rope = apply_rope(q_rope, positions[None, :], theta, inv_freq,
                        rot_scale)
    k_rope = apply_rope(k_rope[:, :, None, :], positions[None, :], theta,
                        inv_freq, rot_scale)[:, :, 0, :]

    new_cache = None
    if mode == "decode":
        assert cache is not None
        cl = jax.lax.dynamic_update_slice_in_dim(
            cache.latent, latent.astype(cache.latent.dtype), pos, 1)
        cr = jax.lax.dynamic_update_slice_in_dim(
            cache.k_rope, k_rope.astype(cache.k_rope.dtype), pos, 1)
        spos = jax.lax.dynamic_update_slice_in_dim(
            cache.slot_pos, jnp.full((1,), pos, jnp.int32), pos, 0)
        new_cache = MLACache(cl, cr, spos)
        # absorbed decode: queries projected into latent space
        w_uk = params["w_uk"].reshape(r, H, nope)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))  # (B,1,H,r)
        s_lat = jnp.einsum("bshr,btr->bhst", q_lat, cl.astype(jnp.float32))
        s_rope = jnp.einsum("bshn,btn->bhst", q_rope.astype(jnp.float32),
                            cr.astype(jnp.float32))
        s = (s_lat + s_rope) * scale  # (B,H,1,T)
        ok = spos <= pos
        s = jnp.where(ok[None, None, None, :], s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", p, cl.astype(jnp.float32))
        w_uv = params["w_uv"].reshape(r, H, vhd)
        o = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv.astype(jnp.float32))
    else:
        k_nope = (latent @ params["w_uk"]).reshape(B, S, H, nope)
        vfull = (latent @ params["w_uv"]).reshape(B, S, H, vhd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rope))], -1)
        qfull = jnp.concatenate([q_nope, q_rope], -1)
        o = flash_attention(qfull.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            vfull.transpose(0, 2, 1, 3), causal=cfg.causal,
                            scale=scale).transpose(0, 2, 1, 3)
        if mode == "prefill":
            assert cache is not None
            cl = jax.lax.dynamic_update_slice_in_dim(
                cache.latent, latent.astype(cache.latent.dtype), 0, 1)
            cr = jax.lax.dynamic_update_slice_in_dim(
                cache.k_rope, k_rope.astype(cache.k_rope.dtype), 0, 1)
            spos = jax.lax.dynamic_update_slice_in_dim(
                cache.slot_pos, jnp.arange(S, dtype=jnp.int32), 0, 0)
            new_cache = MLACache(cl, cr, spos)

    o = o.reshape(B, S, H * vhd).astype(x.dtype)
    return o @ params["wo"], new_cache


def mla_cache_shape(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        latent=jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, seq_len, cfg.qk_rope_head_dim), dtype),
        slot_pos=jnp.full((seq_len,), -(2 ** 30), jnp.int32),
    )
