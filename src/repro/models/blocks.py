"""Layer kinds, the periodic layer plan, and the generic block.

Heterogeneous stacks (Jamba's 1:7 mamba:attn, Gemma3's 5:1 local:global,
DeepSeek's leading dense layer) are decomposed into
``prefix + period x n + suffix`` so that the periodic part runs under a
single ``lax.scan`` with stacked parameters — keeping the lowered HLO
small for the 512-device dry-run while preserving exact layer order.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.common import init_rms_scale, rms_norm
from repro.models.mlp import mlp_apply, mlp_init


@dataclasses.dataclass(frozen=True)
class LayerKind:
    mixer: str                 # "attn" | "mla" | "mamba"
    moe: bool = False
    window: Optional[int] = None   # sliding window (None = global)
    causal: bool = True
    cross: bool = False        # enc-dec decoder cross-attention
    theta: float = 10_000.0


def layer_kind(cfg, i: int, *, decoder: bool = True) -> LayerKind:
    if not cfg.is_attn_layer(i):
        return LayerKind(mixer="mamba", moe=cfg.is_moe_layer(i))
    mixer = "mla" if cfg.use_mla else "attn"
    is_global = cfg.is_global_attn_layer(i)
    window = None if is_global else cfg.sliding_window
    theta = cfg.rope_theta if is_global else cfg.local_rope_theta
    return LayerKind(
        mixer=mixer,
        moe=cfg.is_moe_layer(i),
        window=window,
        causal=cfg.causal if decoder else False,
        cross=(cfg.family == "encdec" and decoder),
        theta=theta,
    )


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    prefix: Tuple[LayerKind, ...]
    period: Tuple[LayerKind, ...]
    n_periods: int
    suffix: Tuple[LayerKind, ...]

    @property
    def num_layers(self) -> int:
        return len(self.prefix) + len(self.period) * self.n_periods + len(self.suffix)

    def all_kinds(self) -> List[LayerKind]:
        return (list(self.prefix) + list(self.period) * self.n_periods
                + list(self.suffix))


def build_plan(cfg, *, decoder: bool = True,
               num_layers: Optional[int] = None) -> LayerPlan:
    L = num_layers if num_layers is not None else cfg.num_layers
    kinds = [layer_kind(cfg, i, decoder=decoder) for i in range(L)]
    best = None
    for pre in range(0, L + 1):
        for p in range(1, L - pre + 1):
            # kinds[pre:] must follow period p
            ok = all(kinds[pre + j] == kinds[pre + (j % p)] for j in range(L - pre))
            if not ok:
                continue
            n = (L - pre) // p
            suf = L - pre - n * p
            cost = pre + p + suf  # unrolled layers in the HLO
            if best is None or cost < best[0]:
                best = (cost, pre, p, n, suf)
    _, pre, p, n, suf = best
    if n <= 1:  # no point scanning a single period; unroll into prefix
        return LayerPlan(tuple(kinds), (), 0, ())
    return LayerPlan(tuple(kinds[:pre]), tuple(kinds[pre:pre + p]), n,
                     tuple(kinds[pre + n * p:]))


# ---------------------------------------------------------------------------
# Generic block
# ---------------------------------------------------------------------------

def block_init(key, cfg, kind: LayerKind, dtype=jnp.bfloat16) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": init_rms_scale(cfg.d_model)}
    if kind.mixer == "attn":
        p["attn"] = attn_lib.gqa_init(ks[0], cfg, dtype)
    elif kind.mixer == "mla":
        p["attn"] = attn_lib.mla_init(ks[0], cfg, dtype)
    else:
        p["mamba"] = mamba_lib.mamba_init(ks[0], cfg, dtype)
    if kind.cross:
        p["norm_cross"] = init_rms_scale(cfg.d_model)
        p["cross"] = attn_lib.gqa_init(ks[1], cfg, dtype)
    if cfg.family == "ssm":
        return p  # pure-mamba block: no separate FFN
    p["norm2"] = init_rms_scale(cfg.d_model)
    if kind.moe:
        p["moe"] = moe_lib.moe_init(ks[2], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def block_cache_shape(cfg, kind: LayerKind, batch: int, seq_len: int,
                      dtype=jnp.bfloat16):
    """Decode-cache structure for one block (None-free, scan-stackable)."""
    cache: Dict[str, Any] = {}
    if kind.mixer == "attn":
        cache["kv"] = attn_lib.gqa_cache_shape(cfg, batch, seq_len, kind.window, dtype)
    elif kind.mixer == "mla":
        cache["kv"] = attn_lib.mla_cache_shape(cfg, batch, seq_len, dtype)
    else:
        cache["ssm"] = mamba_lib.mamba_state_shape(cfg, batch, dtype)
    if kind.cross:
        cache["cross_kv"] = attn_lib.gqa_cache_shape(cfg, batch, cfg.encoder_seq,
                                                     None, dtype)
    return cache


def _cross_attend(params, x, cache_kv: attn_lib.KVCache, cfg):
    """Decoder cross-attention against cached encoder K/V."""
    B, S, d = x.shape
    Hq, Hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(B, S, Hq, hd).transpose(0, 2, 1, 3)
    o = attn_lib.flash_attention(q, cache_kv.k, cache_kv.v, causal=False)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, Hq * hd)
    return o @ params["wo"]


def _build_cross_kv(params, enc_out, cfg) -> attn_lib.KVCache:
    B, F, d = enc_out.shape
    Hk, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc_out @ params["wk"]).reshape(B, F, Hk, hd).transpose(0, 2, 1, 3)
    v = (enc_out @ params["wv"]).reshape(B, F, Hk, hd).transpose(0, 2, 1, 3)
    return attn_lib.KVCache(k=k, v=v, slot_pos=jnp.arange(F, dtype=jnp.int32))


def block_apply(params, x, cfg, kind: LayerKind, *, mode: str = "train",
                cache=None, pos=None, enc_out=None, scan_impl: str = "jnp",
                moe_counts: bool = False):
    """Pre-norm residual block. Returns (x, new_cache, aux_loss); with
    ``moe_counts`` an MoE block returns (x, new_cache, (aux_loss, tokens
    routed to each held expert))."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind.mixer == "attn":
        kv = cache.get("kv") if cache else None
        y, nkv = attn_lib.gqa_apply(params["attn"], h, cfg=cfg, window=kind.window,
                                    theta=kind.theta, cache=kv, pos=pos, mode=mode,
                                    causal=kind.causal)
        if nkv is not None:
            new_cache["kv"] = nkv
    elif kind.mixer == "mla":
        kv = cache.get("kv") if cache else None
        y, nkv = attn_lib.mla_apply(params["attn"], h, cfg=cfg, theta=kind.theta,
                                    cache=kv, pos=pos, mode=mode)
        if nkv is not None:
            new_cache["kv"] = nkv
    else:
        ssm = cache.get("ssm") if cache else None
        y, nssm = mamba_lib.mamba_apply(params["mamba"], h, cfg, state=ssm,
                                        mode=mode, scan_impl=scan_impl)
        if nssm is not None:
            new_cache["ssm"] = nssm
    x = x + y

    if kind.cross:
        hc = rms_norm(x, params["norm_cross"], cfg.norm_eps)
        if mode == "decode":
            ckv = cache["cross_kv"]
        else:
            ckv = _build_cross_kv(params["cross"], enc_out, cfg)
        x = x + _cross_attend(params["cross"], hc, ckv, cfg)
        if mode in ("prefill", "decode"):
            new_cache["cross_kv"] = ckv

    if cfg.family != "ssm":
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        if kind.moe:
            from repro.models import moe_ep
            if moe_counts:      # one device: the held experts' share
                y2, a, counts = moe_lib.moe_apply(params["moe"], h2, cfg,
                                                  with_counts=True)
                aux = (a, counts)
            else:
                y2, aux = moe_ep.moe_dispatch(params["moe"], h2, cfg)
        else:
            y2 = mlp_apply(params["mlp"], h2, cfg.act)
        x = x + y2

    if mode == "train":
        return x, None, aux
    return x, new_cache, aux
