"""DeepSeek-V2: multi-head latent attention with a YaRN rotary, a leading
dense SwiGLU layer, then layers of routed and shared SwiGLU experts. The
layers of DeepSeek-V2-Lite as the offload trainer runs one chip's share
of them.

The layer equations, for x: (S, d) (each departure from the published
model is listed in the configuration file under ``departures``):

    h      = rmsnorm(x) * (1 + norm1)
    q      = h Wq -> (heads, nope + rope); q_n, q_r = its two parts
    c, k_r = h W_dkv -> (kv_lora, rope); c = rmsnorm(c) * (1 + kv_norm)
    k_n, v = c W_uk, c W_uv -> (heads, nope), (heads, v_head)
    q_r, k_r rotated by YaRN's frequencies (k_r shared by every head)
    x      = x + causal_softmax((q_n k_n + q_r k_r) * sigma) v Wo
    h      = rmsnorm(x) * (1 + norm2)
    dense: x = x + swiglu(h)                         (intermediate_size)
    MoE:   p = softmax(h W_router) over all routed experts, float32;
           the top k by value, gates not renormalised;
           x = x + sum over held e in the top k of p_e swiglu_e(h)
                 + swiglu_shared(h)     (shared experts as one FFN)

YaRN (arXiv:2309.00071, as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``):
f_extra[i] = theta^(-2i/rope), f_inter = f_extra / factor; with
dim(r) = rope ln(orig / (2 pi r)) / (2 ln theta), lo = floor(dim(beta_fast)),
hi = ceil(dim(beta_slow)), ramp[i] = clip((i - lo) / (hi - lo), 0, 1) and
inv_freq = f_inter ramp + f_extra (1 - ramp); cos and sin scale by
m(factor, mscale) / m(factor, mscale_all_dim) and sigma is
rope-plus-nope^-1/2 m(factor, mscale_all_dim)^2, m(s, a) = 0.1 a ln s + 1.

The held experts' part is a plain loop over the held experts, each applied
to every token and weighted by its gate (zero where not routed); the
program computes the same sum with grouped matmuls over the routed rows
alone. Initial weights follow the program's initialiser: truncated
normal, fan-in scale, rounded to bfloat16, zero norm scales. Everything
here but ``arch_config`` is plain JAX and imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import flops
from reference import HIGHEST, _mm, _rms, _round, _trunc, head_params


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""
    d: int
    heads: int
    nope: int               # query-key head size without the rotary part
    rope: int               # rotary part, its key shared by every head
    v_head: int
    kv_lora: int
    d_ff: int               # the dense layers' SwiGLU width
    moe_ff: int             # one expert's SwiGLU width
    routed: int             # experts the router scores (its outputs)
    held: int               # experts whose weights a layer holds
    offset: int             # global id of the first held expert
    top_k: int
    shared: int             # shared experts, run as one FFN of shared x moe_ff
    first_dense: int
    vocab: int              # rows of the embedding table as run
    layers: int
    theta: float
    eps: float
    yarn: Tuple[float, int, float, float, float, float]
    # (factor, original positions, beta_fast, beta_slow, mscale,
    #  mscale_all_dim)

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        y = c["rope_scaling"]
        return cls(
            d=c["hidden_size"], heads=c["num_attention_heads"],
            nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
            v_head=c["v_head_dim"], kv_lora=c["kv_lora_rank"],
            d_ff=c["intermediate_size"], moe_ff=c["moe_intermediate_size"],
            routed=c["router_experts"], held=c["n_routed_experts"],
            offset=c["held_expert_offset"], top_k=c["num_experts_per_tok"],
            shared=c["n_shared_experts"],
            first_dense=c["first_k_dense_replace"], vocab=c["table_rows"],
            layers=c["num_hidden_layers"], theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"]),
                  float(y["mscale"]), float(y["mscale_all_dim"])))


def layer_kind(a: Arch, l: int) -> str:
    return "dense" if l < a.first_dense else "moe"


def _attn_leaves(a: Arch) -> List[Tuple[str, Tuple[int, ...]]]:
    qk = a.nope + a.rope
    return [("attn/kv_norm", (a.kv_lora,)),
            ("attn/w_dkv", (a.d, a.kv_lora + a.rope)),
            ("attn/w_uk", (a.kv_lora, a.heads * a.nope)),
            ("attn/w_uv", (a.kv_lora, a.heads * a.v_head)),
            ("attn/wo", (a.heads * a.v_head, a.d)),
            ("attn/wq", (a.d, a.heads * qk))]


def layer_leaves(a: Arch, l: int) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name, shape) of layer ``l``'s tensors, in the order the program
    lays them out in its flat per-layer vector (sorted keys)."""
    if layer_kind(a, l) == "dense":
        ffn = [("mlp/w_down", (a.d_ff, a.d)), ("mlp/w_gate", (a.d, a.d_ff)),
               ("mlp/w_up", (a.d, a.d_ff))]
    else:
        fs = a.shared * a.moe_ff
        ffn = [("moe/router", (a.d, a.routed)),
               ("moe/shared/w_down", (fs, a.d)),
               ("moe/shared/w_gate", (a.d, fs)),
               ("moe/shared/w_up", (a.d, fs)),
               ("moe/w_down", (a.held, a.moe_ff, a.d)),
               ("moe/w_gate", (a.held, a.d, a.moe_ff)),
               ("moe/w_up", (a.held, a.d, a.moe_ff))]
    return _attn_leaves(a) + ffn + [("norm1", (a.d,)), ("norm2", (a.d,))]


def init_params(a: Arch, key) -> Dict[object, Dict[str, jax.Array]]:
    """Initial weights, as float32 arrays holding bfloat16 values, keyed
    by group (layer index, or "head") and leaf name. Drawn op by op, the
    way the program's initialiser draws them."""
    keys = jax.random.split(key, a.layers + 1)
    out: Dict[object, Dict[str, jax.Array]] = {}
    qk = a.nope + a.rope
    for l in range(a.layers):
        ks = jax.random.split(keys[l], 4)
        ka = jax.random.split(ks[0], 5)
        p = {"attn/wq": _trunc(ka[0], (a.d, a.heads * qk), a.d),
             "attn/w_dkv": _trunc(ka[1], (a.d, a.kv_lora + a.rope), a.d),
             "attn/kv_norm": jnp.zeros((a.kv_lora,), jnp.float32),
             "attn/w_uk": _trunc(ka[2], (a.kv_lora, a.heads * a.nope),
                                 a.kv_lora),
             "attn/w_uv": _trunc(ka[3], (a.kv_lora, a.heads * a.v_head),
                                 a.kv_lora),
             "attn/wo": _trunc(ka[4], (a.heads * a.v_head, a.d),
                               a.heads * a.v_head)}
        if layer_kind(a, l) == "dense":
            k1, k2, k3 = jax.random.split(ks[3], 3)
            p["mlp/w_gate"] = _trunc(k1, (a.d, a.d_ff), a.d)
            p["mlp/w_up"] = _trunc(k2, (a.d, a.d_ff), a.d)
            p["mlp/w_down"] = _trunc(k3, (a.d_ff, a.d), a.d_ff)
        else:
            km = jax.random.split(ks[2], 5)
            f, fs = a.moe_ff, a.shared * a.moe_ff
            p["moe/router"] = _trunc(km[0], (a.d, a.routed), a.d)
            p["moe/w_gate"] = _trunc(km[1], (a.held, a.d, f), a.d)
            p["moe/w_up"] = _trunc(km[2], (a.held, a.d, f), a.d)
            p["moe/w_down"] = _trunc(km[3], (a.held, f, a.d), f)
            k1, k2, k3 = jax.random.split(km[4], 3)
            p["moe/shared/w_gate"] = _trunc(k1, (a.d, fs), a.d)
            p["moe/shared/w_up"] = _trunc(k2, (a.d, fs), a.d)
            p["moe/shared/w_down"] = _trunc(k3, (fs, a.d), fs)
        p["norm1"] = jnp.zeros((a.d,), jnp.float32)
        p["norm2"] = jnp.zeros((a.d,), jnp.float32)
        out[l] = {n: v.astype(jnp.float32) for n, v in p.items()}
    out["head"] = head_params(a, keys[a.layers])
    return out


# ---------------------------------------------------------------- YaRN
def yarn_m(scale: float, mscale: float) -> float:
    """YaRN's m(s, a) = 0.1 a ln s + 1, and 1 where s <= 1."""
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(a: Arch) -> Tuple[np.ndarray, float, float]:
    """(inverse frequencies of the rope's pairs, cos/sin scale, softmax
    scale sigma), in float64 from the closed form."""
    factor, orig, fast, slow, ms, ms_all = a.yarn
    dim = a.rope

    def corr(rot):
        return dim * math.log(orig / (2 * math.pi * rot)) \
            / (2 * math.log(a.theta))
    lo = max(math.floor(corr(fast)), 0)
    hi = min(math.ceil(corr(slow)), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    f_extra = a.theta ** (-2.0 * i / dim)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    inv = f_extra / factor * ramp + f_extra * (1.0 - ramp)
    m_all = yarn_m(factor, ms_all)
    sigma = m_all * m_all / math.sqrt(a.nope + a.rope)
    return inv, yarn_m(factor, ms) / m_all, sigma


def _yarn_rope(x, inv, scale):
    """x: (S, H, rope), split-halves pairs (i, i + rope/2)."""
    s, _, r = x.shape
    half = r // 2
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------- layers
def _swiglu(h, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode), wd, mode)


def _experts(a: Arch, mode: str, p, h):
    """The held experts' routed part plus the shared experts, h: (S, d)."""
    logits = _mm(h, p["moe/router"], mode)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, a.top_k)
    y = _swiglu(h, p["moe/shared/w_gate"], p["moe/shared/w_up"],
                p["moe/shared/w_down"], mode)
    for e in range(a.held):
        g = jnp.sum(jnp.where(idx == a.offset + e, gates, 0.0), axis=-1)
        y = y + g[:, None] * _swiglu(h, p["moe/w_gate"][e], p["moe/w_up"][e],
                                     p["moe/w_down"][e], mode)
    return y


def block(a: Arch, mode: str, p, x, kind):
    """One layer on x: (B, S, d) float32."""
    inv, rot_scale, sigma = yarn(a)
    H, r = a.heads, a.kv_lora

    def one(xb):
        s = xb.shape[0]
        h = _rms(xb, p["norm1"], a.eps)
        q = _mm(h, p["attn/wq"], mode).reshape(s, H, a.nope + a.rope)
        q_n, q_r = q[..., :a.nope], q[..., a.nope:]
        dkv = _mm(h, p["attn/w_dkv"], mode)
        c = _rms(dkv[:, :r], p["attn/kv_norm"], a.eps)
        k_r = dkv[:, None, r:]
        k_n = _mm(c, p["attn/w_uk"], mode).reshape(s, H, a.nope)
        v = _mm(c, p["attn/w_uv"], mode).reshape(s, H, a.v_head)
        q_r = _yarn_rope(q_r, inv, rot_scale)
        k_r = _yarn_rope(k_r, inv, rot_scale)[:, 0, :]
        sc = (jnp.einsum("qhd,khd->hqk", _round(q_n, mode), _round(k_n, mode),
                         precision=HIGHEST)
              + jnp.einsum("qhd,kd->hqk", _round(q_r, mode),
                           _round(k_r, mode), precision=HIGHEST)) * sigma
        causal = jnp.tril(jnp.ones((s, s), bool))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _round(pr, mode), _round(v, mode),
                       precision=HIGHEST).reshape(s, H * a.v_head)
        xb = xb + _mm(o, p["attn/wo"], mode)
        h = _rms(xb, p["norm2"], a.eps)
        if kind == "dense":
            return xb + _swiglu(h, p["mlp/w_gate"], p["mlp/w_up"],
                                p["mlp/w_down"], mode)
        return xb + _experts(a, mode, p, h)
    return jax.vmap(one)(x)


# ---------------------------------------------------------------- FLOPs
def _attn_matrix_params(c: dict) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    r = c["kv_lora_rank"]
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + v)
            + H * v * d)


def moe_matrix_params_per_token(c: dict) -> float:
    """Matrix parameters of one MoE layer's FFN a token passes through:
    the router, the shared experts, and the held experts' share of its
    top k (top_k x held / routed experts a token, on average)."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    held = c["num_experts_per_tok"] * c["n_routed_experts"] \
        / c["router_experts"]
    return (d * c["router_experts"] + 3 * d * f * c["n_shared_experts"]
            + held * 3 * d * f)


def _attention(c: dict) -> int:
    """heads x (query-key head size + value head size), one layer."""
    return c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def flops_per_token(c: dict, seq_len: int) -> float:
    """Every layer's attention projections, the dense layers' MLP, each
    MoE layer's router, shared experts and routed share, and the
    unembedding; every layer scores with 192-wide query-key heads and
    weighs 128-wide values."""
    layers, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    matrix = (layers * _attn_matrix_params(c)
              + dense * 3 * c["hidden_size"] * c["intermediate_size"]
              + (layers - dense) * moe_matrix_params_per_token(c)
              + c["hidden_size"] * c["table_rows"])
    return flops.per_token(matrix, layers * _attention(c), seq_len)


def moe_layer_flops_per_token(c: dict, seq_len: int) -> float:
    """The MoE layers' share of ``flops_per_token``: their attention and
    expert matrices and their attention scores, no head."""
    moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return flops.per_token(
        moe * (_attn_matrix_params(c) + moe_matrix_params_per_token(c)),
        moe * _attention(c), seq_len)


# ---------------------------------------------------------------- program
def arch_config(c: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    y = c["rope_scaling"]
    cfg = ArchConfig(
        name=c["name"], family="moe", source=c["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c["qk_nope_head_dim"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], use_mla=True,
        kv_lora_rank=c["kv_lora_rank"], q_lora_rank=0,
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        num_experts=c["router_experts"],
        num_shared_experts=c["n_shared_experts"],
        moe_top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        moe_norm_topk=bool(c["norm_topk_prob"]), moe_capacity_factor=0.0,
        moe_experts_held=c["n_routed_experts"],
        moe_expert_offset=c["held_expert_offset"],
        first_dense_layers=c["first_k_dense_replace"],
        rope_theta=float(c["rope_theta"]),
        rope_scaling_factor=float(y["factor"]),
        rope_original_max_pos=int(y["original_max_position_embeddings"]),
        rope_beta_fast=float(y["beta_fast"]),
        rope_beta_slow=float(y["beta_slow"]),
        rope_mscale=float(y["mscale"]),
        rope_mscale_all_dim=float(y["mscale_all_dim"]),
        norm_eps=float(c["rms_norm_eps"]), act="swiglu")
    if cfg.padded_vocab != c["table_rows"]:
        raise ValueError(f"{c['name']}: the program's table has "
                         f"{cfg.padded_vocab} rows, the file says "
                         f"{c['table_rows']}")
    return cfg
