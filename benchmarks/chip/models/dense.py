"""Dense decoder with grouped-query attention: the layers of StarCoder2-7B
and Phi-3-medium as the offload trainer runs them.

The layer equations (each departure from the published models is listed
in the configuration files under ``departures``):

    h  = rmsnorm(x) * (1 + norm1)                  f32 statistics
    q, k, v = h Wq, h Wk, h Wv                     grouped-query heads
    q, k = rope(q), rope(k)                        split-halves rotary
    x  = x + causal_softmax(q k^T / sqrt(hd)) v Wo
    h  = rmsnorm(x) * (1 + norm2)
    x  = x + gelu_tanh(h W_in) W_out               or SwiGLU

Initial weights follow the program's initialiser: truncated normal,
fan-in scale, rounded to bfloat16, zero norm scales. Everything here but
``arch_config`` is plain JAX and imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

import flops
from reference import HIGHEST, _mm, _rms, _rope, _round, _trunc, head_params


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int              # rows of the embedding table as run
    layers: int
    theta: float
    eps: float
    act: str                # "gelu_tanh" | "swiglu"

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["table_rows"],
                   layers=c["num_hidden_layers"], theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), act=c["mlp"])


def layer_leaves(a: Arch, l: int) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name, shape) of one layer's tensors, in the order the program
    lays them out in its flat per-layer vector (sorted keys). Every
    layer is alike."""
    qd, kd = a.heads * a.head_dim, a.kv_heads * a.head_dim
    mlp = ([("mlp/w_in", (a.d, a.d_ff)), ("mlp/w_out", (a.d_ff, a.d))]
           if a.act == "gelu_tanh" else
           [("mlp/w_down", (a.d_ff, a.d)), ("mlp/w_gate", (a.d, a.d_ff)),
            ("mlp/w_up", (a.d, a.d_ff))])
    return ([("attn/wk", (a.d, kd)), ("attn/wo", (qd, a.d)),
             ("attn/wq", (a.d, qd)), ("attn/wv", (a.d, kd))]
            + mlp + [("norm1", (a.d,)), ("norm2", (a.d,))])


def layer_kind(a: Arch, l: int) -> str:
    return "block"


def init_params(a: Arch, key) -> Dict[object, Dict[str, jax.Array]]:
    """Initial weights, as float32 arrays holding bfloat16 values, keyed
    by group (layer index, or "head") and leaf name. Drawn op by op, the
    way the program's initialiser draws them."""
    keys = jax.random.split(key, a.layers + 1)
    out: Dict[object, Dict[str, jax.Array]] = {}
    for l in range(a.layers):
        ks = jax.random.split(keys[l], 4)
        kq = jax.random.split(ks[0], 4)
        qd, kd = a.heads * a.head_dim, a.kv_heads * a.head_dim
        p = {"attn/wq": _trunc(kq[0], (a.d, qd), a.d),
             "attn/wk": _trunc(kq[1], (a.d, kd), a.d),
             "attn/wv": _trunc(kq[2], (a.d, kd), a.d),
             "attn/wo": _trunc(kq[3], (qd, a.d), qd)}
        if a.act == "swiglu":
            k1, k2, k3 = jax.random.split(ks[3], 3)
            p["mlp/w_gate"] = _trunc(k1, (a.d, a.d_ff), a.d)
            p["mlp/w_up"] = _trunc(k2, (a.d, a.d_ff), a.d)
            p["mlp/w_down"] = _trunc(k3, (a.d_ff, a.d), a.d_ff)
        else:
            k1, k2 = jax.random.split(ks[3], 2)
            p["mlp/w_in"] = _trunc(k1, (a.d, a.d_ff), a.d)
            p["mlp/w_out"] = _trunc(k2, (a.d_ff, a.d), a.d_ff)
        p["norm1"] = jnp.zeros((a.d,), jnp.float32)
        p["norm2"] = jnp.zeros((a.d,), jnp.float32)
        out[l] = {n: v.astype(jnp.float32) for n, v in p.items()}
    out["head"] = head_params(a, keys[a.layers])
    return out


def block(a: Arch, mode: str, p, x, kind, qk=None):
    """One decoder layer on x: (B, S, d) float32. ``qk(p, q, k)``, where
    given, maps the per-head queries and keys, (S, heads, head_dim) and
    (S, kv_heads, head_dim), before the rotary; a family that differs
    from this one there alone passes it rather than copying the layer."""
    def one(xb):
        s = xb.shape[0]
        h = _rms(xb, p["norm1"], a.eps)
        q = _mm(h, p["attn/wq"], mode).reshape(s, a.heads, a.head_dim)
        k = _mm(h, p["attn/wk"], mode).reshape(s, a.kv_heads, a.head_dim)
        v = _mm(h, p["attn/wv"], mode).reshape(s, a.kv_heads, a.head_dim)
        if qk is not None:
            q, k = qk(p, q, k)
        q, k = _rope(q, a.theta), _rope(k, a.theta)
        g = a.heads // a.kv_heads
        k = jnp.repeat(k, g, axis=1)          # q head h reads kv head h // g
        v = jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", _round(q, mode), _round(k, mode),
                        precision=HIGHEST) / math.sqrt(a.head_dim)
        causal = jnp.tril(jnp.ones((s, s), bool))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _round(pr, mode), _round(v, mode),
                       precision=HIGHEST).reshape(s, a.heads * a.head_dim)
        xb = xb + _mm(o, p["attn/wo"], mode)
        h = _rms(xb, p["norm2"], a.eps)
        if a.act == "swiglu":
            y = jax.nn.silu(_mm(h, p["mlp/w_gate"], mode)) \
                * _mm(h, p["mlp/w_up"], mode)
            return xb + _mm(y, p["mlp/w_down"], mode)
        y = jax.nn.gelu(_mm(h, p["mlp/w_in"], mode), approximate=True)
        return xb + _mm(y, p["mlp/w_out"], mode)
    return jax.vmap(one)(x)


# ---------------------------------------------------------------- FLOPs
def layer_matrix_params(c: dict) -> int:
    """Parameters of one layer's weight matrices (norm scales excluded)."""
    d, hd = c["hidden_size"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if c["mlp"] == "swiglu" else 2) * d * c["intermediate_size"]
    return attn + mlp


def flops_per_token(c: dict, seq_len: int) -> float:
    """Every layer's matrices and the unembedding; each layer's heads
    score with and weigh by ``head_dim``-wide vectors."""
    layers = c["num_hidden_layers"]
    return flops.per_token(
        layers * layer_matrix_params(c) + c["hidden_size"] * c["table_rows"],
        layers * c["num_attention_heads"] * 2 * c["head_dim"], seq_len)


# ---------------------------------------------------------------- program
def arch_config(c: dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig
    cfg = ArchConfig(
        name=c["name"], family="dense", source=c["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        act={"gelu_tanh": "gelu", "swiglu": "swiglu"}[c["mlp"]])
    if cfg.padded_vocab != c["table_rows"]:
        raise ValueError(f"{c['name']}: the program's table has "
                         f"{cfg.padded_vocab} rows, the file says "
                         f"{c['table_rows']}")
    return cfg
