"""Mixture-of-Experts layer: top-k routing, then either sort-based
capacity dispatch or dropless grouped matmuls.

Capacity dispatch is sort-based (Mixtral/MegaBlocks style) rather than
GShard one-hot: a (T, E, C) dispatch tensor at assigned scales (T=65k,
E=128, C=5k) would be ~4e13 elements. Sorting T*k assignments keeps
memory O(T*k + E*C*d) and the expert einsum FLOPs equal to *active*
FLOPs (top_k/E of the dense-all-experts cost), which matters for the
roofline: compiled HLO_FLOPs stay proportional to N_active.

Dropless dispatch (``cfg.moe_capacity_factor == 0``, DeepSeek-V2's
forward) sorts the assignments by expert and runs each held expert's
FFN as ``jax.lax.ragged_dot`` over exactly the rows routed to it: no
token is dropped and no expert is padded to a capacity.

A layer may hold only some experts' weights (``cfg.moe_experts_held``
from ``cfg.moe_expert_offset``): it still routes over all
``cfg.num_experts`` and computes the held experts' part plus the shared
experts. Summing the routed parts of layers that hold disjoint shares,
plus the shared part once, gives the uncut layer — the single-chip
share of an expert-parallel deployment.

Experts are sharded over the "model" mesh axis (expert parallelism); the
scatter/gather across the token<->expert resharding is where the
all-to-all shows up in the dry-run collective parse.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import dense_init
from repro.models.mlp import mlp_apply, mlp_init


def moe_init(key, cfg, dtype=jnp.bfloat16):
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    Eh = cfg.held_experts
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], (d, E), dtype=jnp.float32)}
    if cfg.act == "swiglu":
        p["w_gate"] = dense_init(ks[1], (Eh, d, f), in_axis=1, dtype=dtype)
        p["w_up"] = dense_init(ks[2], (Eh, d, f), in_axis=1, dtype=dtype)
        p["w_down"] = dense_init(ks[3], (Eh, f, d), in_axis=1, dtype=dtype)
    else:
        p["w_in"] = dense_init(ks[1], (Eh, d, f), in_axis=1, dtype=dtype)
        p["w_out"] = dense_init(ks[2], (Eh, f, d), in_axis=1, dtype=dtype)
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.moe_d_ff * cfg.num_shared_experts,
                               cfg.act, dtype)
    return p


def _expert_ffn(params, xe, act: str):
    """xe: (E, C, d) -> (E, C, d)."""
    if act == "swiglu":
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["w_gate"]).astype(jnp.float32))
        u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"]).astype(jnp.float32)
        h = (g * u).astype(xe.dtype)
        return jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, params["w_in"]).astype(jnp.float32),
                    approximate=True).astype(xe.dtype)
    return jnp.einsum("ecf,efd->ecd", h, params["w_out"])


def _grouped_ffn(params, xs, sizes, act: str):
    """xs: (N, d) rows sorted by held expert, ``sizes[e]`` of them for
    expert e (rows past their sum belong to none and come out zero) ->
    (N, d) f32. Each expert's matmuls cover exactly its own rows.

    The TPU's grouped matmul leaves the rows past the groups unwritten,
    in its result and in its transpose's: every product's input and the
    result are masked to the grouped rows, so neither a value nor a
    cotangent reads them."""
    grouped = (jnp.arange(xs.shape[0]) < jnp.sum(sizes))[:, None]

    def gmm(a, w):
        return jax.lax.ragged_dot(jnp.where(grouped, a, 0), w, sizes,
                                  preferred_element_type=jnp.float32)
    if act == "swiglu":
        h = jax.nn.silu(gmm(xs, params["w_gate"])) * gmm(xs, params["w_up"])
        y = gmm(h.astype(xs.dtype), params["w_down"])
    else:
        h = jax.nn.gelu(gmm(xs, params["w_in"]), approximate=True)
        y = gmm(h.astype(xs.dtype), params["w_out"])
    return jnp.where(grouped, y, 0.0)


def route(params, xf, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router over all ``cfg.num_experts``: softmax of f32 logits, greedy
    top-k by value, gates renormalised only under ``cfg.moe_norm_topk``.
    xf: (T, d). Returns (probs (T,E), gates (T,k), expert ids (T,k))."""
    logits = xf.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, gate_idx


def _aux_loss(probs, gate_idx, E: int, T: int, K: int) -> jax.Array:
    """Switch-style load-balance loss over all K slots."""
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0) / (T * K)
    return E * jnp.sum(me * ce)


def _dropless(params, xf, gate_vals, gate_idx, cfg):
    """The held experts' routed part, every assignment kept.
    Returns ((T, d) f32, tokens per held expert (Eh,) int32)."""
    T, d = xf.shape
    K, Eh = cfg.moe_top_k, cfg.held_experts
    local = gate_idx.reshape(T * K) - cfg.moe_expert_offset
    held = (local >= 0) & (local < Eh)
    bucket = jnp.where(held, local, Eh)        # Eh = not held here
    order = jnp.argsort(bucket, stable=True)
    # rows routed to no held expert sort last; min(K, Eh) rows a token
    # bound the held rows, so the tail past them is never needed
    n = T * min(K, Eh)
    order = order[:n]
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[bucket].add(1)[:Eh]
    tok = order // K
    ye = _grouped_ffn(params, xf[tok], sizes, cfg.act)
    g = jnp.where(held, gate_vals.reshape(T * K), 0.0)[order]
    y = jnp.zeros((T, d), jnp.float32).at[tok].add(ye * g[:, None])
    return y, sizes


def _capacity(params, xf, gate_vals, gate_idx, cfg, capacity_factor):
    """Sort-based dispatch at a fixed capacity per expert; assignments
    past it are dropped. Returns ((T, d) in xf's dtype, tokens per
    expert before dropping)."""
    T, d = xf.shape
    E, K = cfg.num_experts, cfg.moe_top_k
    if cfg.held_experts != E:
        raise ValueError("capacity dispatch needs every expert held; set "
                         "moe_capacity_factor=0 (dropless) for a share")
    C = max(1, int(math.ceil(T * K / E * capacity_factor)))
    flat_e = gate_idx.reshape(T * K)
    flat_t = jnp.arange(T * K) // K
    flat_g = gate_vals.reshape(T * K)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within expert = rank - (first rank of that expert)
    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * K) - starts[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)  # E*C = trash slot

    xe = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].set(xf[st])
    ye = _expert_ffn(params, xe[:-1].reshape(E, C, d), cfg.act)
    ye = jnp.concatenate([ye.reshape(E * C, d),
                          jnp.zeros((1, d), ye.dtype)], axis=0)
    contrib = ye[slot] * (sg * keep)[:, None].astype(ye.dtype)
    y = jnp.zeros((T, d), jnp.float32).at[st].add(contrib.astype(jnp.float32))
    return y.astype(xf.dtype), counts


def moe_routed(params, x, cfg, *, capacity_factor: Optional[float] = None):
    """The routed experts' part of the layer alone (no shared experts).
    x: (B,S,d). Returns (y (B,S,d) f32, aux, tokens per held expert).
    ``capacity_factor`` (default ``cfg.moe_capacity_factor``): > 0 drops
    tokens past that capacity, 0 is dropless."""
    B, S, d = x.shape
    cf = cfg.moe_capacity_factor if capacity_factor is None \
        else capacity_factor
    T, K = B * S, cfg.moe_top_k
    xf = x.reshape(T, d)
    probs, gate_vals, gate_idx = route(params, xf, cfg)
    aux = _aux_loss(probs, gate_idx, cfg.num_experts, T, K)
    if cf > 0:
        y, counts = _capacity(params, xf, gate_vals, gate_idx, cfg, cf)
    else:
        y, counts = _dropless(params, xf, gate_vals, gate_idx, cfg)
    return y.astype(jnp.float32).reshape(B, S, d), aux, counts


def moe_apply(params, x, cfg, *, capacity_factor: Optional[float] = None,
              with_counts: bool = False):
    """x: (B,S,d). Returns (y, aux_loss), and the tokens routed to each
    held expert when ``with_counts``. Under a capacity, tokens over it
    are dropped (their contribution is the shared-expert/residual path
    only)."""
    y, aux, counts = moe_routed(params, x, cfg,
                                capacity_factor=capacity_factor)
    y = y.astype(x.dtype)
    if cfg.num_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg.act)
    return (y, aux, counts) if with_counts else (y, aux)
