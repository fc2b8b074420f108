"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

XLA auto-SPMD cannot shard the sort-based dispatch of ``moe.moe_apply``:
its data-dependent scatters force replication + TB-scale all-reduces
(measured: 18.8 TB/dev for deepseek-v2-lite train_4k when expert weights
are E-sharded under jit). This module implements the Switch/Mixtral
expert-parallel pipeline by hand inside ``jax.shard_map``:

  local router top-k
    -> bucket assignments by owner shard (sort, capacity-bounded)
    -> all_to_all over the "model" axis              (tokens -> experts)
    -> local sort-based expert FFN over E/m experts
    -> all_to_all back                               (experts -> tokens)
    -> local weighted combine

Sharding contract (set by repro.launch.shardings "opt" mode):
  x            P(bax, "model", None)   batch over data axes, seq over model
  w_gate/up/.. P("model", None, None)  EXPERT dim sharded (stationary)
  router       replicated
  shared       replicated

The transpose of all_to_all is all_to_all, so the backward pass produces
the mirrored token return traffic and parameter gradients stay sharded on
the expert dim — no replicated expert weights at any point.

Enabled via ``set_ep_mesh(mesh)`` (None falls back to the dense-jit
``moe_apply``, which is the right choice on 1 device and for smokes).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import moe as moe_lib
from repro.models.mlp import mlp_apply

_EP: Optional[dict] = None   # {"mesh": Mesh, "axis": str, "bax": tuple}


def set_ep_mesh(mesh, *, axis: str = "model",
                bax: Tuple[str, ...] = ("data", "model")) -> None:
    """Enable expert-parallel dispatch on ``mesh`` (None disables).

    ``bax`` are the axes the BATCH dim of x is sharded over (typically
    all mesh axes, so attention/dense parts stay pure-FSDP and the MoE
    all-to-all runs within model rows); ``axis`` is the expert axis."""
    global _EP
    _EP = None if mesh is None else {"mesh": mesh, "axis": axis,
                                     "bax": tuple(bax)}


def ep_enabled() -> bool:
    return _EP is not None


def _group_by(slot_ids, values, n_slots: int, fill):
    """Scatter values (N, d) into (n_slots+1, d) by slot id (last=trash)."""
    buf = jnp.full((n_slots + 1,) + values.shape[1:], fill, values.dtype)
    return buf.at[slot_ids].set(values)


def _sorted_dispatch(ids, n_buckets: int, capacity: int):
    """ids: (N,) bucket id per element. Returns (order, slot, keep):
    elements sorted by bucket; position within bucket < capacity kept;
    slot = bucket*capacity + pos (trash slot = n_buckets*capacity)."""
    N = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    counts = jnp.zeros((n_buckets,), jnp.int32).at[ids].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N) - starts[sorted_ids]
    keep = (pos < capacity) & (sorted_ids >= 0) & (sorted_ids < n_buckets)
    slot = jnp.where(keep, sorted_ids * capacity + pos, n_buckets * capacity)
    return order, slot, keep


def _moe_ep_local(params, x, cfg, *, axis: str, all_axes,
                  capacity_factor: float) -> Tuple[jax.Array, jax.Array]:
    """Per-shard body (inside shard_map). x: (B_loc, S_loc, d)."""
    m = lax.axis_size(axis)
    my = lax.axis_index(axis)
    B, S, d = x.shape
    T = B * S
    E, K = cfg.num_experts, cfg.moe_top_k
    E_loc = E // m

    xf = x.reshape(T, d)
    logits = xf.astype(jnp.float32) @ params["router"]          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, K)
    if cfg.moe_norm_topk:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                            1e-9)

    # global aux load-balance loss (Switch-style), averaged over the mesh
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[gate_idx.reshape(-1)].add(1.0) / (T * K)
    me = lax.pmean(me, all_axes)
    ce = lax.pmean(ce, all_axes)
    aux = E * jnp.sum(me * ce)

    # ---- stage 1: bucket assignments by OWNER shard ----
    flat_e = gate_idx.reshape(T * K)                 # global expert ids
    flat_t = jnp.arange(T * K) // K
    flat_g = gate_vals.reshape(T * K)
    owner = flat_e // E_loc
    C1 = max(1, int(math.ceil(T * K / m * capacity_factor)))
    order, slot, keep = _sorted_dispatch(owner, m, C1)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]

    send_x = _group_by(slot, xf[st], m * C1, 0)[:-1].reshape(m, C1, d)
    send_e = jnp.full((m * C1 + 1,), -1, jnp.int32).at[slot].set(se)
    send_e = send_e[:-1].reshape(m, C1)

    # ---- all-to-all: tokens -> expert shards ----
    recv_x = lax.all_to_all(send_x, axis, 0, 0, tiled=False)     # (m, C1, d)
    recv_e = lax.all_to_all(send_e, axis, 0, 0, tiled=False)     # (m, C1)

    # ---- stage 2: local expert FFN over E_loc experts ----
    rx = recv_x.reshape(m * C1, d)
    re = recv_e.reshape(m * C1) - my * E_loc          # local ids; pads < 0
    re = jnp.where((re >= 0) & (re < E_loc), re, -1)
    C2 = max(1, int(math.ceil(m * C1 / E_loc * capacity_factor)))
    order2, slot2, keep2 = _sorted_dispatch(re, E_loc, C2)
    xe = _group_by(slot2, rx[order2], E_loc * C2, 0)[:-1].reshape(E_loc, C2, d)
    ye = moe_lib._expert_ffn(params, xe, cfg.act).reshape(E_loc * C2, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)
    # un-sort back to received order (dropped slots contribute 0)
    out = jnp.zeros((m * C1, d), ye.dtype).at[order2].set(
        ye[slot2] * keep2[:, None].astype(ye.dtype))

    # ---- all-to-all back: expert outputs -> token owners ----
    back = lax.all_to_all(out.reshape(m, C1, d), axis, 0, 0, tiled=False)
    back = jnp.concatenate([back.reshape(m * C1, d),
                            jnp.zeros((1, d), back.dtype)], axis=0)

    # ---- local combine ----
    contrib = back[slot] * (sg * keep.astype(jnp.float32))[:, None].astype(back.dtype)
    y = jnp.zeros((T, d), jnp.float32).at[st].add(contrib.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(B, S, d)

    if cfg.num_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg.act)
    return y, aux


def moe_apply_ep(params, x, cfg, *, capacity_factor: float = 1.25
                 ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map (requires set_ep_mesh)."""
    ep = _EP
    assert ep is not None
    mesh, axis, bax = ep["mesh"], ep["axis"], ep["bax"]
    all_axes = tuple(mesh.axis_names)
    x_spec = P(bax if len(bax) > 1 else bax[0], None, None)

    def pspec(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name.startswith("w_"):
            return P(axis, None, None)       # expert dim
        return P()                            # router / shared: replicated

    param_specs = jax.tree_util.tree_map_with_path(pspec, params)
    fn = jax.shard_map(
        partial(_moe_ep_local, cfg=cfg, axis=axis, all_axes=all_axes,
                capacity_factor=capacity_factor),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(params, x)


def moe_dispatch(params, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """EP when enabled and shapes divide; dense-jit fallback otherwise."""
    ep = _EP
    if ep is not None:
        m = ep["mesh"].shape[ep["axis"]]
        bsz = math.prod(ep["mesh"].shape[a] for a in ep["bax"])
        if cfg.num_experts % m == 0 and x.shape[0] % bsz == 0:
            return moe_apply_ep(params, x, cfg)
    return moe_lib.moe_apply(params, x, cfg)
