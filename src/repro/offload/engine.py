"""OffloadEngine: GreedySnake's schedules executed against REAL
three-tier storage, by compiling a schedule plan once and interpreting
it every step — on one rank or on R data-parallel ranks.

Design note (the schedule IR)
=============================

This engine no longer hard-codes any schedule as control flow. Instead:

* ``repro.core.plan`` compiles the schedule — vertical, horizontal, or
  the wave hybrid — into a linear op stream (``FETCH_PARAM``, ``FWD``,
  ``SPILL_CKPT``/``FETCH_CKPT``, ``BWD``, ``WRITEBACK_GRAD``,
  ``OPT_LATE``, ... — see the op table in that module), with
  ``PREFETCH`` hints derived by a lookahead pass;
* ``repro.offload.executor.execute_plan`` — the ONE executor — walks
  the plan against each rank's coordinators and ``repro.io`` engine;
* ``repro.core.plan.plan_traffic`` predicts every byte counter of a
  run statically from the same IR, cross-checked exactly against the
  closed forms in ``repro.core.traffic`` AND the engine's measured
  meters (``tests/test_plan_executor.py``).

Schedules (per-iteration traffic, validated in tests; ms = low-precision
model bytes, cs = per-micro-batch aggregated ckpt bytes, M micro-batches,
W = wave size, nw = M/W waves):

  vertical   (W=M): params 2·ms, grads 2·ms (f32 once), ckpt M·cs
             written, read twice minus the on-device boundary
             micro-batch (§3.4 + §4.2)
  horizontal (W=1): params 2·M·ms, grad buffer (2M-1)·2·ms, one
             micro-batch resident on device at a time
  wave       (1<W<M): params 2·nw·ms, grad buffer (2·nw-1)·2·ms, and
             the wave interior behaves vertically — the knob trades
             checkpoint traffic against parameter reuse
             (``repro.core.traffic.wave_ckpt_traffic``)

and the (1-α) optimizer fraction overlaps backward, the α fraction the
next forward, via ``OPT_LATE`` gates (§4.4).

Orthogonal to the schedule, ``activation_policy`` picks how backward
gets its inputs: ``"recompute"`` (the paper) re-reads each boundary
checkpoint and recomputes the layer inside the vjp; ``"spill"``
(SSDTrain-style) streams each layer's vjp residuals out after its
forward (``SPILL_ACT``) and back ahead of its backward (``FETCH_ACT``)
at the opportunistic ``IOPriority.ACT``, trading ``2·L·M·A`` stream
bytes for the recompute third of backward and the checkpoint
re-reads; ``"auto"`` prices both with ``repro.core.perfmodel`` against
``OffloadConfig.machine`` (or the configured bandwidth caps). Both
policies apply the SAME saved-residual backward, so they are
bitwise-identical (f32) in losses and parameters; the closed forms are
``repro.core.traffic.act_spill_traffic`` + the ``act_spill=True`` ckpt
variants, and ``A`` is sized exactly by ``jax.eval_shape``.

The embedding and LM head stay device-resident (the paper excludes them
from the per-layer pipeline and adds their time separately, §4.5).

Ranks (data parallelism)
========================

ZeRO-style partitioned offload, the layout GreedySnake's multi-GPU
baseline uses. The engine holds ``ranks``: R stacks, each with its own meter, host
store, ``IOEngine`` + SSD path set (``IOConfig.shard_for_rank``), the
contiguous shard ``bounds[l][r]`` of every layer's tiered vectors
(params, master, momentum, variance) and the four coordinators over
that shard. R = 1 is the single-rank engine: one stack rooted at the
workdir itself, whose fields are also the engine's (``eng.meter``,
``eng.p_vecs``, ...). R > 1 runs ONE compiled vertical plan with
``ALLGATHER`` / ``REDUCE_SCATTER`` in place of ``FETCH_PARAM`` /
``WRITEBACK_GRAD``; rank ``r`` owns micro-batches ``[r·Mr, (r+1)·Mr)``,
its stack lives under ``<workdir>/rank<r>`` and its I/O threads carry
the ``rank<r>-`` label.

The collectives are simulated on one device and deterministic: the
reduce-scatter folds the per-micro-batch layer gradients in GLOBAL
micro-batch order — the single-rank fold — and every elementwise op
(accumulation, Adam) commutes bitwise with element-range slicing, so an
R-rank run is bit-identical (f32) to R = 1. Collective bytes are
metered per rank on routes ``"gpu->net"`` / ``"net->gpu"`` at ring cost,
(R-1)/R of the buffer (``"param"`` all-gather, ``"grad"``
reduce-scatter, ``"head_grad"`` for the replicated embedding/head
all-reduce, §4.5); the closed forms are
:func:`repro.core.traffic.dp_vertical_traffic`.
"""
from __future__ import annotations

import dataclasses
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.perfmodel import MachineParams, StorageRatios
from repro.core.plan import (PlanSpec, compile_wave, insert_prefetch,
                             mb_order, shard_bounds)
from repro.io import IOConfig, IOEngine
from repro.io.config import PATH_POLICIES
from repro.models import blocks as blk
from repro.models.common import rms_norm
from repro.models.model import _xent_chunk
from repro.offload.coordinators import (ActivationCoordinator,
                                        InterLayerTensorCoordinator,
                                        OptimizerStepCoordinator,
                                        ParameterCoordinator)
from repro.obs.tracer import CAT_COUNTER
from repro.offload.executor import LayerFns, execute_plan, stall_seconds
from repro.offload.stores import HostStore, SSDStore, TieredVector, TrafficMeter
from repro.optim.cpu_adam import CpuAdam

__all__ = ["OffloadConfig", "OffloadEngine", "build_block_fns",
           "build_layer_fns", "kind_label", "mb_order", "engine_workload"]


@dataclasses.dataclass
class OffloadConfig:
    schedule: str = "vertical"          # "vertical" | "horizontal" | "wave"
    num_microbatches: int = 4
    micro_batch: int = 2
    seq_len: int = 128
    alpha: float = 0.0                  # delayed optimizer ratio (§4.4)
    wave_size: int = 0                  # W for schedule="wave" (must
                                        # divide num_microbatches;
                                        # W=M <=> vertical, W=1 <=> horizontal)
    ratios: StorageRatios = dataclasses.field(default_factory=StorageRatios)
    lr: float = 1e-3
    io_workers: int = 4
    param_dtype: str = "float32"        # f32 => bit-exact vs in-memory ref
    io: Optional[IOConfig] = None       # paths/chunking/budget/bandwidth
                                        # (None: single path = the workdir)
    activation_policy: str = "recompute"  # "recompute" | "spill" | "auto":
                                        # spill streams each layer's vjp
                                        # residuals (SPILL_ACT/FETCH_ACT)
                                        # instead of recomputing backward
                                        # from the boundary checkpoint;
                                        # auto asks the perf model AND
                                        # adapts per (layer, micro-batch)
                                        # at runtime: a spill is skipped
                                        # (recompute fallback, bitwise-
                                        # identical) when the live write
                                        # queue depth says the SSD is
                                        # saturated
    machine: Optional[MachineParams] = None  # link rates for the "auto"
                                        # decision (None: bandwidth caps
                                        # in `io` if set, else defaults)
    prefetch_depth: int = 1             # cross-stream lookahead depth:
                                        # how many same-stream fetches
                                        # ahead each PREFETCH* hint is
                                        # placed (0 disables the hints
                                        # entirely — every fetch becomes
                                        # a synchronous gate-ordered
                                        # read; byte counters and
                                        # results are identical)
    trace: bool = False                 # start with the repro.obs span
                                        # tracer recording (it can also
                                        # be toggled later via
                                        # eng.tracer.enable/disable;
                                        # off = one flag test per site)
    backpressure: float = 0.5           # adaptive-lookahead threshold:
                                        # skip hints / degrade "auto"
                                        # spills once the I/O engine's
                                        # live depth exceeds this
                                        # fraction of its in-flight
                                        # byte budget

    #: guard against typo'd or absurd lookahead depths — a hint placed
    #: hundreds of fetches ahead would just pin host memory
    MAX_PREFETCH_DEPTH = 16

    #: The schedules / activation policies a config may name. Anything
    #: else is rejected at CONSTRUCTION — same eager ``ValueError``
    #: contract as ``IOConfig`` (path_policy) and ``solve_config``.
    SCHEDULES = ("vertical", "horizontal", "wave")
    ACTIVATION_POLICIES = ("recompute", "spill", "auto")

    def __post_init__(self):
        """Reject malformed knobs at CONSTRUCTION (a typo'd schedule or
        depth should fail where it was written, not when a plan first
        compiles)."""
        if self.schedule not in self.SCHEDULES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; "
                f"choose one of {self.SCHEDULES}")
        if self.activation_policy not in self.ACTIVATION_POLICIES:
            raise ValueError(
                f"unknown activation_policy {self.activation_policy!r}; "
                f"choose one of {self.ACTIVATION_POLICIES}")
        d = int(self.prefetch_depth)
        if not 0 <= d <= self.MAX_PREFETCH_DEPTH:
            raise ValueError(
                f"prefetch_depth={self.prefetch_depth} is outside "
                f"[0, {self.MAX_PREFETCH_DEPTH}]; 0 disables the "
                "lookahead hints, 1 is the classic two-stage pipeline, "
                "larger values hint further ahead")
        if not 0.0 < float(self.backpressure) <= 1.0:
            raise ValueError(
                f"backpressure={self.backpressure} must be in (0, 1] "
                "(fraction of the I/O in-flight budget beyond which "
                "lookahead hints are skipped)")

    def resolved_prefetch_depth(self) -> int:
        """The validated lookahead depth (0 = hints off)."""
        self.__post_init__()     # mutable dataclass: re-check at use
        return int(self.prefetch_depth)

    def resolved_wave_size(self) -> int:
        """The W this config's schedule compiles to."""
        M = self.num_microbatches
        if self.schedule == "vertical":
            return M
        if self.schedule == "horizontal":
            return 1
        if self.schedule == "wave":
            W = self.wave_size
            if W < 1 or M % W:
                raise ValueError(
                    f"wave_size={W} must be in [1, M] and divide "
                    f"num_microbatches={M}")
            return W
        raise ValueError(f"unknown schedule {self.schedule!r}")


def _flatten_tree(tree) -> Tuple[np.ndarray, list, list]:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [l.shape for l in leaves]
    flat = np.concatenate([np.asarray(l).reshape(-1) for l in leaves])
    return flat, treedef, shapes


def _make_unflatten(treedef, shapes, dtype):
    sizes = [int(np.prod(s)) for s in shapes]
    offs = np.cumsum([0] + sizes)

    def unflatten(flat):
        leaves = [jax.lax.dynamic_slice_in_dim(flat, int(offs[i]), sizes[i], 0)
                  .reshape(shapes[i]).astype(dtype)
                  for i in range(len(sizes))]
        return jax.tree.unflatten(treedef, leaves)
    return unflatten


def _adam_device(p, m, v, g, step, lr):
    """Plain Adam for the device-resident embedding/head params."""
    b1, b2, eps = 0.9, 0.95, 1e-8
    g = g.astype(jnp.float32)
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g * g
    t = step.astype(jnp.float32)
    up = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
    return (p.astype(jnp.float32) - lr * up).astype(p.dtype), m2, v2


def _named(fn, name: str):
    """``fn`` renamed, so its jit (and the device trace's module) reads
    ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def kind_label(kind) -> str:
    """A layer kind's short name: ``moe`` for an expert layer, else
    ``dense``."""
    return "moe" if kind.moe else "dense"


def build_layer_fns(cfg, kind, unflatten, suffix: str = ""
                    ) -> Dict[str, object]:
    """One layer kind's jitted forward, residual forward and backward,
    named ``layer_fwd<suffix>`` etc. An MoE kind's forwards also return
    the tokens routed to each held expert: ``layer_fwd`` gives (y,
    counts), ``layer_fwd_res`` (y, vjp, counts); its auxiliary
    load-balance loss stays out of the training loss."""

    def layer_fwd(p_flat, x):
        lp = unflatten(p_flat)
        if kind.moe:
            y, _, (_, counts) = blk.block_apply(lp, x, cfg, kind,
                                                mode="train", moe_counts=True)
            return y, counts
        y, _, _ = blk.block_apply(lp, x, cfg, kind, mode="train")
        return y

    def layer_fwd_res(p_flat, x):
        """Forward that ALSO returns the vjp residuals (a Partial
        pytree of arrays). Both activation policies run backward from
        these residuals — spill restores them from storage, recompute
        re-runs this function at backward time — so the two policies'
        gradients are bitwise-identical by construction."""
        return jax.vjp(lambda p, xx: layer_fwd(p, xx), p_flat, x,
                       has_aux=kind.moe)

    def layer_bwd_res(vjp, dy):
        """Backward from saved/recomputed residuals (no forward pass)."""
        dp, dx = vjp(dy)
        return dx, dp.astype(jnp.float32)

    return {name: jax.jit(_named(fn, name + suffix))
            for name, fn in (("layer_fwd", layer_fwd),
                             ("layer_fwd_res", layer_fwd_res),
                             ("layer_bwd_res", layer_bwd_res))}


def build_block_fns(cfg, kind, unflatten, suffix: str = ""
                    ) -> Dict[str, object]:
    """Jitted per-layer / embedding / head functions. Every rank count
    drives the SAME compiled computations, which is what makes an
    R-rank run bit-identical (f32) to a single-rank run — a per-R
    recompilation could legally re-fuse and break that."""

    def embed_fwd(embed, tokens):
        return embed[tokens]

    def head_loss(unembed, norm, x, labels, weights, denom):
        h = rms_norm(x, norm, cfg.norm_eps)
        tot, _ = _xent_chunk(h, unembed, labels, weights)
        return tot / denom

    def head_bwd(unembed, norm, x, labels, weights, denom):
        (loss), vjp = jax.vjp(
            lambda u, nm, xx: head_loss(u, nm, xx, labels, weights, denom),
            unembed, norm, x)
        du, dn, dx = vjp(jnp.ones((), jnp.float32))
        return loss, du, dn, dx

    def embed_bwd(embed, tokens, dx):
        f = lambda e: e[tokens]
        _, vjp = jax.vjp(f, embed)
        return vjp(dx)[0]

    return {
        **build_layer_fns(cfg, kind, unflatten, suffix),
        "embed": jax.jit(embed_fwd),
        "head_bwd": jax.jit(head_bwd),
        "embed_bwd": jax.jit(embed_bwd),
        "adam_dev": jax.jit(_adam_device),
    }


def _act_residual_nbytes(j_layer_fwd_res, P: int, dtype, micro_batch: int,
                         seq_len: int, d_model: int) -> int:
    """EXACT byte size of one (layer, micro-batch) residual payload —
    what each ``SPILL_ACT``/``FETCH_ACT`` moves — via ``jax.eval_shape``
    (no compute, no allocation)."""
    res = jax.eval_shape(
        j_layer_fwd_res,
        jax.ShapeDtypeStruct((P,), dtype),
        jax.ShapeDtypeStruct((micro_batch, seq_len, d_model), dtype))[1]
    return sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(res))


def engine_workload(ocfg: OffloadConfig, cfg, layer_P, itemsize: int,
                    layer_act_nbytes):
    """The ENGINE-accurate :class:`repro.core.perfmodel.Workload`: the
    FLOP model comes from the one place it is maintained
    (``Workload.from_config``); only the byte fields are overridden
    with this engine's actual sizes — its dtype, its flat layer
    vectors (``layer_P``), its measured residual payloads
    (``layer_act_nbytes``), one of each per layer. The one workload
    both the "auto" activation-policy pricing and the online autotuner
    solve against (an autotuner solving the paper's bf16 defaults would
    retune the wrong machine)."""
    from repro.core.perfmodel import Workload
    L = cfg.num_layers
    tokens = ocfg.micro_batch * ocfg.seq_len
    n = sum(layer_P)
    return dataclasses.replace(
        Workload.from_config(cfg, ocfg.micro_batch, ocfg.seq_len),
        ms=n * itemsize,
        cs=L * tokens * cfg.d_model * itemsize,
        os_bytes=3 * n * 4,
        grad_bytes=n * 4,
        as_bytes=sum(layer_act_nbytes),
    )


class _Rank:
    """One rank's stack: its traffic meter, host store, ``IOEngine`` and
    SSD store, its shard of every layer's tiered vectors (appended by
    :meth:`add_layer`) and the four coordinators over that shard."""

    def __init__(self, iocfg: IOConfig, root: str, label: str, tracer,
                 ocfg: OffloadConfig):
        x = ocfg.ratios
        self.meter = TrafficMeter()
        self.host = HostStore(self.meter)
        # All of the rank's offload traffic flows through one IOEngine. A
        # gated param fetch may wait on an optimizer request, and two
        # fetches can be gated at once, so the engine needs at least 3
        # request workers or the α-delay gate discipline can deadlock.
        if iocfg.workers < 3:
            iocfg = dataclasses.replace(iocfg, workers=3)
        self.ioe = IOEngine(iocfg, meter=self.meter, default_root=root,
                            tracer=tracer, label=label)
        self.ssd = SSDStore(root, self.meter, engine=self.ioe)
        self.p_vecs: List[TieredVector] = []
        self.m_master: List[TieredVector] = []
        self.m_m: List[TieredVector] = []
        self.m_v: List[TieredVector] = []
        self.params_c = ParameterCoordinator(self.p_vecs, self.meter,
                                             self.ioe)
        self.ckpt_c = InterLayerTensorCoordinator(x.ckpt, self.host, self.ssd,
                                                  self.meter, self.ioe)
        self.opt_c = OptimizerStepCoordinator(
            self.m_master, self.m_m, self.m_v, self.p_vecs, self.host,
            self.meter, self.ioe, CpuAdam(lr=ocfg.lr), ocfg.alpha)
        self.act_c = ActivationCoordinator(x.act, self.host, self.ssd,
                                           self.meter, self.ioe)
        for c in self.coordinators():
            c.tracer = tracer

    def add_layer(self, l: int, params: np.ndarray, master: np.ndarray,
                  ocfg: OffloadConfig) -> None:
        """Persist this rank's shard of layer ``l``: the low-precision
        params, their f32 master copy and zeroed Adam moments."""
        x = ocfg.ratios
        n = params.size
        pv = TieredVector(f"param:{l}", n, ocfg.param_dtype, x.param,
                          self.host, self.ssd, "param")
        pv.write_full(params)
        self.p_vecs.append(pv)
        for name, lst, init in (("master", self.m_master, master),
                                ("m", self.m_m, np.zeros(n, np.float32)),
                                ("v", self.m_v, np.zeros(n, np.float32))):
            tv = TieredVector(f"{name}:{l}", n, np.float32, x.opt,
                              self.host, self.ssd, "opt")
            tv.write_full(init)
            lst.append(tv)

    def coordinators(self):
        return (self.params_c, self.ckpt_c, self.act_c, self.opt_c)

    def finish(self, L: int, step: int) -> None:
        for l in range(L):
            self.opt_c.flush_late(l, step)
            self.opt_c.wait_late(l)
        self.opt_c.wait_all()
        self.ckpt_c.wait_pending()
        self.act_c.wait_pending()

    def clear(self) -> None:
        """Drop per-plan residue: cancel param prefetches, disarm the α
        gates, clear the checkpoint and activation state."""
        self.params_c.reset()
        self.params_c.clear_gates()
        self.ckpt_c.clear()
        self.act_c.clear()

    def close(self) -> None:
        self.params_c.reset()
        self.ckpt_c.wait_pending()
        self.act_c.wait_pending()
        self.opt_c.wait_all()
        self.ssd.close()              # removes stripe files from the paths
        self.ioe.shutdown(wait=True)


class OffloadEngine:
    """The offload trainer on ``ranks`` data-parallel ranks (see the
    module docstring). R > 1 runs the vertical schedule over a stack of
    one dense layer kind, with the micro-batches split evenly."""

    #: families whose layers are plain decoder blocks on one token stream
    FAMILIES = ("dense", "moe")

    def __init__(self, cfg, ocfg: OffloadConfig, key, workdir: str,
                 ranks: int = 1):
        if cfg.family not in self.FAMILIES:
            raise ValueError(
                f"the offload engine drives {self.FAMILIES} stacks, not "
                f"family {cfg.family!r}")
        # the layer plan's prefix + periods + suffix, one kind per layer
        # (e.g. DeepSeek's leading dense layer, then MoE layers)
        self.layer_kinds = tuple(blk.build_plan(cfg).all_kinds())
        self.kinds = tuple(dict.fromkeys(self.layer_kinds))
        M = ocfg.num_microbatches
        if ranks > 1:
            # refused before any file or thread exists
            if cfg.family != "dense" or len(self.kinds) != 1:
                raise ValueError(
                    f"the data-parallel offload engine drives stacks of one "
                    f"dense layer kind; {cfg.name!r} (family {cfg.family!r}) "
                    "has other kinds — run it with num_ranks=1")
            if ocfg.schedule != "vertical":
                raise ValueError(
                    "data-parallel offload runs the vertical schedule, not "
                    f"{ocfg.schedule!r}")
            if M % ranks:
                raise ValueError(
                    f"num_microbatches={M} must divide evenly across "
                    f"{ranks} ranks (uneven sharding is a ROADMAP follow-on)")
        self.cfg = cfg
        self.ocfg = ocfg
        self.kind = self.kinds[0]
        self.L = cfg.num_layers
        self.R = ranks
        self.Mr = M // ranks            # micro-batches each rank owns
        self.dtype = jnp.dtype(ocfg.param_dtype)
        self.step_num = 0
        self._closed = False
        self.phase_time: Dict[str, float] = {"fwd": 0.0, "bwd": 0.0, "opt_wait": 0.0}
        # one shared span tracer for every layer that touches bytes
        # (executor, IOEngine threads, coordinators); off by default
        from repro.obs import Tracer
        self.tracer = Tracer()
        if ocfg.trace:
            self.tracer.enable()

        iocfg = ocfg.io if ocfg.io is not None else \
            IOConfig(workers=ocfg.io_workers)
        if ranks == 1:
            self.ranks = [_Rank(iocfg, workdir, "", self.tracer, ocfg)]
            # the single-rank surface: eng.meter, eng.ioe, eng.p_vecs, ...
            vars(self).update(vars(self.ranks[0]))
        else:
            self.ranks = [_Rank(iocfg.shard_for_rank(r, ranks),
                                os.path.join(workdir, f"rank{r}"),
                                f"rank{r}-", self.tracer, ocfg)
                          for r in range(ranks)]

        # ---- init params layerwise straight into tiered storage, each
        # rank persisting only its shard ----
        keys = jax.random.split(key, self.L + 1)
        tmpl = {}                   # kind -> (treedef, leaf shapes)
        layer_P, bounds = [], []
        for l, kind in enumerate(self.layer_kinds):
            lp = blk.block_init(keys[l], cfg, kind, dtype=self.dtype)
            flat, treedef, shapes = _flatten_tree(lp)
            flat = flat.astype(ocfg.param_dtype)
            tmpl.setdefault(kind, (treedef, shapes))
            master = flat.astype(np.float32)
            layer_P.append(flat.size)
            bounds.append(shard_bounds(flat.size, ranks))
            for rk, (lo, hi) in zip(self.ranks, bounds[-1]):
                rk.add_layer(l, flat[lo:hi], master[lo:hi], ocfg)
        #: each layer's flat vector size; ``P`` is the common one of a
        #: stack whose layers are alike, None otherwise
        self.layer_P = tuple(layer_P)
        self.P = layer_P[0] if len(set(layer_P)) == 1 else None
        #: ``bounds[l][r]``: rank r's element range of layer l's vectors
        self.bounds = tuple(bounds)
        self._unflattens = {k: _make_unflatten(*tmpl[k], self.dtype)
                            for k in self.kinds}
        self._unflatten = self._unflattens[self.kind]

        # embedding / head resident on device (+ their own device Adam),
        # replicated on every rank: one copy suffices because all ranks
        # apply identical reduced grads
        from repro.models.common import embed_init, init_rms_scale
        ek = jax.random.split(keys[self.L], 2)
        self.embed = embed_init(ek[0], cfg.padded_vocab, cfg.d_model, self.dtype)
        self.unembed = embed_init(ek[1], cfg.padded_vocab, cfg.d_model, self.dtype).T
        self.final_norm = init_rms_scale(cfg.d_model)
        self.head_state = {
            t: {"m": jnp.zeros_like(getattr(self, t), dtype=jnp.float32),
                "v": jnp.zeros_like(getattr(self, t), dtype=jnp.float32)}
            for t in ("embed", "unembed", "final_norm")}

        self._build_jit_fns()
        # size the activation stream exactly (one (layer, mb) residual
        # payload per kind) and resolve the recompute/spill/auto knob
        act = {k: _act_residual_nbytes(
            self._kind_jits[k]["layer_fwd_res"], self.layer_P[
                self.layer_kinds.index(k)], self.dtype, ocfg.micro_batch,
            ocfg.seq_len, cfg.d_model) for k in self.kinds}
        self.layer_act_nbytes = tuple(act[k] for k in self.layer_kinds)
        self.act_nbytes = act[self.kind] if len(set(act.values())) == 1 \
            else None
        self.act_policy = self._resolve_act_policy(ocfg)
        self.act_fallbacks = 0      # micro-batches degraded to recompute
        # cross-stream lookahead state: per-op stall meters, adaptive
        # skip counters, and the backpressure knob the executor reads
        self.op_seconds: Dict[str, float] = defaultdict(float)
        self.hint_skips = 0         # hints skipped under backpressure
        self.act_skips = 0          # "auto" spills degraded per (l, m)
        self.backpressure = ocfg.backpressure
        self.act_adaptive = (ocfg.activation_policy == "auto"
                             and self.act_policy == "spill")
        self._plan = self._compile_plan(ocfg, self.act_policy)

    # ------------------------------------------------------------------
    def _build_jit_fns(self):
        """One set of layer jits per kind. A stack of one dense kind
        keeps the plain names (``layer_fwd``, ...); otherwise each
        kind's carry its label (``layer_bwd_res_moe``), so a device
        trace splits the step by kind."""
        def suffix(k):
            plain = len(self.kinds) == 1 and not k.moe
            return "" if plain else "_" + kind_label(k)
        fns = build_block_fns(self.cfg, self.kind, self._unflatten,
                              suffix(self.kind))
        self.j_layer_fwd = fns["layer_fwd"]
        self.j_layer_fwd_res = fns["layer_fwd_res"]
        self.j_layer_bwd_res = fns["layer_bwd_res"]
        self.j_embed = fns["embed"]
        self.j_head_bwd = fns["head_bwd"]
        self.j_embed_bwd = fns["embed_bwd"]
        self.j_adam_dev = fns["adam_dev"]
        self._kind_jits = {k: build_layer_fns(self.cfg, k,
                                              self._unflattens[k], suffix(k))
                           for k in self.kinds[1:]}
        self._kind_jits[self.kind] = fns
        self._moe_counts: List[Tuple[int, jax.Array]] = []
        self.moe_tokens: Dict[str, int] = {}
        self.layer_fns = [
            LayerFns(j["layer_fwd"], j["layer_fwd_res"], j["layer_bwd_res"],
                     on_counts=(lambda c, l=l: self._moe_counts.append((l, c)))
                     if k.moe else None)
            for l, k in enumerate(self.layer_kinds)
            for j in (self._kind_jits[k],)]
        self.layer_labels = tuple(kind_label(k) for k in self.layer_kinds)

    def _resolve_act_policy(self, ocfg: OffloadConfig) -> str:
        """Resolve ``ocfg.activation_policy`` to "recompute"|"spill".
        "auto" prices both policies with the perf model
        (:func:`repro.core.perfmodel.pick_activation_policy`) using
        ENGINE-accurate workload bytes (this engine's dtype and measured
        residual sizes, not the bf16 paper defaults) and the machine
        from ``ocfg.machine``, the configured bandwidth caps, or the
        defaults."""
        pol = ocfg.activation_policy
        if pol != "auto":
            return pol
        from repro.core.perfmodel import (machine_from_bandwidth,
                                          pick_activation_policy)
        m = ocfg.machine
        if m is None:
            bw = ocfg.io.bandwidth if ocfg.io is not None else None
            m = machine_from_bandwidth(bw) if bw else MachineParams()
        w = engine_workload(ocfg, self.cfg, self.layer_P,
                            self.dtype.itemsize, self.layer_act_nbytes)
        return pick_activation_policy(w, m, ocfg.num_microbatches,
                                      ocfg.resolved_wave_size(), ocfg.alpha,
                                      ocfg.ratios,
                                      lookahead=ocfg.resolved_prefetch_depth()
                                      > 0)

    def _note_moe_counts(self) -> None:
        """Fold the step's per-(layer, micro-batch) held-expert token
        counts into ``moe_tokens``: ``moe.tokens_held``, the routed
        assignments the held experts took, and ``moe.tokens_max_expert``,
        the most one held expert of one layer took in the step. Read
        after the step's loss, whose readback already waited on them."""
        per_layer: Dict[int, np.ndarray] = {}
        for l, c in zip((l for l, _ in self._moe_counts),
                        jax.device_get([c for _, c in self._moe_counts])):
            per_layer[l] = per_layer.get(l, 0) + np.asarray(c, np.int64)
        self._moe_counts.clear()
        self.moe_tokens = {
            "moe.tokens_held": int(sum(v.sum() for v in per_layer.values())),
            "moe.tokens_max_expert": int(max(v.max()
                                             for v in per_layer.values()))}
        if self.tracer.enabled:
            for name, v in self.moe_tokens.items():
                self.tracer.instant("moe", name, CAT_COUNTER, value=v,
                                    step=self.step_num)

    # ------------------------------------------------------------------
    def _mb_order(self, l: int) -> List[int]:
        """The canonical §4.2 alternating micro-batch order
        (:func:`repro.core.plan.mb_order`) for this config's M — on
        every rank count, which is part of the bit-parity guarantee. The
        plan compiler consults THIS method, so tests can perturb the
        order and watch the executor pay the eviction penalty."""
        return mb_order(self.ocfg.num_microbatches, l)

    def _compile_plan(self, ocfg: OffloadConfig, act_policy: str):
        """Compile ``ocfg``'s schedule for this engine's R ranks (R > 1:
        ``ALLGATHER`` / ``REDUCE_SCATTER`` ops, rank-major micro-batch
        blocks) with the cross-stream lookahead hints at the configured
        depth; every train_step interprets the same plan. Raises
        ``ValueError`` on a schedule the compiler refuses."""
        depth = ocfg.resolved_prefetch_depth()
        spec = PlanSpec(L=self.L, M=ocfg.num_microbatches,
                        alpha=ocfg.alpha, ranks=self.R,
                        act_spill=(act_policy == "spill"))
        # depth 0 = the full lookahead-off baseline: no hints AND the
        # pre-lookahead prologue OPT_LATE ordering
        plan = compile_wave(spec, ocfg.resolved_wave_size(),
                            order=self._mb_order,
                            opt_epilogue=depth > 0)
        return insert_prefetch(plan, depth=depth)

    def train_step(self, tokens: np.ndarray) -> float:
        self._moe_counts.clear()
        loss = execute_plan(self, self._plan, tokens)
        if self._moe_counts:
            self._note_moe_counts()
        return loss

    # ------------------------------------------------------------------
    def _split_tokens(self, tokens):
        M, mb = self.ocfg.num_microbatches, self.ocfg.micro_batch
        assert tokens.shape[0] == M * mb
        return tokens.reshape(M, mb, -1)

    def _labels(self, tok_mb):
        """Next-token labels/weights for one micro-batch (last position
        masked)."""
        lab = np.concatenate([tok_mb[:, 1:], np.zeros((tok_mb.shape[0], 1),
                                                      tok_mb.dtype)], 1)
        w = np.ones(tok_mb.shape, np.float32)
        w[:, -1] = 0.0
        return jnp.asarray(lab), jnp.asarray(w)

    # ------------------------------------------------------------------
    # simulated deterministic collectives (the ops of an R > 1 plan)
    # ------------------------------------------------------------------
    def _collective(self, category: str, send: int, recv: int):
        """Charge one collective's ring cost to every rank's meter (and
        pace it when a ``net`` route cap is configured)."""
        for rk in self.ranks:
            rk.meter.add(category, "gpu->net", send)
            rk.meter.add(category, "net->gpu", recv)
            rk.ioe.throttle("gpu->net", send)
            rk.ioe.throttle("net->gpu", recv)

    def _allgather_params(self, l: int) -> jax.Array:
        """Each rank's shard fetch (already prefetched on its own engine)
        concatenated into the full layer vector. Ring all-gather cost:
        each rank sends its shard R-1 times and receives the R-1 other
        shards."""
        shards = [rk.params_c.get(l) for rk in self.ranks]
        full = jnp.concatenate(shards)
        item = self.dtype.itemsize
        total = self.layer_P[l] * item
        for rk, sh in zip(self.ranks, shards):
            mine = sh.size * item
            rk.meter.add("param", "gpu->net", (self.R - 1) * mine)
            rk.meter.add("param", "net->gpu", total - mine)
            rk.ioe.throttle("gpu->net", (self.R - 1) * mine)
            rk.ioe.throttle("net->gpu", total - mine)
        return full

    def _reduce_scatter_update(self, l: int, per_mb: Dict[int, jax.Array],
                               step: int):
        """Deterministic reduce-scatter + per-rank partial Adam: fold the
        per-micro-batch layer grads in GLOBAL micro-batch order (the
        R = 1 accumulation), slice each rank's
        element range, and hand it to that rank's optimizer coordinator.
        Ring cost: (R-1)/R of the f32 buffer per rank, each direction."""
        gacc = jnp.zeros((self.layer_P[l],), jnp.float32)
        for m in self._mb_order(l):
            gacc = gacc + per_mb[m]
        ring = (self.R - 1) * gacc.nbytes // self.R
        self._collective("grad", ring, ring)
        for rk, (lo, hi) in zip(self.ranks, self.bounds[l]):
            rk.opt_c.submit_early(l, gacc[lo:hi], step)

    # ------------------------------------------------------------------
    def finish(self):
        """Flush any α-pending optimizer work and drain outstanding
        checkpoint spills on every rank (end of training): afterwards
        the meters are complete and deterministic."""
        for rk in self.ranks:
            rk.finish(self.L, self.step_num)

    # ------------------------------------------------------------------
    def apply_plan_config(self, wave_size: Optional[int] = None,
                          prefetch_depth: Optional[int] = None,
                          activation_policy: Optional[str] = None,
                          path_policy: Optional[str] = None):
        """Hot-swap the compiled plan BETWEEN iterations — the
        autotuner's retune seam. Changes any subset of the tunable
        knobs (``wave_size`` retargets the schedule to the wave hybrid
        with that W; ``prefetch_depth``; ``activation_policy``;
        ``path_policy`` actuates every rank's I/O engine chunk->path
        placement — no plan-shape change, so no recompile needed for
        it alone, but the same quiesce applies so the policy flips at
        an iteration boundary) and recompiles; the next ``train_step``
        interprets the new plan.

        The seam must not leak per-plan state, in either direction:

        * α tails are flushed and waited (``finish()`` semantics —
          identical to what a prologue plan would apply at the next
          step's start, so the flush is trajectory-neutral for both
          the epilogue and prologue OPT_LATE placements);
        * outstanding param prefetches are cancelled and the armed α
          gates dropped (:meth:`ParameterCoordinator.clear_gates` —
          the tails just settled, so a surviving gate could only
          deadlock the new plan's first fetch);
        * checkpoint device-kept slots / pending spills / bwd-tail
          prefetches and activation residue are cleared — the new plan
          re-derives its own working set.

        The new plan is compiled from a throwaway config copy BEFORE
        anything mutates, so a bad value — or a wave under R > 1, whose
        plans are vertical — raises ``ValueError`` and leaves the
        engine running its current plan. ``prefetch_depth`` and
        ``activation_policy`` swaps are bitwise trajectory-neutral by
        the PR-4/5 invariants; a ``wave_size`` swap is exact w.r.t. an
        engine compiled with the new plan from the same checkpointed
        state (the satellite pin), though the W axis itself regroups
        the f32 gradient fold across waves."""
        changes = {}
        if wave_size is not None:
            changes.update(schedule="wave", wave_size=int(wave_size))
        if prefetch_depth is not None:
            changes["prefetch_depth"] = int(prefetch_depth)
        if activation_policy is not None:
            changes["activation_policy"] = str(activation_policy)
        trial = dataclasses.replace(self.ocfg, **changes)   # validates
        if path_policy is not None and path_policy not in PATH_POLICIES:
            raise ValueError(
                f"path_policy {path_policy!r} not in {PATH_POLICIES}")
        act_policy = self.act_policy if activation_policy is None else \
            self._resolve_act_policy(trial)
        plan = self._compile_plan(trial, act_policy)
        # quiesce: flush + wait the α tails, drain ckpt/act streams, and
        # drop per-plan residue on every coordinator
        self.finish()
        for rk in self.ranks:
            if path_policy is not None:
                rk.ioe.set_path_policy(path_policy)
            rk.clear()
        # commit the knobs and the new plan
        for k, v in changes.items():
            setattr(self.ocfg, k, v)
        self.act_policy = act_policy
        self.act_adaptive = (self.ocfg.activation_policy == "auto"
                             and act_policy == "spill")
        self._plan = plan
        return plan

    # ------------------------------------------------------------------
    def read_params(self, l: int) -> np.ndarray:
        """Layer ``l``'s full low-precision param vector, assembled from
        the rank shards (validation/checkpointing)."""
        from repro.offload.checkpoint import assemble
        return assemble(self, "p_vecs", l, np.dtype(self.ocfg.param_dtype))

    def save_checkpoint(self, directory: str) -> str:
        """Crash-consistent checkpoint of the full trainable state
        (journaled manifest + CRC-verified tensors, stored assembled so
        any rank count restores it; see :mod:`repro.offload.checkpoint`).
        Returns the manifest path."""
        from repro.offload.checkpoint import save_checkpoint
        return save_checkpoint(self, directory)

    def restore_checkpoint(self, directory: str) -> int:
        """Restore from :meth:`save_checkpoint` output of any rank count
        (all-or-nothing, verified before any state mutates), re-sharded
        by ``bounds``. Returns the restored ``step_num``; the continued
        trajectory is bitwise (f32)."""
        from repro.offload.checkpoint import restore_checkpoint
        return restore_checkpoint(self, directory)

    def traffic(self) -> List[Dict[str, int]]:
        """Per-rank meter snapshots (index = rank), each with the rank's
        host peak under ``"host:peak_nbytes"``."""
        return [{**rk.meter.snapshot(), "host:peak_nbytes": rk.host.peak_nbytes}
                for rk in self.ranks]

    def _lookahead_stats(self) -> Dict[str, object]:
        """Prefetch hit/miss counters summed over every rank's
        coordinators, plus the adaptive-skip counters and per-op stall
        meters."""
        coords = [c for rk in self.ranks for c in rk.coordinators()]
        hits = sum(c.la_hits for c in coords)
        misses = sum(c.la_misses for c in coords)
        total = hits + misses
        return {"hits": hits, "misses": misses,
                "hit_rate": hits / total if total else 1.0,
                "hint_skips": self.hint_skips,
                "act_skips": self.act_skips,
                "stall_s": stall_seconds(self.op_seconds),
                "op_seconds": dict(self.op_seconds)}

    def reset_stats(self):
        """Zero EVERY measured-iteration meter — stall/phase timers,
        adaptive-skip and fallback counters, lookahead hit/miss counts —
        so a second measured iteration after reset reports exactly like
        the first (warm-up boundary; the traffic meters have their own
        ``reset``, and the I/O engines' stats are lifetime counters)."""
        self.op_seconds.clear()
        self.hint_skips = self.act_skips = 0
        self.act_fallbacks = 0
        for k in self.phase_time:
            self.phase_time[k] = 0.0
        for rk in self.ranks:
            for c in rk.coordinators():
                c.la_hits = c.la_misses = 0

    @property
    def plan(self):
        """The compiled schedule plan this engine interprets each step
        (what ``obs.reconcile`` joins a snapshot against)."""
        return self._plan

    def metrics_snapshot(self) -> Dict[str, object]:
        """The versioned flat metrics registry snapshot, per-rank fields
        as lists, JSON-serializable; see :func:`repro.obs.build_snapshot`
        for the schema."""
        from repro.obs import build_snapshot
        return build_snapshot(self)

    def close(self):
        """Drain outstanding I/O, delete the workdir's tensor files, and
        shut the transfer engines down. Idempotent."""
        if self._closed:
            return
        self._closed = True
        for rk in self.ranks:
            rk.close()
