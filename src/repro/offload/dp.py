"""Data-parallel sharded offload: R rank workers × R SSD path sets.

ZeRO-style partitioned offload (the layout GreedySnake's multi-GPU
baseline uses, and the one its 4-GPU result beats by scheduling): every
tiered vector — low-precision params, master, momentum, variance — is
split into R contiguous element ranges. Rank ``r`` owns range
``[lo_r, hi_r)`` of every layer's vectors, keeps it on its OWN
``IOEngine`` + SSD path set (``IOConfig.shard_for_rank``), and runs the
α-delayed partial Adam on only that shard, so R ranks drive R× the
aggregate storage bandwidth.

The schedule itself is not re-derived here: ``repro.core.plan``
compiles ONE data-parallel vertical plan (``ALLGATHER`` /
``REDUCE_SCATTER`` ops in place of the single-rank ``FETCH_PARAM`` /
``WRITEBACK_GRAD``; per-micro-batch ops emitted rank-major, each rank's
block consuming the global §4.2 alternating order restricted to it, so
every rank's boundary micro-batch keeps its device slot), and the same
``repro.offload.executor`` that drives the single-rank engine walks it
against this engine's per-rank coordinator stacks. Per iteration:

* rank ``r`` runs micro-batches ``[r·M/R, (r+1)·M/R)``;
* **ALLGATHER(l)**: the low-precision param shards at each layer
  boundary (each rank reads ``1/R`` of the layer from its own SSD
  paths — the per-rank reads are prefetched on all R engines before
  any is awaited, which is where the aggregate-bandwidth win comes
  from);
* **REDUCE_SCATTER(l)**: each fully-accumulated f32 layer gradient is
  folded in GLOBAL micro-batch order and every rank updates only its
  optimizer-state shard.

Determinism (§6.5, extended across the data-parallel axis): the
simulated collectives fold contributions in GLOBAL micro-batch order —
the exact fold the single-rank engine performs — and element-range
slicing commutes bitwise with every elementwise op involved (gradient
accumulation, Adam). An R-rank run is therefore **bit-identical (f32)**
to the single-rank ``OffloadEngine``; a real deployment gets the same
property from deterministic (rank-ordered ring) NCCL reductions.

Metering: each rank has its own ``TrafficMeter``. Collective traffic
uses routes ``"gpu->net"`` / ``"net->gpu"`` with ring costs — per rank
and direction, ``(R-1)/R`` of the buffer (categories ``"param"`` for
the all-gather, ``"grad"`` for the reduce-scatter, ``"head_grad"`` for
the replicated embedding/head all-reduce, which the paper's per-layer
pipeline excludes, §4.5). Closed forms:
:func:`repro.core.traffic.dp_vertical_traffic`; the per-rank counters
are validated against them exactly in the DP test battery.
"""
from __future__ import annotations

import dataclasses
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import (PlanSpec, compile_vertical, insert_prefetch,
                             mb_order, shard_bounds)
from repro.io import IOConfig, IOEngine
from repro.io.config import PATH_POLICIES
from repro.models import blocks as blk
from repro.offload.coordinators import (ActivationCoordinator,
                                        InterLayerTensorCoordinator,
                                        OptimizerStepCoordinator,
                                        ParameterCoordinator)
from repro.offload.engine import (OffloadConfig, _flatten_tree,
                                  _make_unflatten, act_residual_nbytes,
                                  bind_block_fns, build_block_fns,
                                  lookahead_stats,
                                  reset_lookahead_stats,
                                  resolve_activation_policy,
                                  shifted_labels, split_microbatches)
from repro.offload.executor import execute_plan
from repro.offload.stores import (HostStore, SSDStore, TieredVector,
                                  TrafficMeter)
from repro.optim.cpu_adam import CpuAdam

__all__ = ["DataParallelOffloadEngine", "shard_bounds"]


class _Rank:
    """One data-parallel rank: its own meter/host/engine/SSD stack, its
    contiguous shard of every tiered vector, and the three coordinators
    rebound to that shard-local storage."""

    def __init__(self, index: int, world: int, root: str,
                 iocfg: IOConfig, ocfg: OffloadConfig, tracer=None):
        self.index = index
        self.world = world
        self.root = root
        self.meter = TrafficMeter()
        self.host = HostStore(self.meter)
        # same worker floor as the single-rank engine: a gated param
        # fetch may wait on an optimizer request (α-delay ordering)
        if iocfg.workers < 3:
            iocfg = dataclasses.replace(iocfg, workers=3)
        # the tracer is SHARED across ranks (one timeline); the label
        # keeps each rank's worker threads on distinct trace tracks
        self.ioe = IOEngine(iocfg, meter=self.meter, default_root=root,
                            tracer=tracer, label=f"rank{index}-")
        self.ssd = SSDStore(root, self.meter, engine=self.ioe)
        self.p_vecs: List[TieredVector] = []
        self.m_master: List[TieredVector] = []
        self.m_m: List[TieredVector] = []
        self.m_v: List[TieredVector] = []
        # coordinators are attached by the engine once shards exist
        self.params_c: Optional[ParameterCoordinator] = None
        self.ckpt_c: Optional[InterLayerTensorCoordinator] = None
        self.opt_c: Optional[OptimizerStepCoordinator] = None
        self.act_c: Optional[ActivationCoordinator] = None

    def close(self):
        self.params_c.reset()
        self.ckpt_c.wait_pending()
        self.act_c.wait_pending()
        self.opt_c.wait_all()
        self.ssd.close()
        self.ioe.shutdown(wait=True)


class DataParallelOffloadEngine:
    """R-rank data-parallel version of :class:`OffloadEngine` (vertical
    schedule only). Same constructor contract plus ``ranks``; per-rank
    SSD paths come from ``ocfg.io`` partitioned by
    ``IOConfig.shard_for_rank`` (default: ``<workdir>/rank<r>``)."""

    def __init__(self, cfg, ocfg: OffloadConfig, key, workdir: str,
                 ranks: int = 2):
        plan = blk.build_plan(cfg)
        if cfg.family != "dense" or len(set(plan.all_kinds())) != 1:
            raise ValueError(
                f"the data-parallel offload engine drives stacks of one "
                f"dense layer kind; {cfg.name!r} (family {cfg.family!r}) "
                "has other kinds — run it on the single-rank "
                "OffloadEngine (make_engine with num_ranks=1)")
        assert ocfg.schedule == "vertical", \
            "data-parallel offload implements the vertical schedule"
        M = ocfg.num_microbatches
        if M % ranks:
            raise ValueError(
                f"num_microbatches={M} must divide evenly across "
                f"{ranks} ranks (uneven sharding is a ROADMAP follow-on)")
        self.cfg = cfg
        self.ocfg = ocfg
        self.kind = plan.all_kinds()[0]
        self.L = cfg.num_layers
        self.R = ranks
        self.Mr = M // ranks
        self.dtype = jnp.dtype(ocfg.param_dtype)
        self.step_num = 0
        self._closed = False
        self.phase_time: Dict[str, float] = {"fwd": 0.0, "bwd": 0.0,
                                             "opt_wait": 0.0}

        base_io = ocfg.io if ocfg.io is not None else \
            IOConfig(workers=ocfg.io_workers)
        from repro.obs import Tracer
        self.tracer = Tracer()
        if ocfg.trace:
            self.tracer.enable()
        self.ranks: List[_Rank] = [
            _Rank(r, ranks, os.path.join(workdir, f"rank{r}"),
                  base_io.shard_for_rank(r, ranks), ocfg,
                  tracer=self.tracer)
            for r in range(ranks)]

        # ---- init params layerwise, identical key-split to the
        # single-rank engine, each rank persisting only its shard ----
        keys = jax.random.split(key, self.L + 1)
        x = ocfg.ratios
        tmpl = None
        for l in range(self.L):
            lp = blk.block_init(keys[l], cfg, self.kind, dtype=self.dtype)
            flat, treedef, shapes = _flatten_tree(lp)
            flat = flat.astype(ocfg.param_dtype)
            if tmpl is None:
                tmpl = (treedef, shapes)
                self.P = flat.size
                self.bounds = shard_bounds(self.P, ranks)
            f32 = flat.astype(np.float32)
            for rk, (lo, hi) in zip(self.ranks, self.bounds):
                n_r = hi - lo
                pv = TieredVector(f"param:{l}", n_r, ocfg.param_dtype,
                                  x.param, rk.host, rk.ssd, "param")
                pv.write_full(flat[lo:hi])
                rk.p_vecs.append(pv)
                for name, lst, init in (
                        ("master", rk.m_master, f32[lo:hi]),
                        ("m", rk.m_m, np.zeros(n_r, np.float32)),
                        ("v", rk.m_v, np.zeros(n_r, np.float32))):
                    tv = TieredVector(f"{name}:{l}", n_r, np.float32,
                                      x.opt, rk.host, rk.ssd, "opt")
                    tv.write_full(init)
                    lst.append(tv)
        self._unflatten = _make_unflatten(tmpl[0], tmpl[1], self.dtype)

        # embedding / head replicated on every (simulated) device; one
        # copy suffices because all ranks apply identical reduced grads
        from repro.models.common import embed_init, init_rms_scale
        ek = jax.random.split(keys[self.L], 2)
        self.embed = embed_init(ek[0], cfg.padded_vocab, cfg.d_model,
                                self.dtype)
        self.unembed = embed_init(ek[1], cfg.padded_vocab, cfg.d_model,
                                  self.dtype).T
        self.final_norm = init_rms_scale(cfg.d_model)
        self.head_state = {
            t: {"m": jnp.zeros_like(getattr(self, t), dtype=jnp.float32),
                "v": jnp.zeros_like(getattr(self, t), dtype=jnp.float32)}
            for t in ("embed", "unembed", "final_norm")}

        for rk in self.ranks:
            rk.params_c = ParameterCoordinator(rk.p_vecs, rk.meter, rk.ioe)
            rk.ckpt_c = InterLayerTensorCoordinator(
                x.ckpt, rk.host, rk.ssd, rk.meter, rk.ioe)
            rk.opt_c = OptimizerStepCoordinator(
                rk.m_master, rk.m_m, rk.m_v, rk.p_vecs, rk.host, rk.meter,
                rk.ioe, CpuAdam(lr=ocfg.lr), ocfg.alpha)
            # activation shards are per micro-batch OWNER: each rank's
            # residual payloads ride its own IOEngine + SSD path set
            rk.act_c = ActivationCoordinator(x.act, rk.host, rk.ssd,
                                             rk.meter, rk.ioe)
        for c in self._coordinators():
            c.tracer = self.tracer

        bind_block_fns(self, build_block_fns(cfg, self.kind,
                                             self._unflatten))
        self.act_nbytes = act_residual_nbytes(
            self.j_layer_fwd_res, self.P, self.dtype, ocfg.micro_batch,
            ocfg.seq_len, cfg.d_model)
        self.act_policy = resolve_activation_policy(
            ocfg, cfg, self.P, self.dtype.itemsize, self.act_nbytes)
        self.act_fallbacks = 0
        self.op_seconds: Dict[str, float] = defaultdict(float)
        self.hint_skips = 0
        self.act_skips = 0
        self.backpressure = ocfg.backpressure
        self.act_adaptive = (ocfg.activation_policy == "auto"
                             and self.act_policy == "spill")
        self._plan = self._compile_plan()

    # ------------------------------------------------------------------
    # micro-batch ownership and ordering
    # ------------------------------------------------------------------
    def _mb_order(self, l: int) -> List[int]:
        """Global §4.2 alternating order — THE canonical
        ``repro.core.plan.mb_order``; sharing it with the single-rank
        engine is part of the bit-parity guarantee."""
        return mb_order(self.ocfg.num_microbatches, l)

    def _compile_plan(self):
        """Compile the R-rank vertical plan once (ALLGATHER /
        REDUCE_SCATTER ops; rank-major micro-batch blocks); every
        train_step interprets it with the shared executor."""
        depth = self.ocfg.resolved_prefetch_depth()
        spec = PlanSpec(L=self.L, M=self.ocfg.num_microbatches,
                        alpha=self.ocfg.alpha, ranks=self.R,
                        act_spill=(self.act_policy == "spill"))
        return insert_prefetch(
            compile_vertical(spec, order=self._mb_order,
                             opt_epilogue=depth > 0), depth=depth)

    # ------------------------------------------------------------------
    # simulated deterministic collectives
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
        for rk, sh in zip(self.ranks, shards):
            mine = sh.size * item
            rk.meter.add("param", "gpu->net", (self.R - 1) * mine)
            rk.meter.add("param", "net->gpu", self.P * item - mine)
            rk.ioe.throttle("gpu->net", (self.R - 1) * mine)
            rk.ioe.throttle("net->gpu", self.P * item - mine)
        return full

    def _reduce_scatter_update(self, l: int, per_mb: Dict[int, jax.Array],
                               step: int):
        """Deterministic reduce-scatter + per-rank partial Adam: fold the
        per-micro-batch layer grads in GLOBAL micro-batch order (the
        single-rank engine's exact accumulation), slice each rank's
        element range, and hand it to that rank's optimizer coordinator.
        Ring cost: (R-1)/R of the f32 buffer per rank, each direction."""
        gacc = self._allreduce_fold(jnp.zeros((self.P,), jnp.float32),
                                    per_mb, self._mb_order(l))
        ring = (self.R - 1) * gacc.nbytes // self.R
        self._collective("grad", ring, ring)
        for rk, (lo, hi) in zip(self.ranks, self.bounds):
            rk.opt_c.submit_early(l, gacc[lo:hi], step)

    def _allreduce_fold(self, zeros: jax.Array, per_mb: Dict[int, jax.Array],
                        order: Sequence[int]) -> jax.Array:
        out = zeros
        for m in order:
            out = out + per_mb[m]
        return out

    # ------------------------------------------------------------------
    def _split_tokens(self, tokens):
        return split_microbatches(tokens, self.ocfg.num_microbatches,
                                  self.ocfg.micro_batch)

    def _labels(self, tok_mb):
        return shifted_labels(tok_mb)

    def train_step(self, tokens: np.ndarray) -> float:
        return execute_plan(self, self._plan, tokens)

    # ------------------------------------------------------------------
    def finish(self):
        """Flush α-pending optimizer shards and drain spills on every
        rank; afterwards all meters are complete and deterministic."""
        for rk in self.ranks:
            for l in range(self.L):
                rk.opt_c.flush_late(l, self.step_num)
                rk.opt_c.wait_late(l)
            rk.opt_c.wait_all()
            rk.ckpt_c.wait_pending()
            rk.act_c.wait_pending()

    # ------------------------------------------------------------------
    def apply_plan_config(self, prefetch_depth: Optional[int] = None,
                          activation_policy: Optional[str] = None,
                          path_policy: Optional[str] = None):
        """Between-iteration plan hot-swap (the autotuner seam), DP
        variant: same quiesce-and-clear contract as
        :meth:`OffloadEngine.apply_plan_config` applied to EVERY rank
        stack (``path_policy`` actuates every rank's I/O engine — each
        rank places chunks over its own path shard). DP plans are
        vertical by construction, so there is no ``wave_size`` knob
        here — ``lp_search.solve_config`` rejects one under
        ``num_gpus>1`` for the same reason."""
        changes = {}
        if prefetch_depth is not None:
            changes["prefetch_depth"] = int(prefetch_depth)
        if activation_policy is not None:
            changes["activation_policy"] = str(activation_policy)
        trial = dataclasses.replace(self.ocfg, **changes)
        trial.resolved_prefetch_depth()
        if trial.activation_policy not in ("recompute", "spill", "auto"):
            raise ValueError(
                f"unknown activation_policy "
                f"{trial.activation_policy!r}")
        if path_policy is not None and path_policy not in PATH_POLICIES:
            raise ValueError(
                f"path_policy {path_policy!r} not in {PATH_POLICIES}")
        self.finish()
        if path_policy is not None:
            for rk in self.ranks:
                rk.ioe.set_path_policy(path_policy)
        for rk in self.ranks:
            rk.params_c.reset()
            rk.params_c.clear_gates()
            rk.ckpt_c.clear()
            rk.act_c.clear()
        for k, v in changes.items():
            setattr(self.ocfg, k, v)
        if activation_policy is not None:
            self.act_policy = resolve_activation_policy(
                self.ocfg, self.cfg, self.P, self.dtype.itemsize,
                self.act_nbytes)
            self.act_adaptive = (self.ocfg.activation_policy == "auto"
                                 and self.act_policy == "spill")
        self._plan = self._compile_plan()
        return self._plan

    def read_params(self, l: int) -> np.ndarray:
        """The full low-precision param vector of layer l, assembled from
        the rank shards (validation/checkpointing)."""
        out = np.empty(self.P, np.dtype(self.ocfg.param_dtype))
        for rk, (lo, hi) in zip(self.ranks, self.bounds):
            out[lo:hi] = rk.p_vecs[l].read()
        return out

    def save_checkpoint(self, directory: str) -> str:
        """Crash-consistent checkpoint, ASSEMBLED format (full vectors,
        not rank shards) — interchangeable with the single-rank
        engine's; see :mod:`repro.offload.checkpoint`."""
        from repro.offload.checkpoint import save_checkpoint
        return save_checkpoint(self, directory)

    def restore_checkpoint(self, directory: str) -> int:
        """Restore from :meth:`save_checkpoint` output (any rank
        count's), re-sharding by ``bounds``. All-or-nothing."""
        from repro.offload.checkpoint import restore_checkpoint
        return restore_checkpoint(self, directory)

    def traffic(self) -> List[Dict[str, int]]:
        """Per-rank meter snapshots (index = rank)."""
        return [rk.meter.snapshot() for rk in self.ranks]

    def _coordinators(self):
        return [c for rk in self.ranks
                for c in (rk.params_c, rk.ckpt_c, rk.act_c, rk.opt_c)]

    def _lookahead_stats(self) -> Dict[str, object]:
        """Cross-rank aggregate, same shape as the single-rank engine's."""
        return lookahead_stats(self, self._coordinators())

    def reset_stats(self):
        reset_lookahead_stats(self, self._coordinators())

    @property
    def plan(self):
        """The compiled DP plan this engine interprets each step
        (what ``obs.reconcile`` joins a snapshot against)."""
        return self._plan

    def metrics_snapshot(self) -> Dict[str, object]:
        """The versioned flat metrics registry snapshot — same schema
        as the single-rank engine's, per-rank fields as lists; see
        :func:`repro.obs.build_snapshot`."""
        from repro.obs import build_snapshot
        return build_snapshot(self)

    def stats(self) -> Dict[str, object]:
        """Deprecated: use :meth:`metrics_snapshot` (versioned, and a
        strict superset of this shape — see CHANGES.md for the
        deprecation window)."""
        import warnings
        warnings.warn(
            "DataParallelOffloadEngine.stats() is deprecated; use "
            "metrics_snapshot()", DeprecationWarning, stacklevel=2)
        return {
            "ranks": self.R,
            "bounds": list(self.bounds),
            "io": [rk.ioe._collect_stats() for rk in self.ranks],
            "host_peak_nbytes": [rk.host.peak_nbytes for rk in self.ranks],
            "act_policy": self.act_policy,
            "act_fallbacks": self.act_fallbacks,
            "lookahead": self._lookahead_stats(),
        }

    def close(self):
        if self._closed:
            return
        self._closed = True
        for rk in self.ranks:
            rk.close()
