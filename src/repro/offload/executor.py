"""The ONE plan executor: walks a compiled :class:`repro.core.plan.Plan`
against the coordinators / IOEngine stack.

Both engines drive their training steps through :func:`execute_plan` —
``OffloadEngine`` (single rank, any wave size) and
``DataParallelOffloadEngine`` (per-rank coordinator stacks, vertical
plans with ``ALLGATHER`` / ``REDUCE_SCATTER`` ops). The executor owns
only transient per-step state (a register file of device tensors keyed
by micro-batch, the layer-gradient accumulator, the head-gradient
folds); all persistent state — tiered vectors, coordinators, the jitted
block functions — belongs to the engine it is handed.

Determinism: the executor performs the SAME coordinator calls and
floating-point folds, in the SAME order, for a given schedule, so
losses and parameters are bit-identical (f32) across the α /
storage-ratio / DP / activation-policy axes (pinned by the
schedule-parity batteries in ``tests/test_property.py`` /
``tests/test_plan_executor.py`` / ``tests/test_act_stream.py``). The
WAVE-SIZE axis is the exception: a 1 < W < M plan GROUPS the f32
layer-gradient fold differently (per-wave partial sums parked in CPU),
so its optimizer-bound sums can differ from vertical's in the last ulp
— step-1 losses are still bitwise, later steps agree within jit
rounding (W=1 folds element-by-element in a commutative order and
stays bitwise in practice).

Activation policies: under ``act_spill`` plans the forward runs the
residual-returning block function and ``SPILL_ACT``/``FETCH_ACT``
stream each layer's vjp residuals through the ``ActivationCoordinator``
instead of recomputing backward from the checkpoint. BOTH policies
apply ``j_layer_bwd_res`` to residuals — restored or recomputed — so
spill and recompute runs are bitwise-identical (f32) in losses and
parameters by construction (pinned in ``tests/test_act_stream.py``).

Cross-stream lookahead: the compiled plan carries one hint op per
fetch-class op (``PREFETCH`` for params, ``PREFETCH_CKPT`` for backward
checkpoint tails, ``PREFETCH_ACT`` for the activation stream,
``PREFETCH_OPT`` for the α-tail optimizer state reads — see
``repro.core.plan.insert_prefetch``). Hints are pure optimization:
each one submits the matching coordinator's asynchronous read early
and moves no bytes of its own, so the executor may legally SKIP any
hint without changing a single byte counter or output bit. That is
exactly what the backpressure-adaptive gate does: before issuing a
hint it consults the owning ``IOEngine.depth()`` and skips when the
live queue says the SSD is saturated (counted in ``eng.hint_skips``).
Under ``activation_policy="auto"`` the same signal gates each
``SPILL_ACT`` per (layer, micro-batch): when the write queue is
saturated the spill is skipped (``eng.act_skips``) and that
micro-batch's backward falls back to recompute — bitwise-identical by
construction, because both policies run backward from the same vjp
residuals.

Stall metering and the span lifecycle: every op's wall-clock is
accumulated into ``eng.op_seconds[op.name]``; :func:`stall_seconds`
sums the kinds the GPU actually blocks on (the FETCH-class ops and the
waits), which is what the bench-smoke artifact reports and CI gates,
and ``repro.obs.stall_by_stream`` folds into per-stream attribution.
When the engine's shared ``repro.obs.Tracer`` is enabled, each executed
op's body runs inside one ``Tracer.span`` on the executor's track (inside
the same ``t_op`` interval that feeds ``op_seconds``), tagged with the
full plan-op identity — op kind, layer ``l``, micro-batch ``m``, wave
index (counted at the ``PHASE("fwd")`` flips), owning rank, and step —
and stamped on the JAX profiler's clock as well. Inside it the
coordinators record leaf spans for what the op waits on: the
host<->device copies (``exec.h2d``, ``exec.device_wait``,
``exec.d2h``), a parameter fetch's request (``exec.param_wait``), an
early Adam segment (``opt.early_wait``) and the I/O engine's in-flight
budget (``io.budget_wait``). A Chrome trace lines the op timeline up
against the I/O channel tracks (queue-wait/transfer spans recorded by
``repro.io.engine``), the workers' gate, read and Adam spans and the
coordinators' hint-lifecycle spans. Tracing off costs one flag test
per op; the op loop's measurement is unchanged.

Fault discipline: a mid-plan exception (a failed chunk op surfacing
through a coordinator) must not leak device slots or host buffers into
the next step — the executor releases its registers, cancels
outstanding parameter prefetches, clears the checkpoint and activation
coordinators' device-kept/CPU state and drains optimizer requests
before re-raising. A failed ``SPILL_ACT``/``FETCH_ACT`` is SOFTER: it
degrades just that micro-batch to the recompute path (counted in
``eng.act_fallbacks``) — the checkpoint tier it needs is still intact
— and the step completes with bitwise-identical results. The
fault-injection batteries (``tests/test_plan_executor.py``,
``tests/test_act_faults.py``) drive these paths with the
``tests/test_io_faults.py`` failing backend.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import Op, Plan
from repro.obs.tracer import CAT_PLAN, EXEC_TRACK, NO_SPAN
from repro.offload.coordinators import _to_device, _to_host, _xfer


def _ranks(eng):
    """The engine's rank stacks: the DP engine's ``ranks`` list, or the
    single-rank engine itself (it exposes the same coordinator attrs)."""
    rks = getattr(eng, "ranks", None)
    return rks if rks is not None else (eng,)


#: plan-op kinds whose handler time is GPU-blocking stall (awaiting
#: storage / collectives / drains) rather than useful compute — the
#: "stall-seconds" the lookahead exists to shrink.
STALL_OPS = frozenset(o.name for o in (
    Op.FETCH_PARAM, Op.ALLGATHER, Op.FETCH_CKPT, Op.FETCH_CKPT_BWD,
    Op.FETCH_ACT, Op.FETCH_GRAD, Op.GRAD_FETCH_ACC, Op.WAIT_OPT,
    Op.BARRIER))


def stall_seconds(op_seconds) -> float:
    """Total stall from a per-op-kind seconds map (``eng.op_seconds``)."""
    return sum(v for k, v in op_seconds.items() if k in STALL_OPS)


def _saturated(ioe, frac: float, route: str) -> bool:
    """The backpressure signal: should a lookahead hint (or an "auto"
    activation spill) on ``route`` be skipped right now?

    Two saturation conditions, either one suffices:

    * the engine's in-flight byte budget is past ``frac`` utilization
      (requests already queue at submit — adding lookahead would make
      the executor BLOCK on the very backpressure it is trying to
      dodge);
    * the per-path channels already hold more than ``frac * 16`` chunks
      of unfinished work on this route (MLP-Offload's idle-level rule:
      prefetch only INTO idle bandwidth — when the link has a standing
      backlog, an early read cannot finish early, it just steals
      link time from whatever the GPU blocks on next).

    Reads only the engine's O(1) counters (``inflight_bytes``,
    ``route_backlog``) — this is polled per hint op, so it must not
    scan queues (``IOEngine.depth()`` is the rich, occasional-use
    snapshot).
    """
    if ioe.inflight_bytes > frac * ioe.budget_bytes:
        return True
    return ioe.route_backlog(route) > frac * 16 * ioe.chunk_bytes


def execute_plan(eng, plan: Plan, tokens: np.ndarray) -> float:
    """Run one training step of ``eng`` by interpreting ``plan``.
    Returns the summed micro-batch loss (same fold order as the
    imperative engines)."""
    ocfg = eng.ocfg
    mbs = eng._split_tokens(tokens)
    eng.step_num += 1
    step = eng.step_num
    denom = jnp.asarray(float(np.prod(tokens.shape) - tokens.shape[0]),
                        jnp.float32)
    ranks = _ranks(eng)
    multi = len(ranks) > 1
    Mr = eng.Mr if multi else plan.spec.M

    def rank_of(m: int):
        return ranks[m // Mr] if multi else ranks[0]

    spill = plan.spec.act_spill     # SSDTrain-style activation stream
    bp = getattr(eng, "backpressure", 0.5)
    act_adaptive = getattr(eng, "act_adaptive", False)
    op_seconds = eng.op_seconds
    tracer = getattr(eng, "tracer", None)
    rec = tracer is not None and tracer.enabled
    wave = -1                       # becomes 0 at the first PHASE("fwd")
    regs = {}                       # transient device tensors
    p_dev = None                    # current layer's params
    gacc = None                     # f32 layer-gradient accumulator
    per_mb_dp = {}                  # DP: stashed per-micro-batch dW
    head_stash = {}                 # DP: stashed (loss, d_unembed, d_norm)
    embed_stash = {}                # DP: stashed d_embed contributions
    loss_total = 0.0
    d_un = jnp.zeros_like(eng.unembed, dtype=jnp.float32)
    d_nm = jnp.zeros_like(eng.final_norm, dtype=jnp.float32)
    d_embed = jnp.zeros_like(eng.embed, dtype=jnp.float32)

    phase = None
    t0 = time.perf_counter()

    def flip(tag):
        nonlocal phase, t0
        now = time.perf_counter()
        if phase is not None:
            eng.phase_time[phase] = eng.phase_time.get(phase, 0.0) \
                + (now - t0)
        phase, t0 = tag, now

    try:
        for op in plan.ops:
            k = op.op
            t_op = time.perf_counter()
            if k is Op.PHASE and op.tag == "fwd":
                wave += 1
            span = tracer.span(
                EXEC_TRACK, k.name, CAT_PLAN, l=op.l, m=op.m, wave=wave,
                rank=(op.m // Mr if multi and op.m >= 0 else 0),
                step=step) if rec else NO_SPAN
            with span:
                if k is Op.FETCH_CKPT:
                    regs[("x", op.m)] = \
                        rank_of(op.m).ckpt_c.get_ckpt_fwd(op.l, op.m)
                elif k is Op.FWD:
                    x_in = regs.pop(("x", op.m))
                    if spill:
                        # materialise the vjp residuals for the act stream
                        y, res = eng.j_layer_fwd_res(p_dev, x_in)
                        regs[("y", op.m)] = y
                        regs[("res", op.m)] = res
                    else:
                        regs[("y", op.m)] = eng.j_layer_fwd(p_dev, x_in)
                elif k is Op.SPILL_ACT:
                    res = regs.pop(("res", op.m))
                    rk = rank_of(op.m)
                    if act_adaptive and _saturated(rk.ioe, bp, "cpu->ssd"):
                        # SSDTrain's adaptive knob per (layer, micro-batch):
                        # the write queue is saturated, so streaming this
                        # residual would lengthen the critical path — drop
                        # it and let FETCH_ACT degrade this micro-batch to
                        # the recompute path (bitwise-identical results)
                        eng.act_skips += 1
                        del res
                    else:
                        try:
                            rk.act_c.put(op.l, op.m, res)
                        except Exception:
                            # a failed spill degrades this micro-batch to
                            # the recompute path (its checkpoint tier is
                            # intact); drop whatever the coordinator
                            # half-tracked — the FETCH_ACT for this key
                            # then finds nothing and counts the fallback
                            rk.act_c.drop(op.l, op.m)
                elif k is Op.PREFETCH_ACT:
                    rk = rank_of(op.m)
                    if _saturated(rk.ioe, bp, "ssd->cpu"):
                        eng.hint_skips += 1
                    else:
                        rk.act_c.prefetch(op.l, op.m)
                elif k is Op.PREFETCH_CKPT:
                    rk = rank_of(op.m)
                    if _saturated(rk.ioe, bp, "ssd->cpu"):
                        eng.hint_skips += 1
                    else:
                        rk.ckpt_c.prefetch_bwd(op.l, op.m)
                elif k is Op.PREFETCH_OPT:
                    if ocfg.alpha > 0:
                        for rk in ranks:
                            if _saturated(rk.ioe, bp, "ssd->cpu"):
                                eng.hint_skips += 1
                            else:
                                rk.opt_c.prefetch_late(op.l)
                elif k is Op.FETCH_ACT:
                    rk = rank_of(op.m)
                    try:
                        regs[("res", op.m)] = rk.act_c.get(op.l, op.m)
                    except Exception:
                        # failed (or never-landed) fetch: fall back to the
                        # checkpoint re-read; BWD recomputes the residuals
                        rk.act_c.drop(op.l, op.m)
                        eng.act_fallbacks += 1
                        regs[("x", op.m)] = \
                            rk.ckpt_c.get_ckpt_bwd(op.l, op.m)
                elif k is Op.SPILL_CKPT:
                    rank_of(op.m).ckpt_c.put_ckpt(op.l, op.m,
                                                  regs.pop(("y", op.m)),
                                                  keep_on_device=op.keep)
                elif k is Op.FETCH_CKPT_BWD:
                    regs[("x", op.m)] = \
                        rank_of(op.m).ckpt_c.get_ckpt_bwd(op.l, op.m)
                elif k is Op.FETCH_GRAD:
                    regs[("dy", op.m)] = \
                        rank_of(op.m).ckpt_c.get_grad(op.l, op.m)
                elif k is Op.BWD:
                    # Both policies run backward from vjp residuals — spill
                    # restores them from the act stream, recompute re-runs
                    # the residual-returning forward on the fetched ckpt —
                    # so spill/recompute gradients are bitwise-identical.
                    res = regs.pop(("res", op.m), None)
                    if res is None:
                        _, res = eng.j_layer_fwd_res(p_dev,
                                                     regs.pop(("x", op.m)))
                    dx, dp = eng.j_layer_bwd_res(res, regs.pop(("dy", op.m)))
                    if op.acc:
                        gacc = gacc + dp
                    else:
                        per_mb_dp[op.m] = dp
                    regs[("dx", op.m)] = dx
                elif k is Op.SPILL_GRAD:
                    rank_of(op.m).ckpt_c.put_grad(op.l, op.m,
                                                  regs.pop(("dx", op.m)),
                                                  keep_on_device=op.keep)
                elif k is Op.DROP_CKPT:
                    rank_of(op.m).ckpt_c.drop_ckpt(op.l, op.m)
                elif k is Op.PREFETCH:
                    for rk in ranks:
                        if _saturated(rk.ioe, bp, "ssd->cpu"):
                            eng.hint_skips += 1
                        else:
                            rk.params_c.prefetch(op.l)
                elif k is Op.FETCH_PARAM:
                    p_dev = ranks[0].params_c.get(op.l)
                elif k is Op.ALLGATHER:
                    p_dev = eng._allgather_params(op.l)
                elif k is Op.RELEASE_PARAM:
                    p_dev = None
                elif k is Op.RESET_PARAMS:
                    for rk in ranks:
                        rk.params_c.reset()
                elif k is Op.EMBED_FWD:
                    regs[("y", op.m)] = eng.j_embed(eng.embed,
                                                    jnp.asarray(mbs[op.m]))
                elif k is Op.HEAD_BWD:
                    lab, w = eng._labels(mbs[op.m])
                    loss, du, dn, dx = eng.j_head_bwd(
                        eng.unembed, eng.final_norm, regs.pop(("x", op.m)),
                        lab, w, denom)
                    if op.acc:
                        loss_total += float(loss)
                        d_un = d_un + du
                        d_nm = d_nm + dn
                    else:
                        head_stash[op.m] = (loss, du, dn)
                    regs[("dx", op.m)] = dx
                elif k is Op.EMBED_BWD:
                    d = eng.j_embed_bwd(eng.embed, jnp.asarray(mbs[op.m]),
                                        regs.pop(("dy", op.m)))
                    if op.acc:
                        d_embed = d_embed + d
                    else:
                        embed_stash[op.m] = d
                elif k is Op.GRAD_INIT:
                    gacc = jnp.zeros((eng.P,), jnp.float32)
                elif k is Op.GRAD_SPILL:
                    rk = ranks[0]
                    g = _to_host(eng, gacc, "grad", l=op.l)
                    _xfer(rk.meter, rk.ioe, "grad", "gpu->cpu", g.nbytes)
                    rk.host.put(f"gacc:{op.l}", g)
                    gacc = None
                elif k is Op.GRAD_FETCH_ACC:
                    rk = ranks[0]
                    g_host = rk.host.pop(f"gacc:{op.l}")
                    _xfer(rk.meter, rk.ioe, "grad", "cpu->gpu", g_host.nbytes)
                    gacc = gacc + _to_device(eng, g_host, "grad", l=op.l)
                elif k is Op.WRITEBACK_GRAD:
                    ranks[0].opt_c.submit_early(op.l, gacc, step)
                    gacc = None
                elif k is Op.REDUCE_SCATTER:
                    eng._reduce_scatter_update(op.l, per_mb_dp, step)
                    per_mb_dp = {}
                elif k is Op.OPT_LATE:
                    # epilogue seam (default): flush THIS step's α-tail now
                    # (it was retained at WRITEBACK_GRAD) and re-arm the
                    # gate, so the flush overlaps the next step's first
                    # fetches. A tag="pro" op is the lookahead-off PROLOGUE
                    # variant: flush the PREVIOUS step's tail at plan start
                    # (same (gradient, Adam-step) pairs => bitwise-equal).
                    pro = op.tag == "pro"
                    if ocfg.alpha > 0 and not (pro and step <= 1):
                        for rk in ranks:
                            rk.opt_c.flush_late(op.l, step - 1 if pro
                                                else step)
                            # the ready probe keeps a hinted fetch from
                            # parking a request worker on a still-QUEUED
                            # flush (deadlock guard for deep lookahead)
                            rk.params_c.set_gate(
                                op.l,
                                (lambda c, ll: lambda: c.wait_late(ll))(
                                    rk.opt_c, op.l),
                                (lambda c, ll: lambda: c.late_settled(ll))(
                                    rk.opt_c, op.l))
                elif k is Op.FOLD_HEAD:
                    for m in op.ms:
                        loss, du, dn = head_stash[m]
                        loss_total += float(loss)
                        d_un = d_un + du
                        d_nm = d_nm + dn
                    head_stash = {}
                elif k is Op.FOLD_EMBED:
                    for m in op.ms:
                        d_embed = d_embed + embed_stash[m]
                    embed_stash = {}
                elif k is Op.ALLREDUCE_HEAD:
                    head_bytes = int(d_embed.nbytes + d_un.nbytes
                                     + d_nm.nbytes)
                    ring = 2 * (eng.R - 1) * head_bytes // eng.R
                    eng._collective("head_grad", ring, ring)
                elif k is Op.HEAD_ADAM:
                    for name, g in (("embed", d_embed), ("unembed", d_un),
                                    ("final_norm", d_nm)):
                        st = eng.head_state[name]
                        p2, st["m"], st["v"] = eng.j_adam_dev(
                            getattr(eng, name), st["m"], st["v"], g,
                            jnp.asarray(step, jnp.int32),
                            jnp.asarray(ocfg.lr))
                        setattr(eng, name, p2)
                elif k is Op.WAIT_OPT:
                    for rk in ranks:
                        rk.opt_c.wait_all()
                elif k is Op.BARRIER:
                    jax.effects_barrier()
                elif k is Op.PHASE:
                    flip(op.tag)
                else:                    # pragma: no cover - compiler bug
                    raise ValueError(f"unknown plan op {op!r}")
            op_seconds[k.name] += time.perf_counter() - t_op
        flip(None)
    except BaseException:
        # Mid-plan failure: free the device slots and cancel in-flight
        # work so the engine can be reused or torn down cleanly instead
        # of leaking kept boundary tensors / gated prefetches. The
        # step is abandoned wholesale, so α gates and retained α-tail
        # gradients go with it (clear_gates / opt_c.clear) — a stale
        # gate or pending_grad would re-raise this step's fault (or
        # apply its gradient) inside the NEXT step. After this unwind
        # the engine accepts new steps / checkpoint restores cleanly;
        # tests/test_chaos.py pins that.
        regs.clear()
        per_mb_dp = head_stash = embed_stash = {}
        gacc = p_dev = None
        for rk in ranks:
            for fn in (rk.params_c.reset, rk.params_c.clear_gates,
                       rk.ckpt_c.clear, rk.act_c.clear, rk.opt_c.clear):
                try:
                    fn()
                except Exception:
                    pass                 # the original error propagates
        raise
    return loss_total
