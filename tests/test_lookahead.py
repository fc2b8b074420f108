"""Cross-stream lookahead battery.

* The plan-lint meta-test (the PR's structural acceptance): in EVERY
  compiled plan across schedules × M × α × R × activation policy, every
  fetch-class op that can touch the SSD carries exactly one matching
  hint (``PREFETCH`` per param fetch / all-gather, ``PREFETCH_CKPT``
  per backward checkpoint re-read, ``PREFETCH_ACT`` per activation
  fetch, ``PREFETCH_OPT`` per α-tail flush), placed before its fetch
  and never across a ``RESET_PARAMS``; ops whose payloads are provably
  device-kept or CPU-resident (``FETCH_CKPT``, ``FETCH_GRAD``) carry
  none.
* Hints move *when* bytes flow, never *how many*: ``plan_traffic`` is
  invariant under ``insert_prefetch`` at any depth, and live engines
  are bitwise-identical (f32, losses AND parameters) and byte-identical
  (every meter counter) with lookahead on vs off across the acceptance
  grid — single-rank and data-parallel.
* The backpressure-adaptive loop: ``IOEngine.depth()`` introspection,
  hint skipping under a saturated budget (still bitwise/byte-clean),
  and the per-(layer, micro-batch) "auto" spill degradation.
* The perf model's reduced stall terms: ``lookahead=False`` prices the
  hint-free executor at or above the hinted one, in ``perfmodel`` and
  in the LP rows.
"""
import dataclasses
import tempfile
import threading
from collections import defaultdict, deque

import jax
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import (MachineParams, StorageRatios, Workload,
                                  iteration_time_vertical,
                                  iteration_time_vertical_dp,
                                  iteration_time_wave)
from repro.core.plan import (HINT_FOR_FETCH, HINT_KINDS, Op, PlanCosts,
                             PlanSpec, compile_wave, insert_prefetch,
                             plan_traffic)
from repro.data import SyntheticLM
from repro.io import IOConfig, IOEngine, IOPriority
from repro.offload import OffloadConfig, OffloadEngine, make_engine

CFG = ArchConfig(name="look-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S = 1, 16
X0 = StorageRatios(0.0, 0.0, 0.0)

#: fetch-class ops whose payloads are PROVABLY device-kept or
#: CPU-resident (forward ckpt cache, inter-layer grads) — the lint
#: asserts these carry no hints
UNHINTED_FETCHES = (Op.FETCH_CKPT, Op.FETCH_GRAD)

#: the acceptance grid: schedule × M × α × R (wave needs M % 2 == 0,
#: DP plans are vertical with M % R == 0)
GRID = [(sched, M, alpha, R)
        for sched in ("vertical", "horizontal", "wave")
        for M in (1, 2, 4)
        for alpha in (0.0, 0.5)
        for R in (1, 2)
        if not (sched == "wave" and M % 2)
        and not (R > 1 and (sched != "vertical" or M % R))]


def _compiled(sched, M, alpha, R, act_spill=False, depth=1):
    W = {"vertical": M, "horizontal": 1, "wave": 2}[sched]
    spec = PlanSpec(L=3, M=M, alpha=alpha, ranks=R, act_spill=act_spill)
    return insert_prefetch(compile_wave(spec, W), depth=depth)


def _hint_key(op):
    return (op.op, op.l, op.m)


def lint_plan(plan):
    """Assert the hint discipline over one compiled plan (see module
    docstring). Returns the number of (hint, fetch) pairs checked."""
    hints = defaultdict(deque)        # (hint_kind, l, m) -> hint indices
    resets = []
    pairs = 0
    for i, op in enumerate(plan.ops):
        if op.op is Op.RESET_PARAMS:
            resets.append(i)
        elif op.op in HINT_KINDS:
            hints[(op.op, op.l, op.m)].append(i)
        elif op.op in HINT_FOR_FETCH:
            kind = HINT_FOR_FETCH[op.op]
            q = hints[(kind, op.l, op.m)]
            assert q, (f"{op!r} at {i} has no pending {kind.name} hint "
                       f"({plan.schedule}, M={plan.spec.M})")
            h = q.popleft()
            crossed = [r for r in resets if h < r < i]
            assert not crossed, \
                f"hint at {h} for {op!r} at {i} crosses RESET_PARAMS"
            pairs += 1
        elif op.op in UNHINTED_FETCHES:
            pass                      # checked globally below
    leftovers = {k: list(v) for k, v in hints.items() if v}
    assert not leftovers, f"hints without a consumer: {leftovers}"
    for kind in HINT_KINDS:
        wanted = [f for f, h in HINT_FOR_FETCH.items() if h is kind]
        assert plan.count(kind) == sum(plan.count(f) for f in wanted)
    return pairs


# ---------------------------------------------------------------------------
# the plan-lint meta-test (every compiled plan, both activation policies)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched,M,alpha,R", GRID)
@pytest.mark.parametrize("act_spill", [False, True])
def test_plan_lint_every_fetch_has_exactly_one_hint(sched, M, alpha, R,
                                                    act_spill):
    plan = _compiled(sched, M, alpha, R, act_spill=act_spill)
    pairs = lint_plan(plan)
    assert pairs > 0
    # provably-resident payloads carry no hints: no hint kind targets
    # FETCH_CKPT / FETCH_GRAD (structural, from the op->hint table)
    for f in UNHINTED_FETCHES:
        assert f not in HINT_FOR_FETCH
    # spill plans hint the act stream, recompute plans the ckpt tails
    if act_spill:
        assert plan.count(Op.PREFETCH_ACT) == plan.count(Op.FETCH_ACT) > 0
        assert plan.count(Op.PREFETCH_CKPT) == 0
    else:
        assert plan.count(Op.PREFETCH_CKPT) \
            == plan.count(Op.FETCH_CKPT_BWD) > 0
        assert plan.count(Op.PREFETCH_ACT) == 0
    if alpha > 0:
        assert plan.count(Op.PREFETCH_OPT) == plan.count(Op.OPT_LATE) > 0


def test_prologue_plans_keep_hints_behind_the_alpha_gates():
    """Hinting a prologue-ordered plan (a public, if unusual,
    combination) must never hoist a param hint above the OPT_LATE ops
    that arm the fetch gates — the old pre-seam invariant."""
    from repro.core.plan import compile_vertical

    spec = PlanSpec(L=3, M=4, alpha=0.4)
    for depth in (1, 3):
        plan = insert_prefetch(compile_vertical(spec, opt_epilogue=False),
                               depth=depth)
        lint_plan(plan)
        kinds = [op.op for op in plan.ops]
        last_pro = max(i for i, op in enumerate(plan.ops)
                       if op.op is Op.OPT_LATE and op.tag == "pro")
        assert kinds.index(Op.PREFETCH) > last_pro, depth


def test_plan_lint_holds_at_greater_depths():
    for depth in (2, 5):
        for sched in ("vertical", "horizontal", "wave"):
            lint_plan(_compiled(sched, 4, 0.5, 1, depth=depth))
            lint_plan(_compiled(sched, 4, 0.5, 1, act_spill=True,
                                depth=depth))


def test_depth_zero_is_the_prologue_baseline():
    """depth 0 compiles the full lookahead-off plan: no hint ops at
    all, and the α-tail flushes back in the PROLOGUE (tag "pro") —
    the pre-lookahead executor ordering."""
    from repro.core.plan import compile_vertical

    spec = PlanSpec(L=3, M=4, alpha=0.5)
    bare = insert_prefetch(compile_vertical(spec, opt_epilogue=False),
                           depth=0)
    for kind in HINT_KINDS:
        assert bare.count(kind) == 0
    lates = [op for op in bare.ops if op.op is Op.OPT_LATE]
    assert [op.tag for op in lates] == ["pro"] * 3
    kinds = [op.op for op in bare.ops]
    assert kinds.index(Op.OPT_LATE) < kinds.index(Op.EMBED_FWD)
    with pytest.raises(ValueError, match="depth"):
        insert_prefetch(compile_vertical(spec), depth=-1)


# ---------------------------------------------------------------------------
# byte parity: hints move when bytes flow, never how many
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("act_spill", [False, True])
def test_plan_traffic_invariant_under_hints(act_spill):
    costs = PlanCosts(P=1000, param_itemsize=4, ckpt_elems=64,
                      act_itemsize=4, ratios=X0, alpha=0.5,
                      act_res_bytes=512)
    for sched in ("vertical", "horizontal", "wave"):
        W = {"vertical": 4, "horizontal": 1, "wave": 2}[sched]
        spec = PlanSpec(L=3, M=4, alpha=0.5, act_spill=act_spill)
        bare = compile_wave(spec, W)
        pro = compile_wave(spec, W, opt_epilogue=False)
        t0 = plan_traffic(bare, costs)
        assert plan_traffic(insert_prefetch(bare, depth=1), costs) == t0
        assert plan_traffic(insert_prefetch(bare, depth=3), costs) == t0
        # the prologue (lookahead-off) seam moves the same bytes too
        assert plan_traffic(pro, costs) == t0


# ---------------------------------------------------------------------------
# the live acceptance grid: bitwise + byte identity, lookahead on vs off
# ---------------------------------------------------------------------------

def _run(sched, M, alpha, R, depth, steps=2, io=None, policy="recompute",
         machine=None):
    W = {"vertical": 0, "horizontal": 0, "wave": 2}[sched]
    ocfg = OffloadConfig(schedule=sched, num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=alpha,
                         wave_size=W, ratios=X0, prefetch_depth=depth,
                         io=io, activation_policy=policy, machine=machine)
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, ocfg, jax.random.PRNGKey(11), d, num_ranks=R)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        losses = [eng.train_step(data.batch(M * MB, S))
                  for _ in range(steps)]
        eng.finish()
        routes = [dict(rk.meter.bytes) for rk in eng.ranks]
        params = [eng.read_params(l) for l in range(eng.L)]
        look = eng.metrics_snapshot()["lookahead"]
        skips = (eng.hint_skips, eng.act_skips, eng.act_fallbacks)
        eng.close()
    return losses, routes, params, look, skips


@pytest.mark.parametrize("sched,M,alpha,R", GRID)
def test_lookahead_on_off_bitwise_and_byte_identical(sched, M, alpha, R):
    """The acceptance sweep: losses, final parameters, and every
    (category, route) byte counter are identical with the cross-stream
    lookahead on (depth 1) vs off (depth 0, prologue seam) — and the
    hinted run actually prefetches."""
    l0, r0, p0, _, _ = _run(sched, M, alpha, R, depth=0)
    l1, r1, p1, look, _ = _run(sched, M, alpha, R, depth=1)
    assert l0 == l1, "lookahead changed the losses"
    assert r0 == r1, "lookahead changed a byte counter"
    for a, b in zip(p0, p1):
        assert (a == b).all(), "lookahead changed the parameters"
    assert look["hits"] > 0, "the hinted run never prefetched"


def test_deeper_lookahead_still_bitwise():
    l1, r1, p1, _, _ = _run("vertical", 4, 0.5, 1, depth=1)
    l3, r3, p3, look, _ = _run("vertical", 4, 0.5, 1, depth=3)
    assert l1 == l3 and r1 == r3
    for a, b in zip(p1, p3):
        assert (a == b).all()
    assert look["hit_rate"] > 0.5


def test_prefetch_depth_validation():
    # malformed knobs fail at CONSTRUCTION, with a clear error
    for bad in (-1, 99):
        with pytest.raises(ValueError, match="prefetch_depth"):
            OffloadConfig(num_microbatches=2, micro_batch=MB,
                          seq_len=S, prefetch_depth=bad)
    with pytest.raises(ValueError, match="backpressure"):
        OffloadConfig(num_microbatches=2, micro_batch=MB, seq_len=S,
                      backpressure=0.0)
    # a config mutated after construction is re-checked at compile time
    ocfg = OffloadConfig(num_microbatches=2, micro_batch=MB, seq_len=S)
    ocfg.prefetch_depth = -3
    with pytest.raises(ValueError, match="prefetch_depth"):
        ocfg.resolved_prefetch_depth()
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="prefetch_depth"):
            OffloadEngine(CFG, OffloadConfig(
                num_microbatches=2, micro_batch=MB, seq_len=S,
                prefetch_depth=-3), jax.random.PRNGKey(0), d)


# ---------------------------------------------------------------------------
# the backpressure-adaptive loop
# ---------------------------------------------------------------------------

def test_io_engine_depth_introspection():
    with tempfile.TemporaryDirectory() as d:
        ioe = IOEngine(IOConfig(workers=1, inflight_bytes=1 << 30),
                       default_root=d)
        try:
            gate = threading.Event()
            started = threading.Event()

            def blocker():
                started.set()
                gate.wait(10)

            r1 = ioe.submit(blocker, priority=IOPriority.PARAM_FETCH,
                            category="param", route="ssd->cpu",
                            nbytes=1000)
            assert started.wait(5)
            r2 = ioe.submit(lambda: None,
                            priority=IOPriority.OPTIMIZER_STATE,
                            category="opt", route="ssd->cpu", nbytes=500)
            d0 = ioe.depth()
            assert d0["running"] == 1
            assert d0["queued"] == 1
            assert d0["queued_by_priority"]["OPTIMIZER_STATE"] == 1
            assert d0["queued_bytes_by_route"]["ssd->cpu"] == 500
            assert d0["inflight_bytes"] == 1500
            assert d0["budget_bytes"] == 1 << 30
            assert 0 < d0["utilization"] < 1
            gate.set()
            r1.result()
            r2.result()
            d1 = ioe.depth()
            assert d1["queued"] == 0 and d1["inflight_bytes"] == 0
            assert d1["channel_queued"] == 0
        finally:
            ioe.shutdown()


def test_saturation_signal_reads_live_depth():
    """``_saturated`` fires on either live condition — in-flight bytes
    past the budget fraction, or a standing channel backlog on the
    route — and stays quiet on an idle engine."""
    from repro.offload.executor import _saturated

    with tempfile.TemporaryDirectory() as d:
        ioe = IOEngine(IOConfig(workers=1, inflight_bytes=10_000),
                       default_root=d)
        try:
            assert not _saturated(ioe, 0.5, "cpu->ssd")
            gate = threading.Event()
            started = threading.Event()

            def blocker():
                started.set()
                gate.wait(10)

            req = ioe.submit(blocker, priority=IOPriority.CKPT_SPILL,
                             category="ckpt", route="cpu->ssd",
                             nbytes=6_000)
            assert started.wait(5)
            assert _saturated(ioe, 0.5, "cpu->ssd")     # 6000 > 5000
            assert not _saturated(ioe, 0.7, "cpu->ssd")  # 6000 < 7000
            gate.set()
            req.result()
            assert not _saturated(ioe, 0.5, "cpu->ssd")
        finally:
            ioe.shutdown()


def test_hints_skipped_under_saturation_stay_bitwise(monkeypatch):
    """With the saturation signal pinned high, every hint is SKIPPED
    (counted, byte-neutral): losses, params, and every byte counter
    still equal both the hint-free run and the freely-prefetching
    run — the executor guarantee that makes adaptivity always legal."""
    import repro.offload.executor as ex

    l0, r0, p0, _, _ = _run("vertical", 2, 0.5, 1, depth=0)
    l1, r1, p1, _, _ = _run("vertical", 2, 0.5, 1, depth=1)
    monkeypatch.setattr(ex, "_saturated", lambda *a: True)
    ls, rs, ps, look, (skips, _, _) = _run("vertical", 2, 0.5, 1, depth=1)
    assert skips > 0, "a pinned-high signal must skip every hint"
    assert look["hits"] == 0, "skipped hints cannot produce hits"
    assert l0 == l1 == ls, "adaptive skipping changed the losses"
    for a, b, c in zip(p0, p1, ps):
        assert (a == b).all() and (a == c).all()
    assert r0 == r1 == rs, "a skipped hint changed a byte counter"


def test_auto_policy_degrades_spills_under_backpressure(monkeypatch):
    """activation_policy="auto" resolved to spill: a saturated write
    queue degrades individual (layer, micro-batch) spills to the
    recompute path — still bitwise-identical to the recompute run."""
    import repro.offload.executor as ex

    slow_gpu = MachineParams(gpu_flops=1e8, ssd_read_bw=50e9,
                             ssd_write_bw=50e9, pcie_bw=50e9,
                             cpu_adam_bw=100e9)
    l_re, _, p_re, _, _ = _run("vertical", 2, 0.0, 1, depth=1)
    # saturate ONLY the write side: spills skip, read hints still flow
    monkeypatch.setattr(ex, "_saturated",
                        lambda ioe, frac, route: route == "cpu->ssd")
    l_ad, _, p_ad, _, (_, act_skips, fallbacks) = _run(
        "vertical", 2, 0.0, 1, depth=1, policy="auto", machine=slow_gpu)
    # 2 steps x L layers x M=2 micro-batches, every spill degraded
    assert act_skips == 2 * CFG.num_layers * 2, \
        "every (layer, micro-batch) spill must degrade"
    assert fallbacks == act_skips, "skipped spills must recompute"
    assert l_re == l_ad, "adaptive spill skipping changed the losses"
    for a, b in zip(p_re, p_ad):
        assert (a == b).all()


def test_explicit_spill_policy_is_never_adaptive(monkeypatch):
    """Only "auto" adapts: an explicit "spill" run under a pinned-high
    saturation signal keeps its exact deterministic byte counters
    (hints skip — byte-neutral — but no spill ever degrades)."""
    import repro.offload.executor as ex

    monkeypatch.setattr(ex, "_saturated", lambda *a: True)
    _, _, _, _, (_, act_skips, fallbacks) = _run(
        "vertical", 2, 0.0, 1, depth=1, policy="spill")
    assert act_skips == 0 and fallbacks == 0


def test_hinted_prefetch_refused_while_gate_unready():
    """The deadlock guard: a HINT must not submit a fetch whose α gate
    is not ready (its flush still queued) — a burst of prefetch_depth
    gated bodies outranking the queued flushes could otherwise occupy
    every request worker; the consumer path always submits (it blocks
    the executor, not a worker)."""
    from repro.offload.coordinators import ParameterCoordinator
    from repro.offload.stores import HostStore, SSDStore, TieredVector, \
        TrafficMeter

    with tempfile.TemporaryDirectory() as d:
        meter = TrafficMeter()
        ioe = IOEngine(IOConfig(workers=3), default_root=d)
        ssd = SSDStore(d, meter, engine=ioe)
        host = HostStore(meter)
        vec = TieredVector("param:0", 64, "float32", 0.0, host, ssd,
                          "param")
        import numpy as np
        vec.write_full(np.arange(64, dtype=np.float32))
        co = ParameterCoordinator([vec], meter, ioe)
        ready = {"ok": False}
        fired = []
        co.set_gate(0, lambda: fired.append(True),
                    ready=lambda: ready["ok"])
        co.prefetch(0)
        assert co._futures == {}, "hint submitted past an unready gate"
        ready["ok"] = True
        co.prefetch(0)
        assert 0 in co._futures, "ready gate must admit the hint"
        out = co.get(0)
        assert fired == [True] and float(out[5]) == 5.0
        # unready gate + consumer get(): still submits and completes
        co.set_gate(0, lambda: fired.append(True), ready=lambda: False)
        co.prefetch(0)
        assert co._futures == {}
        out = co.get(0)
        assert len(fired) == 2 and float(out[7]) == 7.0
        ssd.close()


def test_deep_lookahead_with_gates_completes_and_stays_bitwise():
    """Integration pin for the same guard: prefetch_depth far above the
    worker count, α>0, L > workers — every plan-start hint burst hits
    freshly-submitted epilogue flushes, and the run must neither hang
    nor change a bit."""
    deep_cfg = ArchConfig(name="deep-tiny", family="dense", source="test",
                          num_layers=4, d_model=32, num_heads=2,
                          num_kv_heads=2, head_dim=16, d_ff=64,
                          vocab_size=256, act="gelu")

    def run(depth):
        ocfg = OffloadConfig(schedule="vertical", num_microbatches=2,
                             micro_batch=MB, seq_len=S, alpha=0.5,
                             ratios=X0, prefetch_depth=depth,
                             io=IOConfig(workers=3))
        with tempfile.TemporaryDirectory() as d:
            eng = OffloadEngine(deep_cfg, ocfg, jax.random.PRNGKey(3), d)
            data = SyntheticLM(deep_cfg.vocab_size, seed=0)
            losses = [eng.train_step(data.batch(2 * MB, S))
                      for _ in range(3)]
            eng.finish()
            routes = dict(eng.meter.bytes)
            eng.close()
        return losses, routes

    l0, r0 = run(0)
    l8, r8 = run(8)
    assert l0 == l8 and r0 == r8


def test_stall_meters_and_stats_plumbing():
    _, _, _, look, _ = _run("vertical", 2, 0.5, 1, depth=1)
    assert look["stall_s"] > 0
    assert set(look) >= {"hits", "misses", "hit_rate", "hint_skips",
                         "act_skips", "stall_s", "op_seconds"}
    assert look["op_seconds"]["FETCH_PARAM"] >= 0
    from repro.offload.executor import STALL_OPS, stall_seconds
    assert "FETCH_PARAM" in STALL_OPS and "FWD" not in STALL_OPS
    assert stall_seconds({"FETCH_PARAM": 1.0, "FWD": 5.0}) == 1.0


# ---------------------------------------------------------------------------
# the perf model's reduced stall terms
# ---------------------------------------------------------------------------

# checkpoint-heavy workload, optimizer state CPU-resident: the
# backward tail re-reads (recompute) / residual fetches (spill) are
# the serialized reads the lookahead hides, and compute + stall
# exceeds the pure SSD stage bound, so the hint-free pricing binds
STALL_M = MachineParams(gpu_flops=100e12, ssd_read_bw=2e9,
                        ssd_write_bw=2e9, pcie_bw=200e9,
                        cpu_adam_bw=500e9)
STALL_W = Workload(ms=1e9, cs=2e9, os_bytes=6e9, grad_bytes=2e9,
                   flops_per_mb=15e12, tokens_per_mb=4096, n_layers=8,
                   as_bytes=1.5e9)
STALL_X = StorageRatios(0.0, 0.0, 1.0)


def test_perfmodel_lookahead_reduces_stall_terms():
    for act in ("recompute", "spill"):
        t_on = iteration_time_vertical(STALL_W, STALL_M, 8, 0.4, STALL_X,
                                       act=act)
        t_off = iteration_time_vertical(STALL_W, STALL_M, 8, 0.4, STALL_X,
                                        act=act, lookahead=False)
        assert t_off > t_on, act
    t_on = iteration_time_wave(STALL_W, STALL_M, 8, 2, 0.4, STALL_X)
    t_off = iteration_time_wave(STALL_W, STALL_M, 8, 2, 0.4, STALL_X,
                                lookahead=False)
    assert t_off > t_on
    t_on = iteration_time_vertical_dp(STALL_W, STALL_M, 8, 0.4, STALL_X,
                                      R=2)
    t_off = iteration_time_vertical_dp(STALL_W, STALL_M, 8, 0.4, STALL_X,
                                       R=2, lookahead=False)
    assert t_off > t_on
    # fully CPU-resident storage has nothing to stall on
    x1 = StorageRatios(1.0, 1.0, 1.0, act=1.0)
    assert iteration_time_vertical(STALL_W, STALL_M, 8, 0.4, x1) == \
        iteration_time_vertical(STALL_W, STALL_M, 8, 0.4, x1,
                                lookahead=False)


def test_lp_rows_price_the_hint_free_executor():
    from repro.core.lp_search import solve_config

    s_on = solve_config(STALL_M, STALL_W, 8, 0.4)
    s_off = solve_config(STALL_M, STALL_W, 8, 0.4, lookahead=False)
    assert s_on is not None and s_off is not None
    assert s_off.iteration_time >= s_on.iteration_time
    # the hint-free spill row carries the residual-fetch stall too
    s_sp_off = solve_config(STALL_M, STALL_W, 8, 0.4, act_policy="spill",
                            lookahead=False)
    s_sp_on = solve_config(STALL_M, STALL_W, 8, 0.4, act_policy="spill")
    assert s_sp_off.iteration_time >= s_sp_on.iteration_time
    # auto still resolves under both pricings
    s_auto = solve_config(STALL_M, STALL_W, 8, 0.4, act_policy="auto",
                          lookahead=False)
    assert s_auto is not None


def test_workload_grid_monotone_under_stall_pricing():
    """More CPU residency can only shrink the hint-free stall terms."""
    t = [iteration_time_vertical(
            STALL_W, STALL_M, 8, 0.4,
            dataclasses.replace(X0, ckpt=c, opt=c), lookahead=False)
         for c in (0.0, 0.5, 1.0)]
    assert t[0] >= t[1] >= t[2]


def test_engine_stats_reset():
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, OffloadConfig(
            num_microbatches=2, micro_batch=MB, seq_len=S, alpha=0.5,
            ratios=X0), jax.random.PRNGKey(0), d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(2 * MB, S))
        assert eng.metrics_snapshot()["lookahead"]["stall_s"] > 0
        eng.reset_stats()
        look = eng.metrics_snapshot()["lookahead"]
        assert look["stall_s"] == 0 and look["hits"] == 0
        assert look["hit_rate"] == 1.0
        eng.finish()
        eng.close()
