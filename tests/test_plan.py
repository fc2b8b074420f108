"""Schedule-IR unit battery: the canonical micro-batch order, the
vertical/horizontal/wave compilers, the PREFETCH lookahead pass, and the
static ``plan_traffic`` analyzer cross-checked against the closed forms
in ``repro.core.traffic`` — all without constructing an engine (the
engine-level three-way cross-check lives in ``test_plan_executor.py``).
"""
import types

import pytest

from repro.core.plan import (Op, PlanCosts, PlanSpec, compile_horizontal,
                             compile_vertical, compile_wave, insert_prefetch,
                             mb_order, plan_traffic, shard_bounds)
from repro.core.perfmodel import StorageRatios
from repro.core.traffic import dp_vertical_traffic, wave_ckpt_traffic

L, M = 3, 4
SPEC = PlanSpec(L=L, M=M)


# ---------------------------------------------------------------------------
# canonical micro-batch order (satellite: ONE implementation, pinned)
# ---------------------------------------------------------------------------

def test_mb_order_alternates():
    """§4.2 regression pin: even layers consume ascending, odd layers
    descending, so each boundary's producer emits in the reverse of its
    consumer's order."""
    assert mb_order(4, 0) == [0, 1, 2, 3]
    assert mb_order(4, 1) == [3, 2, 1, 0]
    assert mb_order(4, 2) == [0, 1, 2, 3]
    assert mb_order(1, 0) == [0] == mb_order(1, 1)
    for l in range(5):
        assert mb_order(6, l) == list(reversed(mb_order(6, l + 1)))
        assert sorted(mb_order(6, l)) == list(range(6))


def test_engines_delegate_to_canonical_order():
    """The engine's ``_mb_order`` — on every rank count — is the
    canonical repro.core.plan one (the duplicate module-level copy in
    offload.engine re-exports it)."""
    from repro.offload import engine as eng_mod
    from repro.offload.engine import OffloadEngine

    assert eng_mod.mb_order is mb_order
    stub = types.SimpleNamespace(
        ocfg=types.SimpleNamespace(num_microbatches=6))
    for l in range(4):
        assert OffloadEngine._mb_order(stub, l) == mb_order(6, l)


# ---------------------------------------------------------------------------
# compilers
# ---------------------------------------------------------------------------

def test_wave_specializations():
    v = compile_vertical(SPEC)
    h = compile_horizontal(SPEC)
    w = compile_wave(SPEC, 2)
    assert (v.schedule, h.schedule, w.schedule) == \
        ("vertical", "horizontal", "wave")
    assert v.ops == compile_wave(SPEC, M).ops
    assert h.ops == compile_wave(SPEC, 1).ops
    # params fetched twice per wave: 2·L·nw fetches
    for plan, nw in ((v, 1), (h, M), (w, 2)):
        assert plan.num_waves == nw
        assert plan.count(Op.FETCH_PARAM) == 2 * L * nw
        # every boundary (0..L) spilled for every micro-batch
        assert plan.count(Op.SPILL_CKPT) == (L + 1) * M
        assert plan.count(Op.FWD) == plan.count(Op.BWD) == L * M
        assert plan.count(Op.HEAD_BWD) == plan.count(Op.EMBED_FWD) == M
        assert plan.count(Op.WRITEBACK_GRAD) == L
        assert plan.count(Op.RESET_PARAMS) == nw
    # cross-wave f32 buffer swap: (nw-1) spills + (nw-1) fetches per layer
    assert v.count(Op.GRAD_SPILL) == v.count(Op.GRAD_FETCH_ACC) == 0
    assert h.count(Op.GRAD_SPILL) == h.count(Op.GRAD_FETCH_ACC) == L * (M - 1)
    assert w.count(Op.GRAD_SPILL) == w.count(Op.GRAD_FETCH_ACC) == L


def test_keep_flags_one_per_boundary_per_wave():
    for W in (1, 2, M):
        plan = compile_wave(SPEC, W)
        nw = M // W
        kept = [op for op in plan.ops if op.op is Op.SPILL_CKPT and op.keep]
        # one kept checkpoint per boundary per wave
        assert len(kept) == (L + 1) * nw
        kept_g = [op for op in plan.ops if op.op is Op.SPILL_GRAD and op.keep]
        assert len(kept_g) == (L + 1) * nw


def test_compile_validation():
    with pytest.raises(ValueError, match="divide"):
        compile_wave(SPEC, 3)
    with pytest.raises(ValueError, match="divide"):
        compile_wave(SPEC, 0)
    with pytest.raises(ValueError, match="vertical"):
        compile_wave(PlanSpec(L=2, M=4, ranks=2), 2)
    with pytest.raises(ValueError, match="ranks"):
        compile_vertical(PlanSpec(L=2, M=3, ranks=2))


def test_alpha_emits_gates_and_skips_wait():
    a = compile_vertical(PlanSpec(L=L, M=M, alpha=0.5))
    z = compile_vertical(SPEC)
    assert a.count(Op.OPT_LATE) == L and z.count(Op.OPT_LATE) == 0
    assert a.count(Op.WAIT_OPT) == 0 and z.count(Op.WAIT_OPT) == 1


def test_dp_plan_uses_collective_ops():
    plan = compile_vertical(PlanSpec(L=L, M=M, ranks=2))
    assert plan.count(Op.ALLGATHER) == 2 * L
    assert plan.count(Op.FETCH_PARAM) == 0
    assert plan.count(Op.REDUCE_SCATTER) == L
    assert plan.count(Op.WRITEBACK_GRAD) == 0
    assert plan.count(Op.FOLD_HEAD) == plan.count(Op.FOLD_EMBED) == 1
    assert plan.count(Op.ALLREDUCE_HEAD) == 1
    # rank-major emission: each layer's FWD micro-batches are the global
    # alternating order restricted to each rank's contiguous block
    fwd_l0 = [op.m for op in plan.ops if op.op is Op.FWD and op.l == 0]
    assert fwd_l0 == [0, 1, 2, 3]
    fwd_l1 = [op.m for op in plan.ops if op.op is Op.FWD and op.l == 1]
    assert fwd_l1 == [1, 0, 3, 2]      # descending within each rank block


# ---------------------------------------------------------------------------
# the PREFETCH lookahead pass
# ---------------------------------------------------------------------------

def _prefetched(plan):
    return [op.l for op in plan.ops if op.op is Op.PREFETCH]


def test_prefetch_one_hint_per_fetch_never_across_reset():
    for W in (1, 2, M):
        plan = insert_prefetch(compile_wave(SPEC, W))
        assert plan.count(Op.PREFETCH) == plan.count(Op.FETCH_PARAM)
        # a hint between a RESET_PARAMS and the next fetch must target
        # that next fetch's layer (no hint survives a reset)
        ops = plan.ops
        for i, op in enumerate(ops):
            if op.op is not Op.RESET_PARAMS:
                continue
            tail = ops[i + 1:]
            hint = next(o for o in tail if o.op is Op.PREFETCH)
            fetch = next(o for o in tail if o.op is Op.FETCH_PARAM)
            assert hint.l == fetch.l == L - 1


def test_prefetch_two_stage_pipeline_order():
    plan = insert_prefetch(compile_vertical(SPEC))
    ops = plan.ops
    # opening: PREFETCH(0) before any compute op
    assert ops[0].op is Op.PREFETCH and ops[0].l == 0
    # after FETCH_PARAM(l) the very next op is the NEXT fetch's hint,
    # for every fetch that still has a successor in its segment
    # (forward: l+1; backward: l-1; the plan's last fetch has none)
    fetches = [(i, op) for i, op in enumerate(ops)
               if op.op is Op.FETCH_PARAM]
    reset_at = next(i for i, op in enumerate(ops)
                    if op.op is Op.RESET_PARAMS)
    for i, op in fetches:
        expect = op.l + 1 if i < reset_at else op.l - 1
        if 0 <= expect < L:
            nxt = ops[i + 1]
            assert nxt.op is Op.PREFETCH and nxt.l == expect, (i, nxt)


def test_alpha_tail_epilogue_seam():
    """The cross-iteration seam: the α-tail OPT_LATE flushes are
    emitted in the plan EPILOGUE (after the last backward writeback),
    each preceded by exactly one PREFETCH_OPT hint placed at its
    layer's WRITEBACK_GRAD — so iteration i's tail flush and state
    reads are in flight together with iteration i+1's first param
    fetches, whose hints sit at plan START (the fetch gate, runtime
    state re-armed by each OPT_LATE, enforces flush-before-fetch)."""
    plan = insert_prefetch(compile_vertical(PlanSpec(L=L, M=M, alpha=0.3)))
    kinds = [op.op for op in plan.ops]
    assert plan.count(Op.OPT_LATE) == plan.count(Op.PREFETCH_OPT) == L
    # next iteration's first param hint is the very first op
    assert kinds.index(Op.PREFETCH) == 0
    # every OPT_LATE sits after the last WRITEBACK_GRAD (the epilogue)
    last_wb = max(i for i, k in enumerate(kinds)
                  if k is Op.WRITEBACK_GRAD)
    assert min(i for i, k in enumerate(kinds) if k is Op.OPT_LATE) > last_wb
    # each PREFETCH_OPT(l) follows its layer's WRITEBACK_GRAD(l) and
    # precedes its OPT_LATE(l)
    for l in range(L):
        wb = next(i for i, op in enumerate(plan.ops)
                  if op.op is Op.WRITEBACK_GRAD and op.l == l)
        hint = next(i for i, op in enumerate(plan.ops)
                    if op.op is Op.PREFETCH_OPT and op.l == l)
        late = next(i for i, op in enumerate(plan.ops)
                    if op.op is Op.OPT_LATE and op.l == l)
        assert wb < hint < late, (l, wb, hint, late)
    # α = 0 plans carry neither
    z = insert_prefetch(compile_vertical(SPEC))
    assert z.count(Op.OPT_LATE) == z.count(Op.PREFETCH_OPT) == 0


# ---------------------------------------------------------------------------
# static traffic analyzer vs closed forms (no engine, exact)
# ---------------------------------------------------------------------------

P, E = 1000, 64            # per-layer param elements / boundary elements
COSTS = PlanCosts(P=P, param_itemsize=4, ckpt_elems=E, act_itemsize=4,
                  ratios=StorageRatios(0.0, 0.0, 0.0), alpha=0.0)


def _expected(W, alpha=0.0):
    """The closed-form (category, route) map for the f32 engine at
    x = (0, 0, 0): ms = L·P·4 (params are f32 here), grads f32 = ms,
    optimizer state = 3·ms, ckpt unit u = E·4."""
    ms = L * P * 4
    u = E * 4
    nw = M // W
    ct = wave_ckpt_traffic(L * u, M, W, L)
    exp = {
        ("param", "ssd->cpu"): 2 * nw * ms,
        ("param", "cpu->gpu"): 2 * nw * ms,
        ("param", "cpu->ssd"): ms,
        ("grad", "gpu->cpu"): nw * ms,
        ("opt", "ssd->cpu"): 3 * ms,
        ("opt", "cpu->ssd"): 3 * ms,
        ("ckpt", "gpu->cpu"): ct.write,
        ("ckpt", "cpu->gpu"): ct.read,
        ("ckpt", "cpu->ssd"): ct.ssd_spill,
        ("ckpt", "ssd->cpu"): ct.ssd_reread,
        ("inter_grad", "gpu->cpu"): ct.inter_grad / 2,
        ("inter_grad", "cpu->gpu"): ct.inter_grad / 2,
    }
    if nw > 1:
        exp[("grad", "cpu->gpu")] = (nw - 1) * ms
    return {k: v for k, v in exp.items() if v}


@pytest.mark.parametrize("W", [1, 2, 4])
@pytest.mark.parametrize("alpha", [0.0, 0.5])
def test_plan_traffic_matches_closed_forms(W, alpha):
    spec = PlanSpec(L=L, M=M, alpha=alpha)
    costs = PlanCosts(P=P, param_itemsize=4, ckpt_elems=E, act_itemsize=4,
                      ratios=StorageRatios(0.0, 0.0, 0.0), alpha=alpha)
    got = plan_traffic(insert_prefetch(compile_wave(spec, W)), costs)
    assert got == _expected(W, alpha)


def test_plan_traffic_wave_interpolates():
    """The wave knob's trade: ckpt re-reads + inter-layer gradients grow
    with W while parameter (re)loads shrink — wave W=2 sits strictly
    between horizontal and vertical on both axes."""
    t = {W: plan_traffic(compile_wave(SPEC, W), COSTS) for W in (1, 2, 4)}

    def g(W, key):
        return t[W].get(key, 0)

    assert g(1, ("param", "cpu->gpu")) > g(2, ("param", "cpu->gpu")) \
        > g(4, ("param", "cpu->gpu"))
    assert g(1, ("ckpt", "cpu->gpu")) < g(2, ("ckpt", "cpu->gpu")) \
        < g(4, ("ckpt", "cpu->gpu"))
    assert g(1, ("inter_grad", "cpu->gpu")) == 0
    assert g(2, ("inter_grad", "cpu->gpu")) \
        < g(4, ("inter_grad", "cpu->gpu"))


def test_plan_traffic_predicts_eviction_penalty():
    """Compiling from a PERTURBED order (always ascending) costs exactly
    one evicted checkpoint re-read per interior boundary and one spilled
    inter-layer gradient round trip — the §4.2 closed-form penalty the
    engine-level boundary test measures."""
    good = plan_traffic(compile_vertical(SPEC), COSTS)
    bad = plan_traffic(
        compile_vertical(SPEC, order=lambda l: list(range(M))), COSTS)
    u = E * 4
    assert bad[("ckpt", "cpu->gpu")] - good[("ckpt", "cpu->gpu")] == L * u
    ig_good = good[("inter_grad", "gpu->cpu")] \
        + good[("inter_grad", "cpu->gpu")]
    ig_bad = bad[("inter_grad", "gpu->cpu")] \
        + bad[("inter_grad", "cpu->gpu")]
    assert ig_bad - ig_good == 2 * L * u


@pytest.mark.parametrize("alpha", [0.0, 0.5])
def test_plan_traffic_dp_matches_closed_form(alpha):
    """The DP plan's per-rank prediction equals dp_vertical_traffic —
    statically, without building a 2-rank engine."""
    R = 2
    spec = PlanSpec(L=L, M=M, alpha=alpha, ranks=R)
    costs = PlanCosts(P=P, param_itemsize=4, ckpt_elems=E, act_itemsize=4,
                      ratios=StorageRatios(0.0, 0.0, 0.0), alpha=alpha,
                      ranks=R, head_nbytes=1 << 20)
    per_rank = plan_traffic(insert_prefetch(compile_vertical(spec)), costs)
    assert len(per_rank) == R
    ms = L * P * 4
    u = E * 4
    t = dp_vertical_traffic(ms, L * u, M, R, grad_bytes=ms, os_bytes=3 * ms,
                            n_layers=L)
    ring_head = 2 * (R - 1) * costs.head_nbytes // R
    for got in per_rank:
        want = {
            ("param", "cpu->gpu"): t.param_fetch,
            ("param", "ssd->cpu"): t.param_fetch,
            ("param", "net->gpu"): t.param_allgather,
            ("param", "gpu->net"): t.param_allgather,
            ("param", "cpu->ssd"): t.param_writeback,
            ("grad", "gpu->cpu"): t.grad_offload,
            ("grad", "net->gpu"): t.grad_reducescatter,
            ("grad", "gpu->net"): t.grad_reducescatter,
            ("opt", "ssd->cpu"): t.opt_read,
            ("opt", "cpu->ssd"): t.opt_write,
            ("ckpt", "gpu->cpu"): t.ckpt.write,
            ("ckpt", "cpu->gpu"): t.ckpt.read,
            ("ckpt", "cpu->ssd"): t.ckpt.ssd_spill,
            ("ckpt", "ssd->cpu"): t.ckpt.ssd_reread,
            ("inter_grad", "gpu->cpu"): t.ckpt.inter_grad / 2,
            ("inter_grad", "cpu->gpu"): t.ckpt.inter_grad / 2,
            ("head_grad", "gpu->net"): ring_head,
            ("head_grad", "net->gpu"): ring_head,
        }
        for key, expect in want.items():
            assert got.get(key, 0) == expect, (key, got.get(key, 0), expect)


def test_wave_traffic_endpoints_match_paper_schedules():
    """The smooth wave form's endpoints ARE the paper forms: W=M is
    vertical_traffic, W=1 is horizontal_traffic (in particular the
    backward recompute reads are never cancelled by the keep saving),
    and the wave LP accepts wave=n as vertical under data parallelism."""
    from repro.core.traffic import (horizontal_traffic, vertical_traffic,
                                    wave_traffic)
    ms, cs = 100.0, 10.0
    assert wave_traffic(ms, cs, 8, 8) == vertical_traffic(ms, cs, 8)
    assert wave_traffic(ms, cs, 8, 1) == horizontal_traffic(ms, cs, 8)
    w2 = wave_traffic(ms, cs, 8, 2)
    assert w2.ckpt_read == (2 * 8 - 4) * cs      # bwd reads all M mbs
    assert w2.inter_grad == 2 * (8 - 4) * cs

    from repro.core.lp_search import solve_config
    from repro.core.perfmodel import MachineParams, Workload
    w = Workload(ms=20e9, cs=0.5e9, os_bytes=120e9, grad_bytes=40e9,
                 flops_per_mb=2e9 * 2 * 4096, tokens_per_mb=4096)
    m = MachineParams()
    dp_none = solve_config(m, w, 8, 0.2, num_gpus=2)
    dp_wave = solve_config(m, w, 8, 0.2, num_gpus=2, wave=8)
    assert dp_none is not None and dp_wave == dp_none
    # a true wave under DP is an argument error, not infeasibility
    # (None strictly means the LP has no feasible point)
    with pytest.raises(ValueError, match="wave"):
        solve_config(m, w, 8, 0.2, num_gpus=2, wave=2)


def test_shard_bounds_cover_contiguously():
    for n, world in [(10, 2), (7, 3), (5, 5), (3, 4)]:
        b = shard_bounds(n, world)
        assert b[0][0] == 0 and b[-1][1] == n
        assert all(b[i][1] == b[i + 1][0] for i in range(world - 1))
        sizes = [hi - lo for lo, hi in b]
        assert max(sizes) - min(sizes) <= 1
