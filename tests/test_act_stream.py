"""Activation-stream battery (the SSDTrain-style spill policy).

The acceptance sweep: the three-way exact cross-check — ``plan_traffic``
== ``traffic.act_spill_traffic`` / ``wave_ckpt_traffic(act_spill=True)``
closed forms == measured engine counters — over vertical / horizontal /
wave × M ∈ {1, 2, 4} × policy ∈ {recompute, spill} × R ∈ {1, 2}, with
spill runs pinned BITWISE-identical (f32) to recompute runs in losses
and parameters. Plus: compiler/lookahead units for the new ops, the
``IOPriority.ACT`` class, the ``ActivationCoordinator`` round-trip, the
"auto" policy resolution, and the ``lp_search`` policy row.
"""
import tempfile

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.lp_search import solve_config
from repro.core.perfmodel import (MachineParams, StorageRatios, Workload,
                                  iteration_time_vertical,
                                  pick_activation_policy)
from repro.core.plan import (Op, PlanCosts, PlanSpec, compile_wave,
                             insert_prefetch, plan_traffic)
from repro.core.traffic import (act_spill_traffic, dp_vertical_traffic,
                                wave_ckpt_traffic)
from repro.data import SyntheticLM
from repro.io import CATEGORY_PRIORITY, IOPriority
from repro.offload import OffloadConfig, OffloadEngine, make_engine

CFG = ArchConfig(name="act-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S = 1, 16
X0 = StorageRatios(0.0, 0.0, 0.0)


def _run(policy, sched, M, W=0, alpha=0.0, ranks=0, steps=2,
         ratios=X0, seed=7):
    """(losses, per-iter measured routes, plan_traffic prediction,
    full low-precision params, act_nbytes) for one engine run."""
    ocfg = OffloadConfig(schedule=sched, num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=alpha,
                         wave_size=W, ratios=ratios,
                         activation_policy=policy)
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, ocfg, jax.random.PRNGKey(seed), d,
                          num_ranks=max(ranks, 1))
        meters = [rk.meter for rk in eng.ranks]
        data = SyntheticLM(CFG.vocab_size, seed=0)
        losses = [eng.train_step(data.batch(M * MB, S))
                  for _ in range(steps)]
        eng.finish()
        measured = [{k: v / steps for k, v in m.bytes.items()}
                    for m in meters]
        pred = plan_traffic(eng._plan, PlanCosts.from_engine(eng))
        params = [eng.read_params(l) for l in range(eng.L)]
        A = eng.act_nbytes
        assert eng.act_fallbacks == 0      # clean runs never degrade
        eng.close()
    if not ranks:
        measured, pred = measured[0], pred
    return losses, measured, pred, params, A


def _closed_form_spill(L, P, M, W, A):
    """Exact (category, route) bytes for the f32 spill engine at
    x = (0,0,0,0): the act stream (act_spill_traffic) + the ckpt forms
    with backward re-reads gone + the unchanged param/grad/opt forms."""
    ms = L * P * 4
    u = MB * S * CFG.d_model * 4
    nw = M // W
    ct = wave_ckpt_traffic(L * u, M, W, L, act_spill=True)
    at = act_spill_traffic(A, M, L)
    exp = {
        ("param", "ssd->cpu"): 2 * nw * ms,
        ("param", "cpu->gpu"): 2 * nw * ms,
        ("param", "cpu->ssd"): ms,
        ("grad", "gpu->cpu"): nw * ms,
        ("grad", "cpu->gpu"): (nw - 1) * ms,
        ("opt", "ssd->cpu"): 3 * ms,
        ("opt", "cpu->ssd"): 3 * ms,
        ("ckpt", "gpu->cpu"): ct.write,
        ("ckpt", "cpu->gpu"): ct.read,
        ("ckpt", "cpu->ssd"): ct.ssd_spill,
        ("ckpt", "ssd->cpu"): ct.ssd_reread,
        ("inter_grad", "gpu->cpu"): ct.inter_grad / 2,
        ("inter_grad", "cpu->gpu"): ct.inter_grad / 2,
        ("act", "gpu->cpu"): at.spill,
        ("act", "cpu->gpu"): at.fetch,
        ("act", "cpu->ssd"): at.ssd_spill,
        ("act", "ssd->cpu"): at.ssd_reread,
    }
    return {k: v for k, v in exp.items() if v}


# ---------------------------------------------------------------------------
# IR units: ops, compiler, lookahead
# ---------------------------------------------------------------------------

def test_act_priority_is_opportunistic():
    """ACT is the lowest class — below even deferrable ckpt spills —
    and the "act" meter category maps to it."""
    assert IOPriority.ACT > IOPriority.CKPT_SPILL
    assert max(IOPriority) == IOPriority.ACT
    assert CATEGORY_PRIORITY["act"] is IOPriority.ACT


@pytest.mark.parametrize("W", [1, 2, 4])
def test_spill_compiler_ops(W):
    """Spill plans carry one SPILL_ACT per (layer, micro-batch) right
    after its FWD, FETCH_ACT replaces FETCH_CKPT_BWD one-for-one, and
    recompute plans carry no act ops at all."""
    L, M = 3, 4
    spec = PlanSpec(L=L, M=M, act_spill=True)
    plan = compile_wave(spec, W)
    assert plan.count(Op.SPILL_ACT) == plan.count(Op.FETCH_ACT) == L * M
    assert plan.count(Op.FETCH_CKPT_BWD) == 0
    assert plan.count(Op.FWD) == plan.count(Op.BWD) == L * M
    ops = plan.ops
    for i, op in enumerate(ops):
        if op.op is Op.FWD:
            assert ops[i + 1].op is Op.SPILL_ACT
            assert (ops[i + 1].l, ops[i + 1].m) == (op.l, op.m)
    base = compile_wave(PlanSpec(L=L, M=M), W)
    for kind in (Op.SPILL_ACT, Op.FETCH_ACT, Op.PREFETCH_ACT):
        assert base.count(kind) == 0
    assert base.count(Op.FETCH_CKPT_BWD) == L * M


def test_act_prefetch_hints():
    """insert_prefetch derives exactly one PREFETCH_ACT per FETCH_ACT,
    placed before it and never across a RESET_PARAMS; the param hints
    are unchanged by the act pass."""
    L, M = 3, 4
    spec = PlanSpec(L=L, M=M, act_spill=True)
    plan = insert_prefetch(compile_wave(spec, M))
    assert plan.count(Op.PREFETCH_ACT) == plan.count(Op.FETCH_ACT) == L * M
    assert plan.count(Op.PREFETCH) == plan.count(Op.FETCH_PARAM)
    ops = plan.ops
    resets = {i for i, op in enumerate(ops) if op.op is Op.RESET_PARAMS}
    hints = {}
    for i, op in enumerate(ops):
        if op.op is Op.PREFETCH_ACT:
            assert (op.l, op.m) not in hints, "duplicate hint"
            hints[(op.l, op.m)] = i
        elif op.op is Op.FETCH_ACT:
            h = hints.pop((op.l, op.m))
            assert h < i, "hint after its fetch"
            assert not any(h < r < i for r in resets), \
                "act hint crosses RESET_PARAMS"
    assert not hints, "hints without a fetch"
    # recompute plans gain no act hints
    base = insert_prefetch(compile_wave(PlanSpec(L=L, M=M), M))
    assert base.count(Op.PREFETCH_ACT) == 0


def test_dp_closed_form_includes_act():
    """dp_vertical_traffic(act_bytes=A): per-rank act fields equal the
    per-rank act_spill_traffic closed form, and ckpt backward re-reads
    vanish."""
    ms, cs, M, R, L, A = 4096.0, 1024.0, 4, 2, 2, 300.0
    t = dp_vertical_traffic(ms, cs, M, R, n_layers=L, act_bytes=A)
    at = act_spill_traffic(A, M // R, L)
    assert (t.act.spill, t.act.fetch) == (at.spill, at.fetch)
    assert (t.act.ssd_spill, t.act.ssd_reread) == (at.ssd_spill,
                                                   at.ssd_reread)
    assert t.ckpt.read_bwd == t.ckpt.ssd_reread == 0.0
    assert t.ssd_read == 2 * ms / R + 6 * ms / R + at.ssd_reread
    # recompute form unchanged
    t0 = dp_vertical_traffic(ms, cs, M, R, n_layers=L)
    assert t0.act is None and t0.ckpt.read_bwd > 0


# ---------------------------------------------------------------------------
# the acceptance sweep: three-way cross-check + bitwise policy parity
# ---------------------------------------------------------------------------

SWEEP = [
    # (sched, M, W, alpha, ranks)
    ("vertical", 1, 0, 0.0, 0),
    ("vertical", 2, 0, 0.5, 0),
    ("vertical", 4, 0, 0.0, 0),
    ("horizontal", 1, 0, 0.0, 0),
    ("horizontal", 2, 0, 0.0, 0),
    ("horizontal", 4, 0, 0.5, 0),
    ("wave", 2, 1, 0.0, 0),
    ("wave", 4, 2, 0.5, 0),
    ("vertical", 2, 0, 0.0, 2),
    ("vertical", 4, 0, 0.5, 2),
]


@pytest.mark.parametrize("sched,M,W,alpha,ranks", SWEEP)
def test_spill_three_way_crosscheck_and_bitwise(sched, M, W, alpha, ranks):
    """For every cell: the spill run's measured counters equal the
    static plan_traffic prediction equal the closed forms, the
    recompute run still cross-checks, and the two policies' losses and
    final low-precision parameters are bitwise-identical (f32)."""
    lr, mr, pr, params_r, _ = _run("recompute", sched, M, W, alpha, ranks)
    ls, ms_, ps, params_s, A = _run("spill", sched, M, W, alpha, ranks)
    assert all(np.isfinite(ls))
    assert lr == ls, "spill changed the losses"
    for a, b in zip(params_r, params_s):
        assert (a == b).all(), "spill changed the parameters"
    if ranks:
        for r, (m, p) in enumerate(zip(ms_, ps)):
            assert m == p, f"rank {r} measured != predicted"
        assert mr == pr
    else:
        assert ms_ == ps, "spill measured != predicted"
        assert mr == pr, "recompute measured != predicted"
        Wr = {"vertical": M, "horizontal": 1}.get(sched, W)
        L = CFG.num_layers
        P = params_s[0].size
        assert ps == _closed_form_spill(L, P, M, Wr, A), \
            "plan_traffic != closed forms"


def test_dp_spill_acts_stay_on_owner_rank():
    """R=2: each rank's act counters cover exactly its own M/R
    micro-batches (the per-rank act_spill_traffic form), on its own
    meter — activation shards ride the owner's path set."""
    _, measured, _, _, A = _run("spill", "vertical", 4, ranks=2)
    L, Mr = CFG.num_layers, 2
    at = act_spill_traffic(A, Mr, L)
    for r, m in enumerate(measured):
        assert m[("act", "gpu->cpu")] == at.spill, f"rank {r}"
        assert m[("act", "cpu->ssd")] == at.ssd_spill, f"rank {r}"
        assert m[("act", "ssd->cpu")] == at.ssd_reread, f"rank {r}"
        assert ("ckpt", "ssd->cpu") not in m, "bwd ckpt re-read survived"


def test_spill_nonzero_ratios_crosscheck():
    """Partial CPU residency incl. an act head fraction: the analyzer's
    rounding matches the coordinator's exactly."""
    _, measured, pred, _, _ = _run(
        "spill", "vertical", 4,
        ratios=StorageRatios(0.5, 0.25, 0.5, act=0.3))
    assert measured == pred
    assert ("act", "cpu->ssd") in measured          # tail still spills
    assert measured[("act", "cpu->ssd")] < measured[("act", "gpu->cpu")]


def test_act_fully_host_resident_never_touches_ssd():
    _, measured, pred, _, _ = _run(
        "spill", "vertical", 2, ratios=StorageRatios(0.0, 0.0, 0.0,
                                                     act=1.0))
    assert measured == pred
    assert ("act", "cpu->ssd") not in measured
    assert ("act", "ssd->cpu") not in measured


# ---------------------------------------------------------------------------
# the auto policy: engine knob, perf model, LP row
# ---------------------------------------------------------------------------

# spill wins when compute is the bottleneck (slow GPU, fast storage);
# recompute wins when storage is (fast GPU, slow storage)
SLOW_GPU = MachineParams(gpu_flops=1e8, ssd_read_bw=50e9, ssd_write_bw=50e9,
                         pcie_bw=50e9, cpu_adam_bw=100e9)
FAST_GPU = MachineParams(gpu_flops=1e15, ssd_read_bw=0.5e9,
                         ssd_write_bw=0.25e9)


def _auto_engine_policy(machine):
    ocfg = OffloadConfig(schedule="vertical", num_microbatches=2,
                         micro_batch=MB, seq_len=S, ratios=X0,
                         activation_policy="auto", machine=machine)
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, ocfg, jax.random.PRNGKey(0), d)
        pol = eng.act_policy
        n_spill = eng._plan.count(Op.SPILL_ACT)
        eng.close()
    return pol, n_spill


def test_auto_policy_resolves_from_roofline():
    pol, n = _auto_engine_policy(SLOW_GPU)
    assert pol == "spill" and n > 0
    pol, n = _auto_engine_policy(FAST_GPU)
    assert pol == "recompute" and n == 0


def test_pick_activation_policy_directions():
    w = Workload(ms=2e9, cs=0.1e9, os_bytes=12e9, grad_bytes=4e9,
                 flops_per_mb=2e12, tokens_per_mb=4096, n_layers=8,
                 as_bytes=0.2e9)
    assert pick_activation_policy(w, SLOW_GPU, 8, 8, 0.0, X0) == "spill"
    assert pick_activation_policy(w, FAST_GPU, 8, 8, 0.0, X0) == "recompute"
    # pricing is consistent with the chooser
    t_re = iteration_time_vertical(w, SLOW_GPU, 8, 0.0, X0)
    t_sp = iteration_time_vertical(w, SLOW_GPU, 8, 0.0, X0, act="spill")
    assert t_sp < t_re


def test_lp_policy_row():
    """solve_config's activation row: explicit policies tag their
    solutions, and "auto" returns the faster of the two on both
    machine regimes."""
    w = Workload(ms=2e9, cs=0.1e9, os_bytes=12e9, grad_bytes=4e9,
                 flops_per_mb=2e12, tokens_per_mb=4096, n_layers=8,
                 as_bytes=0.2e9)
    for m, want in ((SLOW_GPU, "spill"), (FAST_GPU, "recompute")):
        s_re = solve_config(m, w, 8, 0.2, act_policy="recompute")
        s_sp = solve_config(m, w, 8, 0.2, act_policy="spill")
        s_auto = solve_config(m, w, 8, 0.2, act_policy="auto")
        assert s_re.act_policy == "recompute"
        assert s_sp.act_policy == "spill"
        assert s_auto.act_policy == want
        assert s_auto.iteration_time == min(s_re.iteration_time,
                                            s_sp.iteration_time)
    with pytest.raises(ValueError, match="act_policy"):
        solve_config(SLOW_GPU, w, 8, 0.2, act_policy="stream")


def test_unknown_engine_policy_rejected():
    # eager __post_init__ contract: the typo fails at CONSTRUCTION,
    # before any engine (or even a workdir) exists
    with pytest.raises(ValueError, match="activation_policy"):
        OffloadConfig(schedule="vertical", num_microbatches=2,
                      micro_batch=MB, seq_len=S,
                      activation_policy="nope")


# ---------------------------------------------------------------------------
# coordinator unit: byte-exact round trip (incl. 0-d scalar leaves)
# ---------------------------------------------------------------------------

def test_act_coordinator_roundtrip():
    import os

    from repro.io import IOConfig, IOEngine
    from repro.offload.coordinators import ActivationCoordinator
    from repro.offload.stores import HostStore, SSDStore, TrafficMeter

    with tempfile.TemporaryDirectory() as d:
        meter = TrafficMeter()
        ioe = IOEngine(IOConfig(paths=[os.path.join(d, "p")]), meter=meter)
        ssd = SSDStore(ioe.paths[0], meter, engine=ioe)
        host = HostStore(meter)
        co = ActivationCoordinator(0.25, host, ssd, meter, ioe)
        # a vjp-shaped pytree: mixed dtypes INCLUDING 0-d scalars (the
        # numpy ascontiguousarray 0-d -> (1,) promotion regression)
        tree = {"a": jax.numpy.arange(37, dtype=jax.numpy.float32),
                "idx": jax.numpy.asarray(np.int32(5)),
                "b": (jax.numpy.ones((3, 4), jax.numpy.float32),
                      jax.numpy.asarray(np.float32(2.5)))}
        co.put(1, 0, tree)
        co.prefetch(1, 0)
        got = co.get(1, 0)
        assert got["idx"].shape == () and int(got["idx"]) == 5
        assert float(got["b"][1]) == 2.5
        np.testing.assert_array_equal(np.asarray(got["a"]),
                                      np.asarray(tree["a"]))
        # fully consumed: nothing tracked, host head released
        assert co._n == {} and co._pending == {} and co._prefetched == {}
        assert host.nbytes() == 0
        nbytes = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
        assert meter.bytes[("act", "gpu->cpu")] == nbytes
        assert meter.bytes[("act", "cpu->gpu")] == nbytes
        tail = nbytes - int(round(0.25 * nbytes))
        assert meter.bytes[("act", "cpu->ssd")] == tail
        assert meter.bytes[("act", "ssd->cpu")] == tail
        ssd.close()
