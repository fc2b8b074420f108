"""Plan-executor battery: the three-way exact cross-check
(plan_traffic == traffic.* closed forms == engine measured counters)
over schedules × M × α, the wave schedule's end-to-end interpolation,
mid-plan fault cleanup, and the measured-bench → LP plumbing.
"""
import os
import sys
import tempfile

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import StorageRatios, machine_from_bench
from repro.core.plan import PlanCosts, plan_traffic
from repro.core.traffic import wave_ckpt_traffic
from repro.data import SyntheticLM
from repro.offload import OffloadConfig, OffloadEngine, make_engine

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CFG = ArchConfig(name="plan-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S = 1, 16


def _run(schedule, M, alpha, W=0, ranks=0, steps=2,
         ratios=StorageRatios(0.0, 0.0, 0.0), seed=7):
    """(losses, measured per-iter routes, plan_traffic prediction,
    (L, P, plan)) for one engine run with finish() drained."""
    ocfg = OffloadConfig(schedule=schedule, num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=alpha,
                         wave_size=W, ratios=ratios)
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
        shape = (eng.L, eng.P, eng._plan)
        eng.close()
    if not ranks:
        measured, pred = measured[0], [pred][0]
    return losses, measured, pred, shape


def _closed_form(L, P, M, W):
    """The exact (category, route) byte map for the f32 engine at
    x = (0,0,0): the wave_ckpt_traffic counters plus the param/grad/opt
    schedule forms (ms = L·P·4 here because params are f32, so f32
    grads == ms and optimizer state == 3·ms)."""
    ms = L * P * 4
    u = MB * S * CFG.d_model * 4
    nw = M // W
    ct = wave_ckpt_traffic(L * u, M, W, L)
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
    }
    return {k: v for k, v in exp.items() if v}


# ---------------------------------------------------------------------------
# the three-way exact cross-check (satellite: hypothesis-style sweep)
# ---------------------------------------------------------------------------

SWEEP = [(sched, M, alpha)
         for sched in ("vertical", "horizontal", "wave")
         for M in (1, 2, 4)
         for alpha in (0.0, 0.5)
         if not (sched == "wave" and M % 2)]


@pytest.mark.parametrize("sched,M,alpha", SWEEP)
def test_three_way_traffic_crosscheck(sched, M, alpha):
    """plan_traffic(plan) == wave closed forms == measured counters,
    EXACTLY, for every schedule/M/α cell — the IR, the analysis, and
    the running system agree byte-for-byte."""
    W = {"vertical": M, "horizontal": 1, "wave": 2}[sched]
    losses, measured, pred, (L, P, _) = _run(sched, M, alpha, W=W)
    assert all(np.isfinite(losses))
    want = _closed_form(L, P, M, W)
    assert pred == want, ("plan_traffic vs closed form", sched, M, alpha)
    assert measured == want, ("measured vs closed form", sched, M, alpha)


def test_three_way_crosscheck_nonzero_ratios():
    """With partial CPU residency (no closed form pinned at these
    ratios) the static prediction still matches the meters exactly —
    the analyzer replicates TieredVector's rounding."""
    for sched, W in (("vertical", 4), ("wave", 2), ("horizontal", 1)):
        _, measured, pred, _ = _run(sched, 4, 0.5, W=W,
                                    ratios=StorageRatios(0.5, 0.25, 0.5))
        assert measured == pred, sched


@pytest.mark.parametrize("alpha", [0.0, 0.5])
def test_dp_three_way_crosscheck(alpha):
    """R=2: per-rank measured counters == per-rank plan_traffic
    (ALLGATHER / REDUCE_SCATTER / ALLREDUCE_HEAD analyzer paths)."""
    _, measured, pred, _ = _run("vertical", 4, alpha, ranks=2)
    assert len(measured) == len(pred) == 2
    for r, (m, p) in enumerate(zip(measured, pred)):
        assert m == p, f"rank {r}"


# ---------------------------------------------------------------------------
# schedule semantics pinned by the executor
# ---------------------------------------------------------------------------

def test_horizontal_m1_equals_vertical_bitwise():
    """At M=1 the schedules coincide, and the compiled horizontal plan
    now reaches the optimizer: the pre-IR imperative horizontal engine
    parked the single micro-batch's layer gradients in host memory and
    never submitted them (its m==0 branch), silently freezing every
    pipelined layer. Regression-pin the fix as bitwise equality with
    the vertical engine."""
    lv, _, _, _ = _run("vertical", 1, 0.0, W=1, steps=3,
                       ratios=StorageRatios(0.5, 0.5, 0.0))
    lh, _, _, _ = _run("horizontal", 1, 0.0, W=1, steps=3,
                       ratios=StorageRatios(0.5, 0.5, 0.0))
    assert lv == lh, (lv, lh)
    # and training actually progresses: step-3 loss moved from step-1
    assert lh[2] != lh[0]


def test_wave_losses_invariant_across_W():
    """W only re-orders storage traffic, so step-1 losses (forward of
    identical parameters, identical per-micro-batch fold) are
    bit-identical across the whole knob. From step 2 on, equality is
    within jit rounding only: the cross-wave f32 accumulation GROUPS
    differently — vertical folds ((d0+d1)+d2)+d3 where a 2-wave run
    folds (d0+d1)+(d2+d3) via the parked partial — so the optimizer
    sees ulp-level-different sums. This was ALWAYS true (measured: the
    pre-IR fused backward's W=2 accumulators already differed from
    vertical's in ~2.4k elements); the old bitwise-loss pin held only
    because those ulp param deltas happened not to move the loss scalar
    with the fused backward's values. Per-micro-batch gradients ARE
    bitwise-invariant across W, and the spill/recompute policy axis is
    bitwise by construction — ``tests/test_act_stream.py``."""
    ref = None
    for sched, W in (("vertical", 4), ("wave", 2), ("horizontal", 1)):
        losses, _, _, _ = _run(sched, 4, 0.5, W=W)
        if ref is None:
            ref = losses
        else:
            assert losses[0] == ref[0], (sched, losses, ref)
            np.testing.assert_allclose(losses, ref, rtol=1e-5)


def test_wave_interpolates_measured_traffic():
    """The acceptance datapoint, on the live engine: sweeping W trades
    parameter reloads against checkpoint + inter-layer-gradient bytes
    monotonically, with the endpoints being the two paper schedules."""
    rows = {}
    for W in (1, 2, 4):
        _, measured, _, _ = _run("wave", 4, 0.0, W=W, steps=1)
        rows[W] = (
            measured.get(("param", "cpu->gpu"), 0),
            measured.get(("ckpt", "cpu->gpu"), 0)
            + measured.get(("inter_grad", "cpu->gpu"), 0)
            + measured.get(("inter_grad", "gpu->cpu"), 0))
    assert rows[1][0] > rows[2][0] > rows[4][0]
    assert rows[1][1] < rows[2][1] < rows[4][1]


# ---------------------------------------------------------------------------
# mid-plan fault cleanup (satellite bugfix)
# ---------------------------------------------------------------------------

def _faulty_engine(d, M=4):
    from repro.io import install_chaos

    eng = OffloadEngine(CFG, OffloadConfig(
        schedule="vertical", num_microbatches=M, micro_batch=MB, seq_len=S,
        ratios=StorageRatios(0.0, 0.0, 0.0)), jax.random.PRNGKey(3), d)
    install_chaos(eng.ssd)                   # init writes stay intact
    return eng


def _assert_clean(eng):
    assert eng.ckpt_c._device_kept == {}, "leaked device-kept tensors"
    assert eng.ckpt_c._pending == {}, "leaked in-flight spills"
    assert eng.params_c._futures == {}, "leaked param prefetches"
    assert eng.host.nbytes() == 0, "leaked host buffers"


def test_param_fetch_fault_releases_slots_and_recovers():
    """A failing parameter fetch early in the forward pass surfaces as
    the step's exception; the executor must release the already-kept
    embedding boundary tensors and cancel prefetches, and the engine
    must run a clean step afterwards."""
    with tempfile.TemporaryDirectory() as d:
        eng = _faulty_engine(d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.ssd.files.fail_reads = 1
        with pytest.raises(OSError, match="injected read fault"):
            eng.train_step(data.batch(4 * MB, S))
        _assert_clean(eng)
        loss = eng.train_step(data.batch(4 * MB, S))   # fuse expired
        assert np.isfinite(loss)
        eng.finish()
        _assert_clean(eng)
        eng.close()


def test_mid_backward_spill_fault_releases_slots():
    """A checkpoint-spill write fault surfaces mid-backward (when the
    recompute waits on the spill), with device-kept gradients live —
    exactly the state that used to leak across steps."""
    with tempfile.TemporaryDirectory() as d:
        eng = _faulty_engine(d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.ssd.files.fail_writes = 1
        with pytest.raises(OSError, match="injected write fault"):
            eng.train_step(data.batch(4 * MB, S))
        _assert_clean(eng)
        loss = eng.train_step(data.batch(4 * MB, S))
        assert np.isfinite(loss)
        eng.close()


# ---------------------------------------------------------------------------
# measured bench rates -> MachineParams -> Algorithm 1 (satellite)
# ---------------------------------------------------------------------------

SAMPLE = os.path.join(os.path.dirname(__file__), "data",
                      "bench_io_sample.json")


def test_machine_from_bench_roundtrip():
    import json

    with open(SAMPLE) as f:
        raw = json.load(f)
    m = machine_from_bench(SAMPLE)
    assert m.ssd_read_bw == max(v["read_bps"] for v in raw["paths"].values())
    assert m.ssd_write_bw == max(v["write_bps"]
                                 for v in raw["paths"].values())
    assert m.name.endswith("-bench")
    # dict input round-trips identically
    assert machine_from_bench(raw) == m
    # Algorithm 1 solves against the measured machine
    from repro.core.lp_search import solve_config
    from repro.core.perfmodel import Workload
    w = Workload(ms=2e9, cs=0.1e9, os_bytes=12e9, grad_bytes=4e9,
                 flops_per_mb=1e12, tokens_per_mb=4096)
    sol = solve_config(m, w, 8, 0.2)
    assert sol is not None and sol.iteration_time > 0
    # slower measured SSDs than the datasheet default => a longer
    # storage-bound iteration (sanity that the rates actually plug in)
    from repro.core.perfmodel import MachineParams
    sol_fast = solve_config(MachineParams(), w, 8, 0.2)
    assert sol.iteration_time >= sol_fast.iteration_time


def test_bench_engine_wave_smoke():
    """The CI plan-battery datapoint: bench_engine --schedule wave
    --smoke must run the three-plan sweep and assert the interpolation
    (under pytest-timeout like everything else here)."""
    from benchmarks import bench_engine

    bench_engine.main(["--schedule", "wave", "--smoke"])
