"""Data-parallel sharded offload: R ranks × R SSD path sets must be a
pure re-layout of the single-rank engine — bit-identical (f32) losses
and parameters — while every per-rank byte counter matches the
``dp_vertical_traffic`` closed forms exactly."""
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.perfmodel import StorageRatios
from repro.core.plan import Op, PlanSpec, compile_wave, insert_prefetch
from repro.core.traffic import dp_vertical_traffic
from repro.data import SyntheticLM
from repro.offload import IOConfig, OffloadConfig, make_engine, shard_bounds

CFG = get_config("gpt-tiny")
M, MB, S = 4, 2, 64


def _ocfg(alpha=0.0, ratios=StorageRatios(0.5, 0.5, 0.0), io=None):
    return OffloadConfig(schedule="vertical", num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=alpha,
                         ratios=ratios, io=io)


def _run(alpha, ranks, steps=2, ratios=StorageRatios(0.5, 0.5, 0.0),
         io=None):
    """(losses, per-rank route dicts, final per-layer param arrays,
    (L, P)) for a run on ``ranks`` ranks."""
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, _ocfg(alpha, ratios, io),
                          jax.random.PRNGKey(7), d, num_ranks=ranks)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        losses = [eng.train_step(data.batch(M * MB, S))
                  for _ in range(steps)]
        eng.finish()
        routes = [dict(rk.meter.bytes) for rk in eng.ranks]
        params = [eng.read_params(l) for l in range(eng.L)]
        shape = (eng.L, eng.P)
        eng.close()
        return losses, routes, params, shape


# ---------------------------------------------------------------------------
# bit-exact parity with the single-rank engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alpha", [0.0, 0.5])
def test_dp_bit_identical_to_single_rank(alpha):
    """R=2 sharded offload == single rank, bit-for-bit in f32: the
    ordered collectives and elementwise shard updates commute exactly
    with the single-rank fold (§6.5 extended across the DP axis)."""
    l1, _, p1, _ = _run(alpha, ranks=1)
    l2, _, p2, _ = _run(alpha, ranks=2)
    assert l1 == l2, (l1, l2)                    # Python floats: bitwise
    for layer, (a, b) in enumerate(zip(p1, p2)):
        np.testing.assert_array_equal(a, b, err_msg=f"layer {layer}")


def test_dp_four_ranks_losses_match():
    l1, _, p1, _ = _run(0.0, ranks=1, steps=1)
    l4, _, p4, _ = _run(0.0, ranks=4, steps=1)
    assert l1 == l4
    for a, b in zip(p1, p4):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# exact per-rank byte counters vs the closed forms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alpha", [0.0, 0.5])
def test_dp_per_rank_counters_match_closed_form(alpha):
    steps, R = 2, 2
    _, per_rank, _, (L, P) = _run(alpha, ranks=R, steps=steps,
                                  ratios=StorageRatios(0.0, 0.0, 0.0))
    ms = L * P * 4                               # f32 engine
    cs = L * MB * S * CFG.d_model * 4
    t = dp_vertical_traffic(ms, cs, M, R, grad_bytes=ms, os_bytes=3 * ms,
                            n_layers=L)
    for r, routes in enumerate(per_rank):
        got = {k: v / steps for k, v in routes.items()}
        want = {
            ("param", "cpu->gpu"): t.param_fetch,
            ("param", "ssd->cpu"): t.param_fetch,      # x_param = 0
            ("param", "net->gpu"): t.param_allgather,
            ("param", "gpu->net"): t.param_allgather,  # even shards
            ("param", "cpu->ssd"): t.param_writeback,
            ("grad", "gpu->cpu"): t.grad_offload,
            ("grad", "net->gpu"): t.grad_reducescatter,
            ("grad", "gpu->net"): t.grad_reducescatter,
            ("opt", "ssd->cpu"): t.opt_read,
            ("opt", "cpu->ssd"): t.opt_write,
            ("ckpt", "gpu->cpu"): t.ckpt.write,
            ("ckpt", "cpu->gpu"): t.ckpt.read,
            ("ckpt", "cpu->ssd"): t.ckpt.ssd_spill,    # x_ckpt = 0
            ("ckpt", "ssd->cpu"): t.ckpt.ssd_reread,
            ("inter_grad", "gpu->cpu"): t.ckpt.inter_grad / 2,
            ("inter_grad", "cpu->gpu"): t.ckpt.inter_grad / 2,
        }
        for key, expect in want.items():
            assert got.get(key, 0) == expect, (
                f"rank {r} {key}: measured {got.get(key, 0)} per step, "
                f"closed form {expect}")


def test_single_rank_counters_match_r1_closed_form():
    """dp_vertical_traffic degenerates to the single-rank engine at R=1
    (no collectives, full shard)."""
    steps = 2
    _, (routes,), _, (L, P) = _run(0.0, ranks=1, steps=steps,
                                   ratios=StorageRatios(0.0, 0.0, 0.0))
    ms = L * P * 4
    cs = L * MB * S * CFG.d_model * 4
    t = dp_vertical_traffic(ms, cs, M, 1, grad_bytes=ms, os_bytes=3 * ms,
                            n_layers=L)
    assert t.param_allgather == t.grad_reducescatter == 0
    assert routes[("param", "cpu->gpu")] / steps == t.param_fetch
    assert routes[("grad", "gpu->cpu")] / steps == t.grad_offload
    assert routes[("opt", "ssd->cpu")] / steps == t.opt_read
    assert routes[("opt", "cpu->ssd")] / steps == t.opt_write
    assert routes[("ckpt", "cpu->gpu")] / steps == t.ckpt.read
    assert routes[("ckpt", "ssd->cpu")] / steps == t.ckpt.ssd_reread


# ---------------------------------------------------------------------------
# rank / path layout
# ---------------------------------------------------------------------------

def test_dp_ranks_drive_disjoint_path_sets():
    """With an explicit path list, IOConfig.shard_for_rank hands rank r
    paths r, r+R, ...: stripes must land only on the owning rank's
    paths, and close() must clean every path."""
    with tempfile.TemporaryDirectory() as d:
        paths = [os.path.join(d, f"nvme{i}") for i in range(4)]
        eng = make_engine(
            CFG, _ocfg(io=IOConfig(paths=paths, chunk_bytes=1 << 16)),
            jax.random.PRNGKey(7), d, num_ranks=2)
        assert [list(rk.ioe.paths) for rk in eng.ranks] == \
            [[paths[0], paths[2]], [paths[1], paths[3]]]
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(M * MB, S))
        eng.finish()
        for p in paths:
            assert os.listdir(p), f"no stripes on {p}"
        eng.close()
        for p in paths:
            assert os.listdir(p) == [], f"close() left stripes on {p}"


def _thread_names(ioe):
    return [t.name for w in (ioe._front, *ioe._channels) for t in w._threads]


@pytest.mark.parametrize("schedule", ["vertical", "wave"])
def test_single_rank_runs_the_single_rank_plan_and_layout(schedule):
    """R = 1 compiles exactly the single-rank plan (no collective or fold
    op), keeps its stripes under the workdir itself and leaves its I/O
    threads unlabelled."""
    W = {"vertical": M, "wave": 2}[schedule]
    ocfg = OffloadConfig(schedule=schedule, wave_size=W, num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=0.25,
                         ratios=StorageRatios(0.5, 0.5, 0.5))
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, ocfg, jax.random.PRNGKey(7), d)
        spec = PlanSpec(L=eng.L, M=M, alpha=0.25, ranks=1)
        assert eng.plan == insert_prefetch(compile_wave(spec, W), depth=1)
        collective = {Op.ALLGATHER, Op.REDUCE_SCATTER, Op.ALLREDUCE_HEAD,
                      Op.FOLD_HEAD, Op.FOLD_EMBED}
        assert not any(op.op in collective for op in eng.plan.ops)
        assert (eng.R, eng.Mr, len(eng.ranks)) == (1, M, 1)
        assert eng.bounds == tuple([(0, n)] for n in eng.layer_P)
        assert eng.meter is eng.ranks[0].meter
        assert eng.p_vecs is eng.ranks[0].p_vecs
        assert list(eng.ioe.paths) == [d]
        names = _thread_names(eng.ioe)
        assert names and not any("rank" in n for n in names), names
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(M * MB, S))
        eng.finish()
        files = os.listdir(d)
        assert files and not any(f.startswith("rank") for f in files)
        eng.close()


def test_dp_wave_swap_is_refused_and_keeps_the_plan():
    """``apply_plan_config(wave_size=...)`` under R = 2: the plan
    compiler refuses a non-vertical data-parallel plan before anything
    changes, so the running plan and config stay as they were."""
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, _ocfg(alpha=0.5), jax.random.PRNGKey(7), d,
                          num_ranks=2)
        plan = eng.plan
        with pytest.raises(ValueError, match="vertical"):
            eng.apply_plan_config(wave_size=2)
        assert eng.plan is plan
        assert eng.ocfg.schedule == "vertical"
        assert [_thread_names(rk.ioe)[0] for rk in eng.ranks] == \
            ["rank0-io-req-0", "rank1-io-req-0"]
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(M * MB, S))
        eng.close()


@pytest.mark.parametrize("kw", [{"num_microbatches": 3},
                                {"schedule": "horizontal"}])
def test_dp_refusals_leave_no_file_or_thread(kw):
    """The R > 1 refusals fire before any file or thread exists."""
    import threading

    before = threading.active_count()
    ocfg = OffloadConfig(**{**dict(num_microbatches=M, micro_batch=MB,
                                   seq_len=S), **kw})
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError):
            make_engine(CFG, ocfg, jax.random.PRNGKey(7), d, num_ranks=2)
        assert os.listdir(d) == []
    assert threading.active_count() <= before


def test_shard_bounds_cover_contiguously():
    for n, world in [(10, 2), (7, 3), (5, 5), (3, 4)]:
        b = shard_bounds(n, world)
        assert b[0][0] == 0 and b[-1][1] == n
        assert all(b[i][1] == b[i + 1][0] for i in range(world - 1))
        sizes = [hi - lo for lo, hi in b]
        assert max(sizes) - min(sizes) <= 1


def test_dp_rejects_uneven_microbatches():
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="divide evenly"):
            make_engine(CFG, _ocfg(), jax.random.PRNGKey(7), d,
                        num_ranks=3)


def test_dp_aggregate_throughput_scales():
    """R=2 rank stacks with per-path SSD-speed pacing must deliver
    >= 1.6x the aggregate throughput of R=1 (the Fig. 10 storage leg;
    see benchmarks/bench_dp.py). Pacing is sleep-based, so the ratio is
    stable even on a loaded CI runner; best-of-3 guards the rest."""
    import time

    from repro.io import IOEngine, IOPriority
    from repro.offload.stores import SSDStore, TrafficMeter

    cap = 150e6
    nbytes = 16 << 20

    def measure(R):
        arr = np.zeros(nbytes, np.uint8)
        bounds = shard_bounds(nbytes, R)
        best = float("inf")
        with tempfile.TemporaryDirectory() as root:
            stacks = []
            for r in range(R):
                p = os.path.join(root, f"rank{r}")
                eng_r = IOEngine(IOConfig(paths=[p], chunk_bytes=1 << 20,
                                          bandwidth={"cpu->ssd": cap}))
                stacks.append(SSDStore(p, TrafficMeter(), engine=eng_r))
            shards = [arr[lo:hi] for lo, hi in bounds]
            for rep in range(3):
                t0 = time.perf_counter()
                reqs = [s.engine.submit(
                            (lambda s=s, sh=sh, rep=rep:
                             s.write(f"x{rep}", sh, "opt")),
                            priority=IOPriority.OPTIMIZER_STATE,
                            nbytes=sh.nbytes)
                        for s, sh in zip(stacks, shards)]
                for q in reqs:
                    q.result()
                best = min(best, time.perf_counter() - t0)
            for s in stacks:
                s.close()
        return nbytes / best

    r1, r2 = measure(1), measure(2)
    assert r2 / r1 >= 1.6, (
        f"aggregate write throughput R=1 {r1 / 1e6:.0f} MB/s -> "
        f"R=2 {r2 / 1e6:.0f} MB/s is only {r2 / r1:.2f}x (>= 1.6x "
        f"expected: the rank engines must drive their paths concurrently)")


# ---------------------------------------------------------------------------
# R-GPU performance model / LP
# ---------------------------------------------------------------------------

def test_dp_perfmodel_and_lp():
    import dataclasses

    from repro.core.lp_search import find_optimal_config, solve_config
    from repro.core.perfmodel import (MachineParams, Workload,
                                      iteration_time_vertical,
                                      iteration_time_vertical_dp,
                                      rooflines_dp)

    m = MachineParams()
    w = Workload(ms=20e9, cs=0.5e9, os_bytes=120e9, grad_bytes=40e9,
                 flops_per_mb=2e9 * 2 * 4096, tokens_per_mb=4096,
                 n_layers=32)
    x = StorageRatios(0.2, 0.2, 0.2)
    t1 = iteration_time_vertical(w, m, 8, 0.2, x)
    assert iteration_time_vertical_dp(w, m, 8, 0.2, x, R=1) == t1
    # storage-bound regime: 2 ranks with their own SSD paths must be
    # faster than 1, but no better than 2x (Amdahl + collectives)
    t2 = iteration_time_vertical_dp(w, m, 8, 0.2, x, R=2)
    assert t2 < t1
    assert t2 >= t1 / 2 - 1e-9
    # an interconnect-starved fabric becomes the binding roofline
    slow = dataclasses.replace(m, interconnect_bw=1e8)
    t2_slow = iteration_time_vertical_dp(w, slow, 8, 0.2, x, R=2)
    assert t2_slow >= 0.5 * (2 * w.ms + w.grad_bytes) / 1e8
    io_r, comp_r, ic_r = rooflines_dp(w, m, x, 4)
    io_1, comp_1, _ = rooflines_dp(w, m, x, 1)
    assert io_r == pytest.approx(io_1 / 4)       # R path sets: R x agg bw
    assert comp_r == pytest.approx(comp_1 * 4)
    # the DP LP: feasible, valid ratios, and it honours the
    # interconnect lower bound rows
    sol = solve_config(m, w, 8, 0.2, num_gpus=2)
    assert sol is not None
    assert sol.t_f >= 0.5 * w.ms / m.interconnect_bw - 1e-9
    assert sol.t_b >= 0.5 * (w.ms + w.grad_bytes) / m.interconnect_bw - 1e-9
    with pytest.raises(ValueError, match="divisible"):
        solve_config(m, w, 7, 0.2, num_gpus=2)   # n % R != 0 is an
    # argument error now — None strictly means LP-infeasible
    best = find_optimal_config(m, w, alphas=[0.0, 0.2], max_n=16,
                               num_gpus=2)
    assert best is not None and best.n % 2 == 0
