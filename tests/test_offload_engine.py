"""End-to-end tests of the SSD-offload engine against the paper's
traffic model and the schedule-equivalence identity."""
import dataclasses
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.lp_search import solve_config
from repro.core.perfmodel import MachineParams, StorageRatios, Workload
from repro.data import SyntheticLM
from repro.offload import IOConfig, OffloadConfig, OffloadEngine

CFG = get_config("gpt-tiny")
M, MB, S = 4, 2, 64


def _run(schedule, alpha=0.0, ratios=StorageRatios(0.5, 0.5, 0.0), steps=2,
         seed=0):
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, OffloadConfig(
            schedule=schedule, num_microbatches=M, micro_batch=MB, seq_len=S,
            alpha=alpha, ratios=ratios), jax.random.PRNGKey(7), d)
        data = SyntheticLM(CFG.vocab_size, seed=seed)
        eng.meter.reset()
        losses = [eng.train_step(data.batch(M * MB, S)) for _ in range(steps)]
        eng.finish()
        routes = dict(eng.meter.bytes)
        eng.close()
        return losses, routes, eng


def test_vertical_equals_horizontal_loss():
    lv, _, _ = _run("vertical")
    lh, _, _ = _run("horizontal")
    np.testing.assert_allclose(lv, lh, atol=1e-4)


@pytest.mark.parametrize("alpha", [0.2, 0.5])
def test_alpha_delay_loss_identical(alpha):
    l0, _, _ = _run("vertical", alpha=0.0)
    la, _, _ = _run("vertical", alpha=alpha)
    np.testing.assert_allclose(l0, la, atol=1e-4)


def test_vertical_traffic_matches_formula():
    """§3.4: params loaded 2x per iteration (GPU loads), grads moved once."""
    _, routes, eng = _run("vertical", steps=3)
    ms = eng.L * eng.P * 4          # f32 params bytes
    # params: cpu->gpu == 2 * ms per iteration
    assert routes[("param", "cpu->gpu")] == 3 * 2 * ms
    # grads: gpu->cpu == 1 * ms (f32) per iteration, never fetched back
    assert routes[("grad", "gpu->cpu")] == 3 * ms
    assert ("grad", "cpu->gpu") not in routes


def test_horizontal_traffic_matches_formula():
    """§1: params 2M x ms; grad buffer (2M-1) x ms_f32."""
    _, routes, eng = _run("horizontal", steps=3)
    ms = eng.L * eng.P * 4
    assert routes[("param", "cpu->gpu")] == 3 * 2 * M * ms
    grad_total = routes[("grad", "gpu->cpu")] + routes[("grad", "cpu->gpu")]
    assert grad_total == 3 * (2 * M - 1) * ms


def test_vertical_param_traffic_independent_of_M():
    """The core §3.4 claim: vertical parameter traffic does not scale
    with the number of micro-batches."""
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, OffloadConfig(
            schedule="vertical", num_microbatches=8, micro_batch=1,
            seq_len=S), jax.random.PRNGKey(7), d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.meter.reset()
        eng.train_step(data.batch(8, S))
        eng.finish()
        p8 = eng.meter.bytes[("param", "cpu->gpu")]
        eng.close()
    _, routes, eng2 = _run("vertical", steps=1)
    assert p8 == routes[("param", "cpu->gpu")] == 2 * eng2.L * eng2.P * 4


def test_boundary_microbatch_ckpt_stays_on_device(monkeypatch):
    """§4.2: the alternating micro-batch order keeps each boundary's
    last-produced checkpoint (and inter-layer gradient) on device, so the
    measured ckpt bytes equal the exact closed form "read twice minus the
    on-device boundary micro-batch" — and perturbing the order evicts
    exactly one micro-batch per interior boundary."""
    from repro.core.traffic import vertical_ckpt_traffic
    from repro.offload import OffloadEngine

    _, routes, eng = _run("vertical", steps=1)
    u = MB * S * CFG.d_model * 4          # one boundary tensor, f32
    ct = vertical_ckpt_traffic(eng.L * u, M, eng.L)
    assert routes[("ckpt", "gpu->cpu")] == ct.write
    assert routes[("ckpt", "cpu->gpu")] == ct.read
    ig = routes[("inter_grad", "gpu->cpu")] \
        + routes[("inter_grad", "cpu->gpu")]
    assert ig == ct.inter_grad

    # Perturb the order (always ascending): every interior boundary's
    # kept micro-batch is now consumed LAST, so the device slot is lost
    # and the engine pays the re-read / spill the §4.2 order avoids.
    monkeypatch.setattr(OffloadEngine, "_mb_order",
                        lambda self, l: list(range(M)))
    _, bad, _ = _run("vertical", steps=1)
    extra_read = bad[("ckpt", "cpu->gpu")] - ct.read
    extra_ig = (bad[("inter_grad", "gpu->cpu")]
                + bad[("inter_grad", "cpu->gpu")]) - ct.inter_grad
    assert (extra_read, extra_ig) == (eng.L * u, 2 * eng.L * u), (
        f"perturbed _mb_order: expected exactly {eng.L} evicted boundary "
        f"checkpoints (+{eng.L * u} read bytes) and {eng.L} spilled "
        f"inter-layer gradients (+{2 * eng.L * u} bytes); measured "
        f"+{extra_read} ckpt-read and +{extra_ig} inter-grad bytes")


def test_ssd_files_actually_used():
    """With x=0 everything lives on SSD: files must be read and written."""
    _, routes, _ = _run("vertical", ratios=StorageRatios(0.0, 0.0, 0.0),
                        steps=1)
    assert routes[("param", "ssd->cpu")] > 0
    assert routes[("opt", "ssd->cpu")] > 0
    assert routes[("opt", "cpu->ssd")] > 0
    assert routes[("ckpt", "cpu->ssd")] > 0


def test_striped_multipath_loss_and_traffic_identical():
    """Striping the SSD tier over several paths is a pure I/O-layout
    change: losses and byte counters must match the single-path run."""
    l1, r1, _ = _run("vertical")
    with tempfile.TemporaryDirectory() as d:
        paths = [os.path.join(d, f"nvme{i}") for i in range(3)]
        eng = OffloadEngine(CFG, OffloadConfig(
            schedule="vertical", num_microbatches=M, micro_batch=MB,
            seq_len=S, ratios=StorageRatios(0.5, 0.5, 0.0),
            io=IOConfig(paths=paths, chunk_bytes=1 << 16)),
            jax.random.PRNGKey(7), d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.meter.reset()
        l3 = [eng.train_step(data.batch(M * MB, S)) for _ in range(2)]
        eng.finish()
        r3 = dict(eng.meter.bytes)
        eng.close()
        for p in paths:
            assert os.listdir(p) == []       # close() cleaned every path
    np.testing.assert_allclose(l1, l3, atol=1e-5)
    assert r1 == r3


def test_host_peak_within_lp_budget():
    """Algorithm 1's LP sizes the CPU tier; the vertical schedule's
    measured peak host residency must respect the LP's memory cap."""
    with tempfile.TemporaryDirectory() as d:
        probe = OffloadEngine(CFG, OffloadConfig(
            num_microbatches=M, micro_batch=MB, seq_len=S),
            jax.random.PRNGKey(7), d)
        L, P = probe.L, probe.P
        probe.close()
    # engine quantities: params/ckpts are f32 on this container
    w = Workload(ms=L * P * 4, cs=L * MB * S * CFG.d_model * 4,
                 os_bytes=3 * L * P * 4, grad_bytes=L * P * 4,
                 flops_per_mb=1e9, tokens_per_mb=MB * S, n_layers=L)
    full = M * w.cs + w.ms + w.os_bytes + w.grad_transient
    m = dataclasses.replace(MachineParams(), cpu_mem=0.6 * full / 0.95)
    sol = solve_config(m, w, M, 0.0)
    assert sol is not None
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, OffloadConfig(
            schedule="vertical", num_microbatches=M, micro_batch=MB,
            seq_len=S, ratios=sol.x), jax.random.PRNGKey(7), d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        for _ in range(2):
            eng.train_step(data.batch(M * MB, S))
        eng.finish()
        peak = eng.host.peak_nbytes
        assert eng.traffic()[0]["host:peak_nbytes"] == peak
        eng.close()
    # allowance: per-boundary transients (current-layer full tails,
    # inter-layer grads) the LP's steady-state model excludes
    budget = 0.95 * m.cpu_mem + w.cs
    assert 0 < peak <= budget, (peak / 1e6, budget / 1e6)


def test_loss_decreases_offloaded():
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, OffloadConfig(
            schedule="vertical", num_microbatches=M, micro_batch=4,
            seq_len=S, alpha=0.3, lr=3e-3), jax.random.PRNGKey(7), d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        losses = [eng.train_step(data.batch(M * 4, S)) for _ in range(25)]
        eng.finish()
        eng.close()
    assert np.mean(losses[-5:]) < losses[0] - 0.5, losses
