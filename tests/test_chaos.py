"""Chaos battery for the resilient I/O fabric (`repro.io.chaos`).

Four pins, in order of the fault-discipline ladder:

* **Integrity** — with ``IOConfig.integrity`` on, silent disk
  corruption (torn overwrites, bit flips) is caught at the next read
  as :class:`IntegrityError`; a torn FIRST write surfaces as the
  (permanent) short-read error because the file itself is short.
* **Retry** — probabilistic transient faults (EAGAIN) on every stream
  are absorbed by the engine's bounded retry, and an entire training
  run under transient chaos is BITWISE identical (losses and params)
  to its fault-free twin, across schedules, DP, and α — the
  acceptance grid.
* **Failover** — a path killed mid-run: complete-chunk overwrites
  (the caller's buffer is authoritative) re-place onto survivors and
  round-trip bitwise; placement drains off the dead device.
* **Unwind** — when a fault DOES escalate past retry and kills a
  step, the executor's failure path must leave the engine clean:
  no leaked budget/staging, no stale α gates, futures, or retained
  ``pending_grad`` tails — the next step (and a checkpoint restore)
  runs clean. Exercised as a sweep over error rates × activation
  policies, which drives faults through every priority class
  (PARAM_FETCH, OPTIMIZER_STATE, CKPT_SPILL, ACT).
"""
import os
import tempfile
import threading

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import StorageRatios
from repro.data import SyntheticLM
from repro.io import (ChaosFiles, ChaosSpec, IntegrityError, IOConfig,
                      IOEngine, install_chaos)
from repro.offload import (OffloadConfig, OffloadEngine, make_engine)
from repro.offload.stores import SSDStore, TrafficMeter

T = 5.0

CFG = ArchConfig(name="chaos-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S, M = 1, 16, 4


def _chaos_store(root, n_paths=1, spec=None, **cfg_kw):
    cfg_kw.setdefault("chunk_bytes", 1 << 10)
    if n_paths > 1:
        cfg_kw.setdefault("path_policy", "backlog")
    paths = [os.path.join(root, f"nvme{i}") for i in range(n_paths)]
    eng = IOEngine(IOConfig(paths=paths, **cfg_kw))
    ssd = SSDStore(paths[0], TrafficMeter(), engine=eng)
    files = install_chaos(ssd, spec)
    return eng, ssd, files


def _drainable(eng, nbufs=None):
    """Can the FULL staging pool be acquired (nothing leaked)?"""
    nbufs = nbufs if nbufs is not None else eng.config.staging_buffers
    got = threading.Event()

    def drain():
        bufs = [eng.staging.acquire(64) for _ in range(nbufs)]
        got.set()
        for b in bufs:
            b.release()

    t = threading.Thread(target=drain, daemon=True)
    t.start()
    ok = got.wait(T)
    t.join(T)
    return ok


# ---------------------------------------------------------------------------
# defaults + transient retry
# ---------------------------------------------------------------------------

def test_default_chaosfiles_is_plain_striped():
    """All knobs off: ChaosFiles is bit-for-bit a StripedFiles."""
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(d)
        arr = np.arange(2048, dtype=np.float32)
        ssd.write("t", arr, "opt")
        np.testing.assert_array_equal(ssd.read("t", "opt"), arr)
        assert all(v == 0 for v in files.injected.values())
        s = eng.metrics_snapshot()
        assert s["chunk_retries"] == 0 and s["chunk_failovers"] == 0
        ssd.close()


def test_transient_faults_absorbed_by_retry():
    """EAGAIN chaos on every chunk op: bounded retry absorbs it, the
    data round-trips bitwise, and nothing leaks."""
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(
            d, spec=ChaosSpec(error_rate=0.3, seed=7), retries=5)
        arr = np.arange(4096, dtype=np.float32)
        ssd.write("t", arr, "opt")
        np.testing.assert_array_equal(ssd.read("t", "opt"), arr)
        assert files.injected["transient"] > 0
        s = eng.metrics_snapshot()
        assert s["chunk_retries"] == files.injected["transient"]
        assert s["inflight_bytes"] == 0
        ssd.close()


def test_transient_fault_escalates_without_retry():
    """retries=0: the same transient fault propagates to the caller on
    the first attempt (classification does not imply retry)."""
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(
            d, spec=ChaosSpec(error_rate=1.0, seed=7), retries=0)
        with pytest.raises(OSError, match="injected transient"):
            ssd.write("t", np.zeros(256, np.float32), "opt")
        assert eng.metrics_snapshot()["inflight_bytes"] == 0
        ssd.close()


# ---------------------------------------------------------------------------
# integrity: silent corruption is caught at the next read
# ---------------------------------------------------------------------------

def test_torn_overwrite_detected_by_crc():
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(d, integrity=True)
        arr = np.arange(1024, dtype=np.float32)          # 4 chunks
        ssd.write("t", arr, "opt")
        np.testing.assert_array_equal(ssd.read("t", "opt"), arr)
        files.spec = ChaosSpec(torn_write_rate=1.0, seed=1)
        ssd.write("t", arr + 1.0, "opt")                 # tears land
        files.spec = ChaosSpec()
        with pytest.raises(IntegrityError, match="CRC32C mismatch"):
            ssd.read("t", "opt")
        assert files.injected["torn"] > 0
        assert eng.metrics_snapshot()["integrity_errors"] > 0
        ssd.close()


def test_bit_flip_detected_by_crc():
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(d, integrity=True)
        files.spec = ChaosSpec(bit_flip_rate=1.0, seed=2)
        ssd.write("t", np.arange(512, dtype=np.float32), "opt")
        files.spec = ChaosSpec()
        with pytest.raises(IntegrityError, match="CRC32C mismatch"):
            ssd.read("t", "opt")
        assert files.injected["flip"] > 0
        ssd.close()


def test_torn_first_write_is_a_short_read():
    """A torn FIRST write of a single-chunk tensor leaves the file
    physically short — caught by short-read detection (permanent, no
    CRC needed), not silently zero-padded."""
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(d, integrity=True)
        files.spec = ChaosSpec(torn_write_rate=1.0, seed=3)
        ssd.write("t", np.arange(256, dtype=np.float32), "opt")  # 1 chunk
        files.spec = ChaosSpec()
        with pytest.raises(IOError, match="short read"):
            ssd.read("t", "opt")
        ssd.close()


def test_integrity_off_means_no_verification():
    """Without the opt-in, the same bit flip goes UNDETECTED — the pin
    that verification (and its sidecar cost) is strictly opt-in."""
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(d)                # integrity off
        files.spec = ChaosSpec(bit_flip_rate=1.0, seed=2)
        arr = np.arange(512, dtype=np.float32)
        ssd.write("t", arr, "opt")
        files.spec = ChaosSpec()
        back = ssd.read("t", "opt")                      # no raise
        assert not np.array_equal(back, arr)             # corrupt bytes
        ssd.close()


# ---------------------------------------------------------------------------
# failover: a path killed mid-run
# ---------------------------------------------------------------------------

def test_midrun_path_kill_write_failover():
    """Kill one of two paths while a tensor is spread across both: the
    next full overwrite (caller buffer authoritative) re-places the
    dead path's chunks onto the survivor, round-trips bitwise, and the
    dead path is drained for future placement. No budget leak."""
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(d, n_paths=2, staging_buffers=2)
        arr = np.arange(2048, dtype=np.float32)          # 8 chunks
        ssd.write("t", arr, "opt")
        on1 = [c for c in range(8) if ssd.files.placement("t", c)[0] == 1]
        assert on1, "placement never used path 1"
        files.kill_path(1)
        arr2 = arr * 2.0
        ssd.write("t", arr2, "opt")                      # fails over
        np.testing.assert_array_equal(ssd.read("t", "opt"), arr2)
        assert all(ssd.files.placement("t", c)[0] == 0 for c in range(8))
        s = eng.metrics_snapshot()
        assert s["chunk_failovers"] >= len(on1)
        assert s["paths_drained"] == [False, True]
        assert s["inflight_bytes"] == 0
        assert _drainable(eng, 2)
        # NEW tensors avoid the drained path pre-emptively
        ssd.write("u", arr, "opt")
        assert all(ssd.files.placement("u", c)[0] == 0 for c in range(8))
        np.testing.assert_array_equal(ssd.read("u", "opt"), arr)
        ssd.close()


def test_all_paths_dead_is_loud():
    """When no survivor exists the failure is loud, not a hang or a
    silent success."""
    with tempfile.TemporaryDirectory() as d:
        eng, ssd, files = _chaos_store(d, n_paths=2)
        arr = np.arange(1024, dtype=np.float32)
        ssd.write("t", arr, "opt")
        files.kill_path(0)
        files.kill_path(1)
        with pytest.raises(OSError):
            ssd.write("t", arr + 1.0, "opt")
        assert eng.metrics_snapshot()["inflight_bytes"] == 0
        ssd.close()


# ---------------------------------------------------------------------------
# the acceptance grid: transient chaos on every stream => bitwise training
# ---------------------------------------------------------------------------

GRID = [("vertical", 0.5, 1), ("horizontal", 0.0, 1),
        ("wave", 0.5, 1), ("vertical", 0.5, 2)]


def _train(schedule, alpha, ranks, spec, steps=3):
    """Losses + final assembled params for a short run, chaos-injected
    on every rank's SSD stream when ``spec`` is given."""
    io = IOConfig(retries=5, integrity=True, chunk_bytes=1 << 10)
    kw = {"wave_size": 2} if schedule == "wave" else {}
    oc = OffloadConfig(schedule=schedule, num_microbatches=M,
                       micro_batch=MB, seq_len=S,
                       ratios=StorageRatios(0.5, 0.5, 0.5),
                       alpha=alpha, io=io, activation_policy="spill",
                       **kw)
    data = SyntheticLM(CFG.vocab_size, seed=0)
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, oc, jax.random.PRNGKey(0), d,
                          num_ranks=ranks)
        files = [install_chaos(rk.ssd, spec) for rk in eng.ranks] \
            if spec is not None else []
        losses = [eng.train_step(data.batch(M * MB, S))
                  for _ in range(steps)]
        eng.finish()
        params = [eng.read_params(l) for l in range(eng.L)]
        injected = sum(f.injected["transient"] for f in files)
        stats = eng.ranks[0].ioe.metrics_snapshot()
        eng.close()
    return losses, params, injected, stats


@pytest.mark.parametrize("schedule,alpha,ranks", GRID)
def test_transient_chaos_training_is_bitwise(schedule, alpha, ranks):
    """Transient faults + latency spikes on EVERY SSD stream: training
    is bitwise identical (losses and params) to the fault-free twin —
    a retried chunk op moves the same bytes to the same place, so
    recovery is invisible to the arithmetic."""
    spec = ChaosSpec(error_rate=0.05, latency_rate=0.05,
                     latency_s=0.0005, seed=11)
    ref_losses, ref_params, _, _ = _train(schedule, alpha, ranks, None)
    losses, params, injected, stats = _train(schedule, alpha, ranks, spec)
    assert injected > 0, "chaos never fired — the run proves nothing"
    assert stats["chunk_retries"] > 0
    assert losses == ref_losses, "chaos changed the loss trajectory"
    for l, (a, b) in enumerate(zip(params, ref_params)):
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"layer {l} params diverged")
    assert stats["inflight_bytes"] == 0


# ---------------------------------------------------------------------------
# unwind: an escalated fault kills the step, not the engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate,policy", [(0.02, "recompute"),
                                         (0.1, "spill")])
def test_failed_step_unwind_leaves_engine_clean(rate, policy):
    """retries=0 so every injected fault escalates and kills its step,
    across several steps (faults land in different plan phases /
    priority classes each time). After chaos is lifted the SAME engine
    must run a clean step: no stale α gates or param futures, no
    retained ``pending_grad`` tails, act coordinator empty, byte
    budget drained, staging pool fully acquirable."""
    io = IOConfig(retries=0, chunk_bytes=1 << 10)
    oc = OffloadConfig(schedule="vertical", num_microbatches=M,
                       micro_batch=MB, seq_len=S,
                       ratios=StorageRatios(0.5, 0.5, 0.5),
                       alpha=0.5, io=io, activation_policy=policy)
    data = SyntheticLM(CFG.vocab_size, seed=0)
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, oc, jax.random.PRNGKey(0), d)
        files = install_chaos(eng.ssd, ChaosSpec(error_rate=rate, seed=3))
        failed = 0
        for _ in range(6):
            try:
                eng.train_step(data.batch(M * MB, S))
            except OSError:
                failed += 1
        assert failed > 0, "chaos never killed a step"
        files.spec = ChaosSpec()                 # lift the chaos
        loss = eng.train_step(data.batch(M * MB, S))
        assert np.isfinite(loss)
        eng.finish()
        assert eng.params_c._futures == {}
        # gates left by the clean step are benign: finish() flushed
        # every α tail, so firing them must be a no-op, not a re-raise
        for fn in list(eng.params_c._gate.values()):
            fn()
        assert eng.act_c._pending == {} and eng.act_c._prefetched == {}
        assert not any(f"pending_grad:{l}" in eng.host
                       for l in range(eng.L)), "stale α-tail gradient"
        s = eng.ioe.metrics_snapshot()
        assert s["inflight_bytes"] == 0, "failed steps leaked budget"
        assert _drainable(eng.ioe), "failed steps leaked staging"
        eng.close()
