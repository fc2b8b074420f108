"""``CpuAdam.update``, blocked and threaded, against the unblocked numpy
update it replaced: bit for bit on master, m and v.

Each case builds random state, runs one update both ways and compares
with ``np.array_equal``. Sizes straddle the block (``BLOCK``) and the
inline/pooled cut-over; ranges cover whole arrays and ``lo``/``hi``
sub-ranges; weight decay, the bias-correction step and the gradient's
dtype vary. The pool is reached by size alone.
"""
import tempfile
import threading

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import StorageRatios
from repro.data import SyntheticLM
from repro.offload import OffloadConfig, OffloadEngine
from repro.optim import cpu_adam
from repro.optim.cpu_adam import BLOCK, BLOCKS_PER_THREAD, CpuAdam

#: the smallest range split over the pool (given two CPUs)
POOLED = 2 * BLOCKS_PER_THREAD * BLOCK


def reference_update(adam, master, m, v, grad, step, lo=0, hi=None):
    """The update as it was before blocking: one numpy expression per
    operation over the whole range, full-length temporaries."""
    hi = master.size if hi is None else hi
    if hi <= lo:
        return
    p = master[lo:hi]
    g = grad[lo:hi].astype(np.float32)
    m_ = m[lo:hi]
    v_ = v[lo:hi]
    np.multiply(m_, adam.b1, out=m_)
    m_ += (1 - adam.b1) * g
    np.multiply(v_, adam.b2, out=v_)
    v_ += (1 - adam.b2) * (g * g)
    bc1 = 1 - adam.b1 ** step
    bc2 = 1 - adam.b2 ** step
    denom = np.sqrt(v_ / bc2) + adam.eps
    upd = (m_ / bc1) / denom
    if adam.wd:
        upd = upd + adam.wd * p
    p -= adam.lr * upd


def _state(n, gdtype, seed):
    rng = np.random.default_rng(seed)
    p = rng.standard_normal(n, dtype=np.float32)
    m = rng.standard_normal(n, dtype=np.float32) * np.float32(1e-3)
    v = np.abs(rng.standard_normal(n, dtype=np.float32)) * np.float32(1e-6)
    g = (rng.standard_normal(n, dtype=np.float32)
         * np.float32(1e-2)).astype(gdtype)
    return p, m, v, g


def _range(n, sub):
    """Whole array, or a sub-range that starts and ends off a block
    boundary."""
    if not sub:
        return 0, None
    return n // 3 + 1, n - n // 5 - 1


SIZES = [1, BLOCK - 1, BLOCK + 1, POOLED + 12345, 5 * POOLED // 2 + 7]
CASES = [(n, wd, step, gd, sub)
         for n in SIZES
         for wd, step in [(0.0, 1), (0.1, 1000), (0.1, 1), (0.0, 1000)]
         for gd in (np.float32, np.float16)
         for sub in ((False, True) if n > 1 else (False,))
         # the two largest sizes take fewer combinations, each once
         if n < POOLED or (wd, step, gd) in [(0.0, 1, np.float32),
                                             (0.1, 1000, np.float16),
                                             (0.1, 1, np.float32)]]


def _id(case):
    n, wd, step, gd, sub = case
    return f"n{n}-wd{wd}-step{step}-{np.dtype(gd).name}" + ("-sub" * sub)


@pytest.mark.parametrize("case", CASES, ids=[_id(c) for c in CASES])
def test_update_matches_unblocked_bit_for_bit(case):
    n, wd, step, gd, sub = case
    lo, hi = _range(n, sub)
    p, m, v, g = _state(n, gd, seed=n + step)
    want = [a.copy() for a in (p, m, v)]
    adam = CpuAdam(lr=1e-3, weight_decay=wd)
    reference_update(adam, *want, g, step, lo, hi)
    adam.update(p, m, v, g, step, lo, hi)
    for got, ref, name in zip((p, m, v), want, "pmv"):
        assert np.array_equal(got, ref), name
    span = (n if hi is None else hi) - lo
    pooled = adam.threads_for(span) > 1
    if span >= POOLED:
        assert pooled or cpu_adam._cpus() == 1
    assert (adam.pooled_updates, adam.inline_updates) == (int(pooled),
                                                          int(not pooled))


def test_threads_follow_size_and_cpus(monkeypatch):
    monkeypatch.setattr(cpu_adam, "_cpus", lambda: 64)
    assert CpuAdam.threads_for(1) == 1
    assert CpuAdam.threads_for(POOLED - 1) == 1
    assert CpuAdam.threads_for(POOLED) == 2
    assert CpuAdam.threads_for(1 << 40) == cpu_adam.MAX_THREADS
    monkeypatch.setattr(cpu_adam, "_cpus", lambda: 1)
    assert CpuAdam.threads_for(1 << 40) == 1


@pytest.mark.parametrize("cap", [3, 5])
def test_uneven_thread_split_matches_unblocked(monkeypatch, cap):
    """More threads than the cap in the code allows, each run a whole
    number of blocks but not all of one length."""
    monkeypatch.setattr(cpu_adam, "MAX_THREADS", cap)
    monkeypatch.setattr(cpu_adam, "_cpus", lambda: 8)
    n = cap * POOLED // 2 + 3 * BLOCK + 101
    p, m, v, g = _state(n, np.float16, seed=cap)
    want = [a.copy() for a in (p, m, v)]
    adam = CpuAdam(lr=1e-3, weight_decay=0.1)
    reference_update(adam, *want, g, 2)
    assert adam.threads_for(n) == cap
    adam.update(p, m, v, g, 2)
    for got, ref, name in zip((p, m, v), want, "pmv"):
        assert np.array_equal(got, ref), name
    assert adam.pooled_updates == 1


def test_two_updates_on_disjoint_slices_at_once():
    """Two callers (an early and a late segment) share one pool and one
    array; each writes only its own elements."""
    n = 2 * POOLED + 999
    cut = POOLED + 17
    p, m, v, g = _state(n, np.float32, seed=5)
    want = [a.copy() for a in (p, m, v)]
    adam = CpuAdam(lr=1e-3, weight_decay=0.1)
    reference_update(adam, *want, g, 3, 0, cut)
    reference_update(adam, *want, g, 3, cut, n)
    start = threading.Barrier(2)
    errors = []

    def run(lo, hi):
        try:
            start.wait()
            adam.update(p, m, v, g, 3, lo, hi)
        except Exception as e:  # pragma: no cover - reported below
            errors.append(e)

    threads = [threading.Thread(target=run, args=r)
               for r in ((0, cut), (cut, n))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for got, ref, name in zip((p, m, v), want, "pmv"):
        assert np.array_equal(got, ref), name
    assert adam.pooled_updates + adam.inline_updates == 2


def test_empty_range_is_a_no_op_and_not_counted():
    p, m, v, g = _state(8, np.float32, seed=1)
    before = [a.copy() for a in (p, m, v)]
    adam = CpuAdam()
    adam.update(p, m, v, g, 1, 5, 5)
    for got, ref in zip((p, m, v), before):
        assert np.array_equal(got, ref)
    assert (adam.pooled_updates, adam.inline_updates) == (0, 0)


def test_engine_counts_updates_and_spans_carry_threads():
    cfg = ArchConfig(name="adam-tiny", family="dense", source="test",
                     num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                     head_dim=16, d_ff=64, vocab_size=256, act="gelu")
    ocfg = OffloadConfig(schedule="vertical", num_microbatches=2,
                         micro_batch=1, seq_len=16, alpha=0.5,
                         ratios=StorageRatios(0.0, 0.0, 0.0), trace=True)
    data = SyntheticLM(cfg.vocab_size, seed=0)
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(cfg, ocfg, jax.random.PRNGKey(3), d)
        for _ in range(2):
            eng.train_step(data.batch(2, 16))
        eng.finish()
        spans = [s for s in eng.tracer.spans() if s[1] == "opt.adam"]
        adam = eng.opt_c.adam
        eng.close()
    # two layers, early and late segments, two steps
    assert len(spans) == 2 * 2 * 2
    for s in spans:
        assert s[5]["threads"] == CpuAdam.threads_for(s[5]["n"]) == 1
    # a tiny model's segments all run inline
    assert (adam.pooled_updates, adam.inline_updates) == (0, len(spans))
