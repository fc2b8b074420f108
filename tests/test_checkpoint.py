"""Crash-consistent checkpoint battery (`repro.offload.checkpoint`).

The contract under test:

* **Bitwise resume** — save mid-training, restore into a FRESH engine
  built from a different PRNG key: the continued loss trajectory is
  bitwise identical to the uninterrupted run (the plan-swap pin,
  through disk). Saving is non-destructive — the original engine keeps
  training and produces the same reference trajectory.
* **Topology interchange** — vectors are stored assembled, so a
  single-rank checkpoint restores into a DP engine (and the params
  match bitwise): DP sharding is contiguous slicing.
* **Crash consistency** — the manifest commits last (tmp + rename):
  a torn/missing/wrong-version manifest, a torn or corrupt tensor
  file, or mismatched engine meta raise :class:`CheckpointError`
  BEFORE any engine state is touched — a failed restore leaves the
  engine trainable and bit-identical to before the attempt.
* **Generation GC** — re-saving into the same directory keeps only
  the files the committed manifest references.
"""
import dataclasses
import json
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import StorageRatios
from repro.data import SyntheticLM
from repro.offload import (CheckpointError, OffloadConfig, load_manifest,
                           make_engine)

CFG = ArchConfig(name="ckpt-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S, M = 1, 16, 4


def _mk(d, ranks=1, key=0, cfg=CFG):
    oc = OffloadConfig(schedule="vertical", num_microbatches=M,
                       micro_batch=MB, seq_len=S,
                       ratios=StorageRatios(0.5, 0.5, 0.5),
                       alpha=0.5, activation_policy="spill")
    return make_engine(cfg, oc, jax.random.PRNGKey(key), d,
                       num_ranks=ranks)


def _steps(eng, n, data):
    return [eng.train_step(data.batch(M * MB, S)) for _ in range(n)]


def _params(eng):
    return [eng.read_params(l) for l in range(eng.L)]


def test_save_restore_resumes_bitwise():
    data = SyntheticLM(CFG.vocab_size, seed=0)
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2, \
            tempfile.TemporaryDirectory() as ck:
        a = _mk(d1, key=0)
        _steps(a, 2, data)
        manifest = a.save_checkpoint(ck)
        assert os.path.basename(manifest) == "manifest.json"
        # non-destructive: the SAME engine continues -> reference
        data_a = SyntheticLM(CFG.vocab_size, seed=1)
        ref = _steps(a, 2, data_a)
        a.finish()
        a.close()
        # fresh engine, DIFFERENT init key: restore overwrites it all
        b = _mk(d2, key=99)
        step = b.restore_checkpoint(ck)
        assert step == 2 and b.step_num == 2
        data_b = SyntheticLM(CFG.vocab_size, seed=1)
        got = _steps(b, 2, data_b)
        assert got == ref, "resumed trajectory diverged"
        b.finish()
        b.close()


def test_single_rank_checkpoint_restores_into_dp():
    data = SyntheticLM(CFG.vocab_size, seed=0)
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2, \
            tempfile.TemporaryDirectory() as ck:
        a = _mk(d1, ranks=1, key=0)
        _steps(a, 2, data)
        a.save_checkpoint(ck)
        a.finish()
        want = _params(a)
        a.close()
        b = _mk(d2, ranks=2, key=5)
        assert b.restore_checkpoint(ck) == 2
        for l, (x, y) in enumerate(zip(_params(b), want)):
            np.testing.assert_array_equal(x, y,
                                          err_msg=f"layer {l} params")
        # and it trains
        assert np.isfinite(b.train_step(data.batch(M * MB, S)))
        b.finish()
        b.close()


def test_generation_gc_keeps_only_committed_files():
    data = SyntheticLM(CFG.vocab_size, seed=0)
    with tempfile.TemporaryDirectory() as d, \
            tempfile.TemporaryDirectory() as ck:
        eng = _mk(d)
        _steps(eng, 2, data)
        eng.save_checkpoint(ck)
        assert any(f.endswith(".g2.bin") for f in os.listdir(ck))
        _steps(eng, 2, data)
        eng.save_checkpoint(ck)
        gens = {f.rsplit(".g", 1)[1] for f in os.listdir(ck)
                if f.endswith(".bin")}
        assert gens == {"4.bin"}, "stale generation files survived GC"
        doc = load_manifest(ck)
        assert doc["meta"]["step_num"] == 4
        eng.finish()
        eng.close()


def _saved_engine(d, ck):
    data = SyntheticLM(CFG.vocab_size, seed=0)
    eng = _mk(d)
    _steps(eng, 2, data)
    eng.save_checkpoint(ck)
    return eng, data


def _assert_untouched_and_trainable(eng, before, data):
    for l, (x, y) in enumerate(zip(_params(eng), before)):
        np.testing.assert_array_equal(
            x, y, err_msg=f"failed restore mutated layer {l}")
    assert np.isfinite(eng.train_step(data.batch(M * MB, S)))


def test_torn_manifest_is_rejected_engine_untouched():
    with tempfile.TemporaryDirectory() as d, \
            tempfile.TemporaryDirectory() as ck:
        eng, data = _saved_engine(d, ck)
        before = _params(eng)
        mp = os.path.join(ck, "manifest.json")
        raw = open(mp, "rb").read()
        with open(mp, "wb") as f:                 # simulate a torn write
            f.write(raw[:len(raw) // 2])
        with pytest.raises(CheckpointError, match="torn or corrupt"):
            eng.restore_checkpoint(ck)
        _assert_untouched_and_trainable(eng, before, data)
        eng.finish()
        eng.close()


def test_missing_manifest_is_rejected():
    with tempfile.TemporaryDirectory() as d, \
            tempfile.TemporaryDirectory() as ck:
        eng = _mk(d)
        with pytest.raises(CheckpointError, match="no checkpoint manifest"):
            eng.restore_checkpoint(ck)
        eng.close()


def test_wrong_version_is_rejected():
    with tempfile.TemporaryDirectory() as d, \
            tempfile.TemporaryDirectory() as ck:
        eng, data = _saved_engine(d, ck)
        mp = os.path.join(ck, "manifest.json")
        doc = json.load(open(mp))
        doc["version"] = 999
        json.dump(doc, open(mp, "w"))
        with pytest.raises(CheckpointError, match="version"):
            eng.restore_checkpoint(ck)
        eng.finish()
        eng.close()


def test_corrupt_tensor_is_rejected_engine_untouched():
    """One flipped byte in one tensor file: CRC verification fails the
    whole restore before any state is written."""
    with tempfile.TemporaryDirectory() as d, \
            tempfile.TemporaryDirectory() as ck:
        eng, data = _saved_engine(d, ck)
        before = _params(eng)
        doc = load_manifest(ck)
        fn = doc["tensors"]["master:0"]["file"]
        fp = os.path.join(ck, fn)
        raw = bytearray(open(fp, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(fp, "wb").write(bytes(raw))
        with pytest.raises(CheckpointError, match="CRC32C mismatch"):
            eng.restore_checkpoint(ck)
        _assert_untouched_and_trainable(eng, before, data)
        eng.finish()
        eng.close()


def test_torn_tensor_is_rejected():
    with tempfile.TemporaryDirectory() as d, \
            tempfile.TemporaryDirectory() as ck:
        eng, data = _saved_engine(d, ck)
        doc = load_manifest(ck)
        fn = doc["tensors"]["v:1"]["file"]
        fp = os.path.join(ck, fn)
        raw = open(fp, "rb").read()
        open(fp, "wb").write(raw[: len(raw) // 2])
        with pytest.raises(CheckpointError, match="torn checkpoint tensor"):
            eng.restore_checkpoint(ck)
        eng.finish()
        eng.close()


def test_meta_mismatch_is_rejected():
    """A checkpoint from a 2-layer model must not restore into a
    3-layer engine."""
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2, \
            tempfile.TemporaryDirectory() as ck:
        eng, _ = _saved_engine(d1, ck)
        eng.finish()
        eng.close()
        cfg3 = dataclasses.replace(CFG, name="ckpt-tiny-3", num_layers=3)
        other = _mk(d2, cfg=cfg3)
        with pytest.raises(CheckpointError, match="meta mismatch"):
            other.restore_checkpoint(ck)
        other.close()
