"""Dynamic chunk-placement battery: the per-tensor chunk-location
table, its persisted sidecar, and the policy axis end-to-end.

* The static policy is a LAYOUT CONSTANT: stripe files bit-for-bit
  identical to the hand-computed ``chunk i -> path i % P`` layout, and
  zero ``.map.json`` sidecars on disk.
* Dynamic placement round-trips: full/partial/short-last-chunk writes
  under "weighted"/"backlog" read back exactly, survive a reopen
  through a FRESH engine (the sidecar is the only carrier), and a
  tensor written static stays readable after a policy flip (and vice
  versa).
* ``IOConfig.shard_for_rank`` slices ``path_bandwidth`` caps along
  with their paths, so a DP rank's placement weights exactly the
  devices it drives.
* Policy neutrality on the REAL engine across the schedule × M × α × R
  acceptance grid: static vs backlog give bitwise-identical losses and
  parameters and byte-identical per-(category, route) traffic —
  placement moves bytes between PATHS only, never between routes.
* Per-path conservation: on a traced 2-path run the per-path chunk
  meters sum exactly to the route totals (``obs.reconcile``'s check),
  and a tampered snapshot is flagged.
* ``machine_for_path_policy`` prices heterogeneous paths (P × min
  under static, sum under backlog) and ``machine_from_snapshot``
  ingests the per-path achieved rates that feed it.
* ``IOEngine.choose_path`` honours rate weights and drains placement
  away from a path with consecutive failures.
"""
import json
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.lp_search import solve_config
from repro.core.perfmodel import (MachineParams, StorageRatios,
                                  machine_for_path_policy,
                                  machine_from_snapshot)
from repro.data import SyntheticLM
from repro.io import IOConfig, IOEngine, IOPriority, StripedFiles
from repro.io.engine import PATH_FAIL_DRAIN_THRESHOLD
from repro.obs import reconcile
from repro.offload import OffloadConfig, OffloadEngine, make_engine

CHUNK = 1000        # odd size: exercises chunk-boundary arithmetic


def _engine(tmp, n_paths=2, **kw):
    paths = [os.path.join(tmp, f"p{i}") for i in range(n_paths)]
    kw.setdefault("chunk_bytes", CHUNK)
    return IOEngine(IOConfig(paths=paths, **kw))


def _payload(nbytes, seed=0):
    return np.random.default_rng(seed).integers(
        0, 255, nbytes, dtype=np.uint8)


def _sidecars(eng):
    return [f for p in eng.paths for f in os.listdir(p)
            if f.endswith(".map.json")]


# ---------------------------------------------------------------------------
# the static layout pin: bit-for-bit i % P, zero placement state
# ---------------------------------------------------------------------------

def test_static_layout_bit_for_bit_and_sidecar_free():
    """Under path_policy="static" the stripe files must equal the
    hand-computed round-robin layout byte for byte — chunk c at slot
    c // P of path c % P — and no sidecar may ever be written."""
    P = 3
    data = _payload(10 * CHUNK + 500)           # 10 full chunks + short
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, n_paths=P)
        sf = StripedFiles(eng)
        sf.write("t", data, 0, IOPriority.CKPT_SPILL)
        sf.close()
        eng.shutdown()
        for p in range(P):
            # chunks p, p+P, ... at consecutive slots; all full except
            # a trailing short chunk, so the file is their plain concat
            expected = b"".join(bytes(data[c * CHUNK:(c + 1) * CHUNK])
                                for c in range(p, 11, P))
            with open(os.path.join(eng.paths[p], f"t.s{p}.bin"),
                      "rb") as f:
                assert f.read() == expected, f"path {p}"
        assert _sidecars(eng) == []


def test_static_reproduces_same_bytes_as_before_policy_existed():
    """Two static engines (one default-constructed, one explicit) must
    produce identical stripe files — the policy knob's default changes
    nothing."""
    data = _payload(7 * CHUNK + 123, seed=3)
    blobs = {}
    for tag, kw in (("default", {}), ("explicit", {"path_policy":
                                                   "static"})):
        with tempfile.TemporaryDirectory() as d:
            eng = _engine(d, n_paths=2, **kw)
            sf = StripedFiles(eng)
            sf.write("t", data, 0, IOPriority.CKPT_SPILL)
            sf.close()
            eng.shutdown()
            blobs[tag] = [open(os.path.join(p, "t.s%d.bin" % i),
                               "rb").read()
                          for i, p in enumerate(eng.paths)]
    assert blobs["default"] == blobs["explicit"]


# ---------------------------------------------------------------------------
# dynamic round-trips: table, sidecar, reopen, short last chunk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["weighted", "backlog"])
def test_dynamic_roundtrip_and_reopen(policy):
    """Write under a dynamic policy, read back; then reopen the same
    paths through a FRESH engine + StripedFiles (placement travels only
    through the sidecar) and read again."""
    data = _payload(9 * CHUNK + 321, seed=1)
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, n_paths=3, path_policy=policy,
                      path_bandwidth=(4e9, 1e9, 1e9))
        sf = StripedFiles(eng)
        sf.write("t", data, 0, IOPriority.CKPT_SPILL)
        out = np.empty_like(data)
        sf.readinto("t", out, 0, IOPriority.PARAM_FETCH)
        np.testing.assert_array_equal(out, data)
        assert _sidecars(eng) == ["t.map.json"]  # on paths[0] only
        # ranged partial update sticks to the recorded placement
        patch = _payload(2 * CHUNK, seed=2)
        sf.write("t", patch, 777, IOPriority.CKPT_SPILL)
        ref = data.copy()
        ref[777:777 + patch.nbytes] = patch
        sf.readinto("t", out, 0, IOPriority.PARAM_FETCH)
        np.testing.assert_array_equal(out, ref)
        sf.close()
        eng.shutdown()

        eng2 = _engine(d, n_paths=3, path_policy=policy,
                       path_bandwidth=(4e9, 1e9, 1e9))
        sf2 = StripedFiles(eng2)
        out2 = np.empty_like(ref)
        sf2.readinto("t", out2, 0, IOPriority.PARAM_FETCH)
        np.testing.assert_array_equal(out2, ref)
        # delete removes stripes AND the sidecar
        sf2.delete("t")
        assert _sidecars(eng2) == []
        sf2.close()
        eng2.shutdown()


def test_short_last_chunk_stays_sticky():
    """The short last chunk is never re-placed (a move would need a
    read-modify-write): under backlog it stays on its static path, and
    overwriting just that tail keeps the table unchanged."""
    n_full = 6
    data = _payload(n_full * CHUNK + 77, seed=4)
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, n_paths=2, path_policy="backlog")
        sf = StripedFiles(eng)
        sf.write("t", data, 0, IOPriority.CKPT_SPILL)
        p, slot = sf.placement("t", n_full)      # the short chunk
        tail = _payload(77, seed=5)
        sf.write("t", tail, n_full * CHUNK, IOPriority.CKPT_SPILL)
        assert sf.placement("t", n_full) == (p, slot)   # never re-placed
        out = np.empty_like(data)
        sf.readinto("t", out, 0, IOPriority.PARAM_FETCH)
        ref = data.copy()
        ref[n_full * CHUNK:] = tail
        np.testing.assert_array_equal(out, ref)
        sf.close()
        eng.shutdown()


def test_policy_flip_cross_readability():
    """A tensor written static stays readable after flipping the live
    engine to backlog — and chunks rewritten after the flip move while
    the rest keep their static placement."""
    data = _payload(8 * CHUNK, seed=6)
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, n_paths=2)              # starts static
        sf = StripedFiles(eng)
        sf.write("t", data, 0, IOPriority.CKPT_SPILL)
        assert _sidecars(eng) == []
        eng.set_path_policy("backlog")
        patch = _payload(3 * CHUNK, seed=7)
        sf.write("t", patch, 2 * CHUNK, IOPriority.CKPT_SPILL)
        ref = data.copy()
        ref[2 * CHUNK:5 * CHUNK] = patch
        out = np.empty_like(ref)
        sf.readinto("t", out, 0, IOPriority.PARAM_FETCH)
        np.testing.assert_array_equal(out, ref)
        # untouched chunks still on their static default
        assert sf.placement("t", 0) == (0, 0)
        assert sf.placement("t", 7) == (1, 3)
        sf.close()
        eng.shutdown()


def test_stale_sidecar_rejected_on_reopen():
    """Reopening a dynamically-placed tensor with a different chunk
    size (or path count) must fail loudly, not read garbage."""
    data = _payload(5 * CHUNK, seed=8)
    with tempfile.TemporaryDirectory() as d:
        # 4:1 weights guarantee at least one chunk leaves its static
        # path, so the sidecar definitely exists to go stale
        eng = _engine(d, n_paths=2, path_policy="backlog",
                      path_bandwidth=(4e9, 1e9))
        sf = StripedFiles(eng)
        sf.write("t", data, 0, IOPriority.CKPT_SPILL)
        assert _sidecars(eng) == ["t.map.json"]
        sf.close()
        eng.shutdown()
        eng2 = _engine(d, n_paths=2, chunk_bytes=CHUNK * 2,
                       path_policy="backlog")
        sf2 = StripedFiles(eng2)
        out = np.empty_like(data)
        with pytest.raises(ValueError, match="stale chunk map"):
            sf2.readinto("t", out, 0, IOPriority.PARAM_FETCH)
        sf2.close()
        eng2.shutdown()


# ---------------------------------------------------------------------------
# DP path sharding carries the caps
# ---------------------------------------------------------------------------

def test_shard_for_rank_slices_caps_with_paths():
    cfg = IOConfig(paths=["/a", "/b", "/c", "/d"],
                   path_bandwidth=(4e9, 1e9, 2e9, 3e9),
                   path_policy="backlog")
    r0 = cfg.shard_for_rank(0, 2)
    r1 = cfg.shard_for_rank(1, 2)
    assert list(r0.paths) == ["/a", "/c"]
    assert r0.path_bandwidth == (4e9, 2e9)
    assert list(r1.paths) == ["/b", "/d"]
    assert r1.path_bandwidth == (1e9, 3e9)
    assert r0.path_policy == r1.path_policy == "backlog"
    # more ranks than paths: the shared device's cap follows the subdir
    r5 = cfg.shard_for_rank(5, 6)
    assert list(r5.paths) == [os.path.join("/b", "rank5")]
    assert r5.path_bandwidth == (1e9,)
    # no caps configured: sharding never invents any
    assert IOConfig(paths=["/a", "/b"]).shard_for_rank(0, 2) \
        .path_bandwidth is None


def test_config_validates_policy_and_caps():
    with pytest.raises(ValueError, match="path_policy"):
        IOConfig(path_policy="roundest-robin")
    with pytest.raises(ValueError, match="> 0"):
        IOConfig(paths=["/a"], path_bandwidth=(0.0,))
    with pytest.raises(ValueError, match="cap"):
        IOConfig(paths=["/a", "/b"], path_bandwidth=(1e9,))


# ---------------------------------------------------------------------------
# choose_path: weights + fault drain
# ---------------------------------------------------------------------------

def test_choose_path_weighted_ratio():
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, n_paths=2, path_policy="weighted",
                      path_bandwidth=(3e9, 1e9))
        picks = [eng.choose_path(1000) for _ in range(400)]
        counts = [picks.count(0), picks.count(1)]
        assert counts[0] == 300 and counts[1] == 100  # exact 3:1 argmin
        eng.shutdown()


def test_choose_path_drains_failed_path():
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, n_paths=2, path_policy="backlog")
        eng._path_failures[0] = PATH_FAIL_DRAIN_THRESHOLD
        assert all(eng.choose_path(100) == 1 for _ in range(20))
        # every path down: fall back to all (fail loudly downstream
        # rather than deadlocking placement)
        eng._path_failures[1] = PATH_FAIL_DRAIN_THRESHOLD
        assert set(eng.choose_path(100) for _ in range(10)) <= {0, 1}
        eng.shutdown()


# ---------------------------------------------------------------------------
# heterogeneous pricing + live-rate ingestion
# ---------------------------------------------------------------------------

def test_machine_for_path_policy_pricing():
    m = MachineParams(name="het", ssd_path_read_bw=(200e6, 50e6),
                      ssd_path_write_bw=(100e6, 25e6))
    st = machine_for_path_policy(m, "static")
    assert st.ssd_read_bw == pytest.approx(2 * 50e6)
    assert st.ssd_write_bw == pytest.approx(2 * 25e6)
    for pol in ("weighted", "backlog"):
        dy = machine_for_path_policy(m, pol)
        assert dy.ssd_read_bw == pytest.approx(250e6)
        assert dy.ssd_write_bw == pytest.approx(125e6)
    # no per-path evidence: the machine passes through unchanged
    plain = MachineParams(name="plain")
    assert machine_for_path_policy(plain, "backlog") is plain


def test_machine_from_snapshot_ingests_per_path_rates():
    snap = {"trace": {"routes": {
        "ssd->cpu": {"bytes": 300, "busy_s": 2.0, "rate_bps": 150.0,
                     "per_path": {"0": {"bytes": 200, "busy_s": 1.0,
                                        "rate_bps": 200.0},
                                  "1": {"bytes": 100, "busy_s": 1.0,
                                        "rate_bps": 100.0}}},
        "cpu->ssd": {"bytes": 80, "busy_s": 1.0, "rate_bps": 80.0,
                     "per_path": {"0": {"bytes": 80, "busy_s": 1.0,
                                        "rate_bps": 80.0}}},
    }}}
    m = machine_from_snapshot(snap, MachineParams())
    assert m.ssd_path_read_bw == pytest.approx((200.0, 100.0))
    assert m.ssd_path_write_bw == pytest.approx((80.0,))
    # and the LP prices the split policy-dependently from here
    assert machine_for_path_policy(m, "static").ssd_read_bw == \
        pytest.approx(200.0)
    assert machine_for_path_policy(m, "backlog").ssd_read_bw == \
        pytest.approx(300.0)


def test_solve_config_path_policy_pricing_and_tag():
    import dataclasses

    from repro.configs import get_config
    from repro.core.perfmodel import Workload
    m = dataclasses.replace(MachineParams(),
                            ssd_path_read_bw=(4.8e9, 1.2e9),
                            ssd_path_write_bw=(2.4e9, 0.6e9))
    w = Workload.from_config(get_config("gpt-65b"), micro_batch=2,
                             seq_len=2048)
    st = solve_config(m, w, 8, 0.2, path_policy="static")
    bl = solve_config(m, w, 8, 0.2, path_policy="backlog")
    assert st is not None and bl is not None
    assert st.path_policy == "static"
    assert bl.path_policy == "backlog"
    # backlog prices the device at sum-of-rates (6/3 GB/s) vs static's
    # P x min (2.4/1.2 GB/s): never a slower predicted iteration
    assert bl.iteration_time <= st.iteration_time
    with pytest.raises(ValueError, match="path_policy"):
        solve_config(m, w, 8, 0.2, path_policy="fastest")


# ---------------------------------------------------------------------------
# the acceptance grid: policy neutrality on the real engine
# ---------------------------------------------------------------------------

CFG = ArchConfig(name="pp-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S = 1, 16
X0 = StorageRatios(0.0, 0.0, 0.0)

#: schedule × M × α × R (wave needs M % 2 == 0, DP plans are vertical
#: with M % R == 0) — the same filters as the obs/lookahead batteries
GRID = [(sched, M, alpha, R)
        for sched in ("vertical", "horizontal", "wave")
        for M in (2, 4)
        for alpha in (0.0, 0.5)
        for R in (1, 2)
        if not (sched == "wave" and M % 2)
        and not (R > 1 and (sched != "vertical" or M % R))]


def _run(sched, M, alpha, R, policy, steps=2):
    """One run over a 4-path striped workdir; returns (losses,
    per-rank route bytes, params, sidecar count)."""
    W = {"vertical": 0, "horizontal": 0, "wave": 2}[sched]
    with tempfile.TemporaryDirectory() as d:
        io = IOConfig(paths=[os.path.join(d, f"p{i}") for i in range(4)],
                      chunk_bytes=1 << 10, path_policy=policy,
                      path_bandwidth=(4e9, 1e9, 2e9, 3e9))
        ocfg = OffloadConfig(schedule=sched, num_microbatches=M,
                             micro_batch=MB, seq_len=S, alpha=alpha,
                             wave_size=W, ratios=X0, io=io,
                             prefetch_depth=1)
        eng = make_engine(CFG, ocfg, jax.random.PRNGKey(11), d, num_ranks=R)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        losses = [eng.train_step(data.batch(M * MB, S))
                  for _ in range(steps)]
        eng.finish()
        routes = [dict(rk.meter.bytes) for rk in eng.ranks]
        params = [eng.read_params(l) for l in range(eng.L)]
        n_maps = sum(len(_sidecars(rk.ioe)) for rk in eng.ranks)
        eng.close()
    return losses, routes, params, n_maps


@pytest.mark.parametrize("sched,M,alpha,R", GRID)
def test_policy_neutral_losses_params_and_route_bytes(sched, M, alpha, R):
    """Static vs backlog placement: identical losses, bitwise-identical
    parameters, byte-identical per-(category, route) traffic — and the
    static run leaves zero sidecars while the backlog run places."""
    l_st, r_st, p_st, maps_st = _run(sched, M, alpha, R, "static")
    l_bl, r_bl, p_bl, maps_bl = _run(sched, M, alpha, R, "backlog")
    assert l_st == l_bl
    assert r_st == r_bl
    for a, b in zip(p_st, p_bl):
        assert np.array_equal(a, b)             # bitwise
    assert maps_st == 0
    assert maps_bl > 0


# ---------------------------------------------------------------------------
# per-path conservation through obs.reconcile
# ---------------------------------------------------------------------------

def test_per_path_meters_sum_to_route_totals():
    """A traced 2-path backlog run reconciles byte-exactly, including
    the per-path conservation check; tampering with one per-path meter
    is flagged and flips ``.ok``."""
    with tempfile.TemporaryDirectory() as d:
        io = IOConfig(paths=[os.path.join(d, "p0"), os.path.join(d, "p1")],
                      chunk_bytes=1 << 10, path_policy="backlog")
        eng = OffloadEngine(CFG, OffloadConfig(
            schedule="vertical", num_microbatches=2, micro_batch=MB,
            seq_len=S, alpha=0.5, ratios=X0, io=io, prefetch_depth=1,
            trace=True), jax.random.PRNGKey(11), d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        for _ in range(2):
            eng.train_step(data.batch(2 * MB, S))
        eng.finish()
        snap = eng.metrics_snapshot()
        plan = eng.plan
        eng.close()
    rec = reconcile(plan, snap)
    assert rec.path_sum_mismatches == []
    assert rec.ok, rec.format()
    # every traced route's per-path split is non-trivial and sums back
    for route, dd in snap["trace"]["routes"].items():
        pp = dd.get("per_path") or {}
        if pp:
            assert sum(v["bytes"] for v in pp.values()) == dd["bytes"]
    # tamper: steal bytes from one path's meter
    snap2 = json.loads(json.dumps(snap))
    for dd in snap2["trace"]["routes"].values():
        if dd.get("per_path"):
            next(iter(dd["per_path"].values()))["bytes"] += 1
            break
    rec2 = reconcile(plan, snap2)
    assert rec2.path_sum_mismatches
    assert not rec2.ok
    assert "per-path conservation VIOLATED" in rec2.format()
