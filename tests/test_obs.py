"""repro.obs battery: span tracing, the metrics registry, and
plan-vs-actual reconciliation.

* Tracing-off is byte- AND bitwise-neutral across the full schedule ×
  M × α × R acceptance grid, and on the SAME traced runs
  ``obs.reconcile`` byte columns match ``plan_traffic`` exactly (the
  three-way cross-check discipline extended to the snapshot path).
* ``Tracer.export_chrome`` emits valid Chrome trace-event JSON
  (schema-checked field by field) with the executor / channel / hint
  tracks present.
* Reconciliation stays byte-exact on the paced-SSD smoke (bandwidth
  caps + activation spill + α-tail), and the snapshot feeds
  ``perfmodel.machine_from_snapshot``.
* Satellite regressions: ``reset_stats()`` clears EVERY meter (a
  second measured iteration matches the first), and ``IOEngine``
  reports per-path chunk backlog / cumulative bytes without disturbing
  the aggregate keys.
"""
import json
import tempfile
import threading

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import (MachineParams, StorageRatios,
                                  machine_from_snapshot)
from repro.data import SyntheticLM
from repro.io import IOConfig, IOEngine, IOPriority, StripedFiles
from repro.obs import (SNAPSHOT_VERSION, Tracer, reconcile, stall_by_stream,
                       top_stall_stream)
from repro.offload import OffloadConfig, make_engine, shard_bounds

CFG = ArchConfig(name="obs-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S = 1, 16
X0 = StorageRatios(0.0, 0.0, 0.0)

#: the acceptance grid: schedule × M × α × R (wave needs M % 2 == 0,
#: DP plans are vertical with M % R == 0) — same grid as the
#: lookahead battery
GRID = [(sched, M, alpha, R)
        for sched in ("vertical", "horizontal", "wave")
        for M in (1, 2, 4)
        for alpha in (0.0, 0.5)
        for R in (1, 2)
        if not (sched == "wave" and M % 2)
        and not (R > 1 and (sched != "vertical" or M % R))]


def _build(sched, M, alpha, R, workdir, trace, io=None, policy="recompute",
           depth=1):
    W = {"vertical": 0, "horizontal": 0, "wave": 2}[sched]
    ocfg = OffloadConfig(schedule=sched, num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=alpha,
                         wave_size=W, ratios=X0, prefetch_depth=depth,
                         io=io, activation_policy=policy, trace=trace)
    return make_engine(CFG, ocfg, jax.random.PRNGKey(11), workdir,
                       num_ranks=R)


def _run(sched, M, alpha, R, trace, steps=2, **kw):
    """One measured run; returns (losses, per-rank route bytes, params,
    snapshot, plan, span count)."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build(sched, M, alpha, R, d, trace, **kw)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        losses = [eng.train_step(data.batch(M * MB, S))
                  for _ in range(steps)]
        eng.finish()
        # snapshot FIRST: the param readback below is a debug fetch
        # outside the plan, and reconciliation must not see its bytes
        snap = eng.metrics_snapshot()
        plan = eng.plan
        n_spans = len(eng.tracer)
        routes = [dict(rk.meter.bytes) for rk in eng.ranks]
        params = [eng.read_params(l) for l in range(eng.L)]
        eng.close()
    return losses, routes, params, snap, plan, n_spans


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------

def test_tracer_ring_capacity_and_drop_count():
    tr = Tracer(capacity=4)
    tr.enable()
    for i in range(6):
        tr.record("t", f"s{i}", "c", float(i), float(i) + 0.5, n=i)
    assert len(tr) == 4
    assert tr.dropped == 2
    names = [s[1] for s in tr.spans()]
    assert names == ["s2", "s3", "s4", "s5"]    # oldest evicted first
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_disabled_by_default_and_flag_gated():
    tr = Tracer()
    assert not tr.enabled
    tr.enable()
    assert tr.enabled
    tr.disable()
    assert not tr.enabled


def test_tracer_summary_aggregates_chunk_spans():
    tr = Tracer()
    tr.enable()
    tr.record("p0", "ssd->cpu", "io.chunk", 0.0, 2.0,
              route="ssd->cpu", nbytes=100)
    tr.record("p0", "ssd->cpu:wait", "io.queue", 0.0, 1.0,
              route="ssd->cpu", nbytes=100)
    tr.record("exec", "FWD", "plan", 0.0, 1.0)      # not an io span
    s = tr.summary()
    assert s["spans"] == 3
    d = s["routes"]["ssd->cpu"]
    assert d["bytes"] == 100 and d["ops"] == 1
    assert d["busy_s"] == pytest.approx(2.0)
    assert d["queue_s"] == pytest.approx(1.0)
    # single channel: the wall-clock envelope IS the busy sum
    assert d["channels"] == 1
    assert d["busy_wall_s"] == pytest.approx(2.0)
    assert d["rate_bps"] == pytest.approx(50.0)


def test_tracer_summary_concurrent_channels_union_rate():
    """The concurrency-blindness regression: two path channels moving
    chunks in the SAME wall-clock window must report the device's
    aggregate rate (bytes / union-of-intervals), not the ~1/P figure
    that dividing by the summed per-channel busy seconds yields."""
    tr = Tracer()
    tr.enable()
    # two channels, fully overlapped: each moves 100 B over [0, 2]
    tr.record("p0", "ssd->cpu", "io.chunk", 0.0, 2.0,
              route="ssd->cpu", nbytes=100)
    tr.record("p1", "ssd->cpu", "io.chunk", 0.0, 2.0,
              route="ssd->cpu", nbytes=100)
    d = tr.summary()["routes"]["ssd->cpu"]
    assert d["channels"] == 2
    assert d["busy_s"] == pytest.approx(4.0)         # per-thread sum
    assert d["busy_wall_s"] == pytest.approx(2.0)    # union
    # aggregate device rate: 200 B / 2 s — exactly 2x the single-path
    # rate, where bytes/busy_s would have read half of it
    assert d["rate_bps"] == pytest.approx(100.0)
    assert d["bytes"] / d["busy_s"] == pytest.approx(50.0)

    # serialized channels (no overlap): union degrades to the sum, so
    # the estimator is exact for devices that don't really parallelize
    tr.clear()
    tr.record("p0", "cpu->ssd", "io.chunk", 0.0, 1.0,
              route="cpu->ssd", nbytes=50)
    tr.record("p1", "cpu->ssd", "io.chunk", 1.0, 2.0,
              route="cpu->ssd", nbytes=50)
    d = tr.summary()["routes"]["cpu->ssd"]
    assert d["channels"] == 2
    assert d["busy_wall_s"] == pytest.approx(d["busy_s"]) == pytest.approx(2.0)
    assert d["rate_bps"] == pytest.approx(50.0)


def test_machine_from_snapshot_recovers_paced_two_path_rate(tmp_path):
    """Live-rate ingestion end-to-end on a token-bucket paced 2-path
    device: ``machine_from_snapshot`` must recover approximately the
    configured aggregate cap. Before the union fix it read ~1/2 of it
    (both path channels sleep against the shared bucket, so their busy
    seconds double-count the same pacing window)."""
    cap = 16e6          # small enough that burst (= cap/64) << payload
    tr = Tracer()
    tr.enable()
    cfg = IOConfig(paths=[str(tmp_path / "p0"), str(tmp_path / "p1")],
                   bandwidth={"cpu->ssd": cap, "ssd->cpu": cap},
                   chunk_bytes=1 << 16)
    eng = IOEngine(cfg, tracer=tr)
    sf = StripedFiles(eng)
    data = np.random.default_rng(0).integers(
        0, 255, size=2_000_000, dtype=np.uint8)
    sf.write("x", data, 0, IOPriority.CKPT_SPILL)
    out = np.empty_like(data)
    sf.readinto("x", out, 0, IOPriority.PARAM_FETCH)
    sf.close()
    eng.shutdown()
    assert np.array_equal(out, data)
    snap = {"trace": tr.summary()}
    routes = snap["trace"]["routes"]
    for route in ("cpu->ssd", "ssd->cpu"):
        d = routes[route]
        assert d["channels"] == 2
        # the paced aggregate: within a band of the cap (burst credit
        # lets it land slightly above; scheduling jitter slightly below)
        assert 0.6 * cap < d["rate_bps"] < 2.0 * cap, (route, d)
        # and strictly above the concurrency-blind estimate
        assert d["rate_bps"] > d["bytes"] / d["busy_s"]
    m = machine_from_snapshot(snap, MachineParams())
    assert m.ssd_write_bw == pytest.approx(routes["cpu->ssd"]["rate_bps"])
    assert m.ssd_read_bw == pytest.approx(routes["ssd->cpu"]["rate_bps"])


# ---------------------------------------------------------------------------
# the acceptance grid: tracing-off neutrality + byte-exact reconcile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sched,M,alpha,R", GRID)
def test_trace_neutral_and_reconcile_exact(sched, M, alpha, R):
    """Tracing on vs off: identical losses, identical byte counters,
    bitwise-identical parameters — and the traced run's snapshot
    reconciles byte-exactly against the plan."""
    l_off, r_off, p_off, snap_off, plan_off, n_off = _run(
        sched, M, alpha, R, trace=False)
    l_on, r_on, p_on, snap_on, plan_on, n_on = _run(
        sched, M, alpha, R, trace=True)
    assert l_off == l_on
    assert r_off == r_on
    for a, b in zip(p_off, p_on):
        assert np.array_equal(a, b)             # bitwise
    assert n_off == 0                           # off path records nothing
    assert n_on > 0
    assert snap_off["trace"]["spans"] == 0
    # the load-bearing invariant: measured == plan_traffic, per rank,
    # per (category, route), exactly — from the snapshot alone
    rec = reconcile(plan_on, snap_on)
    assert rec.rows and rec.ok, [r for r in rec.rows if not r.match]
    assert {r.rank for r in rec.rows} == set(range(R))
    # the untraced snapshot reconciles identically (bytes don't care)
    rec_off = reconcile(plan_off, snap_off)
    assert rec_off.ok and not rec_off.route_seconds_measured


# ---------------------------------------------------------------------------
# Chrome trace-event export schema
# ---------------------------------------------------------------------------

def test_chrome_export_is_valid_trace_event_json(tmp_path):
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, 1, d, trace=True)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(2 * MB, S))
        eng.finish()
        path = eng.tracer.export_chrome(str(tmp_path / "trace.json"))
        eng.close()
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    evs = doc["traceEvents"]
    assert evs
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert set(e["ph"] for e in evs) <= {"M", "X", "i"}
    for e in evs:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert isinstance(e["name"], str)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] in ("t", "p", "g")
    # one thread_name metadata row per track, tids unique
    tracks = {e["args"]["name"]: e["tid"] for e in meta
              if e["name"] == "thread_name"}
    assert len(set(tracks.values())) == len(tracks)
    # the three instrumentation layers all present
    assert "exec" in tracks                          # plan-op track
    assert any(t.startswith("io-path") for t in tracks)   # channel tracks
    assert any(t.startswith("hints/") for t in tracks)    # hint lifecycle
    by_cat = {}
    for e in spans:
        by_cat.setdefault(e.get("cat"), []).append(e)
    # plan-op spans carry the full identity tuple
    for e in by_cat["plan"][:5]:
        a = e["args"]
        assert {"l", "m", "wave", "rank", "step"} <= set(a)
    # chunk spans carry route / priority / nbytes / path index
    chunk = by_cat["io.chunk"][0]["args"]
    assert {"route", "priority", "nbytes", "path"} <= set(chunk)
    assert chunk["priority"] in {p.name for p in IOPriority}
    # queue-wait spans pair with execution spans (same categories' count)
    assert len(by_cat["io.queue"]) == len(by_cat["io.chunk"])
    # hint lifecycle spans carry their outcome
    hint = by_cat["hint"][0]["args"]
    assert hint["outcome"] in ("hit", "late", "cancelled", "unused")
    assert instants is not None      # instants are optional but well-formed


def test_dp_ranks_get_distinct_channel_tracks(tmp_path):
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.0, 2, d, trace=True)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(2 * MB, S))
        eng.finish()
        path = eng.tracer.export_chrome(str(tmp_path / "dp.json"))
        eng.close()
    with open(path) as f:
        doc = json.load(f)
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any(t.startswith("rank0-io-path") for t in tracks)
    assert any(t.startswith("rank1-io-path") for t in tracks)


# ---------------------------------------------------------------------------
# reconciliation on the paced-SSD smoke + machine ingestion
# ---------------------------------------------------------------------------

def test_reconcile_byte_exact_on_paced_ssd_smoke(tmp_path):
    io = IOConfig(bandwidth={"ssd->cpu": 2e9, "cpu->ssd": 2e9},
                  chunk_bytes=1 << 16)
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, 1, d, trace=True, io=io,
                     policy="spill", depth=2)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        for _ in range(2):
            eng.train_step(data.batch(2 * MB, S))
        eng.finish()
        snap = eng.metrics_snapshot()
        plan = eng.plan
        eng.close()
    rec = reconcile(plan, snap, machine=MachineParams())
    assert rec.ok and rec.steps == 2
    cats = {r.category for r in rec.rows}
    assert "act" in cats                        # the spill stream showed up
    # predicted seconds exist for every route that moved bytes;
    # measured seconds exist for the SSD routes the channels executed
    assert set(rec.route_seconds_predicted) >= {"ssd->cpu", "cpu->ssd"}
    assert rec.route_seconds_measured.get("cpu->ssd", 0) > 0
    # stall attribution: a sorted, non-negative stream table
    assert rec.stalls == sorted(rec.stalls, key=lambda kv: -kv[1])
    streams = dict(rec.stalls)
    assert all(v >= 0 for v in streams.values())
    assert top_stall_stream(snap["op_seconds"]) in (*streams, "none")
    # the report renders
    table = rec.format()
    assert "exact" in table and "MISMATCH" not in table
    # live machine ingestion: measured chunk rates replace SSD params
    m = machine_from_snapshot(snap)
    assert m.name.endswith("-live")
    assert m.ssd_write_bw > 0
    base = MachineParams()
    empty = machine_from_snapshot({"trace": {"routes": {}}}, base)
    assert empty.ssd_read_bw == base.ssd_read_bw
    assert empty.ssd_write_bw == base.ssd_write_bw


def test_stall_by_stream_fold():
    op_s = {"FETCH_PARAM": 1.0, "ALLGATHER": 0.5, "WAIT_OPT": 0.25,
            "FWD": 99.0}                        # FWD is not a stall kind
    streams = stall_by_stream(op_s)
    assert streams == {"param": 1.5, "opt": 0.25}
    assert top_stall_stream(op_s) == "param"
    assert top_stall_stream({}) == "none"
    assert top_stall_stream({"FWD": 9.0}) == "none"


def test_reconcile_rejects_rank_mismatch():
    _, _, _, snap, plan, _ = _run("vertical", 2, 0.0, 1, trace=False,
                                  steps=1)
    snap["traffic"] = snap["traffic"] * 2       # pretend two ranks
    with pytest.raises(ValueError, match="rank"):
        reconcile(plan, snap)


# ---------------------------------------------------------------------------
# the metrics registry schema (the autotuner ingestion contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R", [1, 2])
def test_metrics_snapshot_schema_and_json_roundtrip(R):
    _, _, _, snap, _, _ = _run("vertical", 2, 0.5, R, trace=True, steps=1)
    assert snap["version"] == SNAPSHOT_VERSION
    required = {"version", "schedule", "ranks", "steps", "act_policy",
                "traffic", "io", "io_depth", "host_peak_nbytes",
                "host_nbytes", "bounds", "op_seconds", "stall_s",
                "phase_time", "lookahead", "hint_skips", "act_skips",
                "act_fallbacks", "plan_costs", "trace"}
    assert required <= set(snap)
    assert snap["ranks"] == R and snap["steps"] == 1
    # per-rank fields are rank-indexed lists in BOTH engines' snapshots
    for key in ("traffic", "io", "io_depth", "host_peak_nbytes",
                "host_nbytes"):
        assert isinstance(snap[key], list) and len(snap[key]) == R
    # subsumes stats(): the io shape and lookahead shape are embedded
    io0 = snap["io"][0]
    assert {"submitted", "completed", "chunk_ops",
            "chunk_bytes_per_path", "chunk_ops_per_path"} <= set(io0)
    assert {"hits", "misses", "hit_rate",
            "hint_skips"} <= set(snap["lookahead"])
    assert {"fwd", "bwd", "opt_wait"} <= set(snap["phase_time"])
    # plan_costs is enough to re-derive predictions (reconcile uses it)
    pc = snap["plan_costs"]
    assert {"P", "param_itemsize", "ckpt_elems", "ratios",
            "alpha", "ranks"} <= set(pc)
    assert pc["ranks"] == R
    # the whole contract is JSON-serializable, by construction
    again = json.loads(json.dumps(snap))
    assert again["version"] == SNAPSHOT_VERSION
    # per layer, each rank's element range: one range covering the
    # layer at R = 1
    assert [[hi - lo for lo, hi in b] for b in snap["bounds"]] == \
        [[hi - lo for lo, hi in shard_bounds(n, R)]
         for n in snap["plan_costs"]["layer_P"]]
    assert all(len(b) == R for b in snap["bounds"])


# ---------------------------------------------------------------------------
# satellite 1: reset_stats clears EVERY meter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R", [1, 2])
def test_second_measured_iteration_matches_first_after_reset(R):
    """The warm-up-boundary regression: meter.reset + reset_stats
    between two identical measured iterations must make the second
    report EXACTLY like the first — byte counters byte-for-byte, the
    deterministic lookahead totals equal, every PR-4/5 meter back to
    zero at the boundary."""
    with tempfile.TemporaryDirectory() as d:
        eng = _build("vertical", 2, 0.5, R, d, trace=False)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        meters = [rk.meter for rk in eng.ranks] if R > 1 else [eng.meter]

        def measured_iteration():
            loss = eng.train_step(data.batch(2 * MB, S))
            eng.finish()
            look = eng.metrics_snapshot()["lookahead"]
            return (loss, [dict(m.snapshot()) for m in meters],
                    look["hits"] + look["misses"])

        first = measured_iteration()
        # poison every resettable meter, including the PR-4/5 ones the
        # old reset missed, then reset
        eng.act_fallbacks = 7
        eng.hint_skips += 3
        eng.act_skips += 2
        for m in meters:
            m.reset()
        eng.reset_stats()
        look = eng.metrics_snapshot()["lookahead"]
        assert look["hits"] == look["misses"] == 0
        assert look["hint_skips"] == 0 and look["act_skips"] == 0
        assert look["stall_s"] == 0 and not look["op_seconds"]
        assert eng.act_fallbacks == 0
        assert all(v == 0.0 for v in eng.phase_time.values())
        second = measured_iteration()
        eng.close()
    # identical byte counters and total fetch count (the hit/miss SPLIT
    # is timing-dependent; the total per iteration is not)
    assert first[1] == second[1]
    assert first[2] == second[2]


# ---------------------------------------------------------------------------
# satellite 2: per-path IOEngine counters
# ---------------------------------------------------------------------------

def test_io_engine_per_path_counters(tmp_path):
    cfg = IOConfig(paths=[str(tmp_path / "p0"), str(tmp_path / "p1")])
    eng = IOEngine(cfg)
    try:
        release = threading.Event()
        f0 = eng.submit_chunk(0, release.wait, IOPriority.CKPT_SPILL,
                              route="cpu->ssd", nbytes=100)
        f1 = eng.submit_chunk(0, lambda: None, IOPriority.CKPT_SPILL,
                              route="cpu->ssd", nbytes=50)
        d = eng.depth()
        # path 0 holds one running + one queued chunk; path 1 is idle
        assert d["channel_backlog_per_path"] == [2, 0]
        assert d["channel_backlog_bytes_per_path"] == [150, 0]
        release.set()
        f0.result(); f1.result()
        f2 = eng.submit_chunk(1, lambda: None, IOPriority.ACT,
                              route="ssd->cpu", nbytes=30)
        f2.result()
        d = eng.depth()
        assert d["channel_backlog_per_path"] == [0, 0]
        assert d["channel_backlog_bytes_per_path"] == [0, 0]
        s = eng.metrics_snapshot()
        # cumulative per-path meters survive completion...
        assert s["chunk_bytes_per_path"] == [150, 30]
        assert s["chunk_ops_per_path"] == [2, 1]
        # ...and the aggregate keys are unchanged in shape and value
        assert s["chunk_ops"] == 3
        assert s["num_paths"] == 2
        assert {"submitted", "completed", "cancelled",
                "max_inflight_bytes", "bytes_by_priority",
                "inflight_bytes",
                "staging_oversized_allocs"} <= set(s)
    finally:
        eng.shutdown()


def test_io_engine_chunk_spans_split_queue_wait_from_transfer(tmp_path):
    tr = Tracer()
    tr.enable()
    cfg = IOConfig(paths=[str(tmp_path / "p0")])
    eng = IOEngine(cfg, tracer=tr)
    try:
        eng.submit_chunk(0, lambda: None, IOPriority.PARAM_FETCH,
                         route="ssd->cpu", nbytes=64).result()
    finally:
        eng.shutdown()
    spans = tr.spans()
    waits = [s for s in spans if s[2] == "io.queue"]
    runs = [s for s in spans if s[2] == "io.chunk"]
    assert len(waits) == 1 and len(runs) == 1
    (_, _, _, w0, w1, wargs) = waits[0]
    (_, _, _, r0, r1, rargs) = runs[0]
    assert w1 <= r0 or w1 == pytest.approx(r0)   # wait ends where run starts
    assert wargs["nbytes"] == rargs["nbytes"] == 64
    assert rargs["path"] == 0
    assert rargs["priority"] == "PARAM_FETCH"
