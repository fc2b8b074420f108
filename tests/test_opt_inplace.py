"""The host optimizer's in-place path: an Adam segment whose range lies
wholly in the host tier updates master, m and v through views of the
host arrays, and casts the new low-precision parameters straight into
the parameters' host view.

The reference is the copy path the optimizer ran before: every segment
reads master/m/v into fresh arrays (``read_range``), updates them,
writes all three back (``write_seg``) and writes a full-length
``astype`` of master into the parameters (``CopyPath`` below, patched
over the coordinator). Each case compares the two with
``np.array_equal``: losses, master/m/v and parameters, across Adam
state ratios, α and schedules; meters against ``plan_traffic``; the
in-place and staged element counters; the cast's rounding; a hint on a
host-resident tail; and a segment that fails mid-update.
"""
import importlib.util
import sys
import tempfile
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.perfmodel import StorageRatios
from repro.data import SyntheticLM
from repro.io import IOConfig, IOEngine
from repro.obs import Tracer, reconcile
from repro.offload import OffloadConfig, OffloadEngine, make_engine
from repro.offload.coordinators import OptimizerStepCoordinator
from repro.offload.stores import HostStore, SSDStore, TieredVector, \
    TrafficMeter
from repro.optim.cpu_adam import CpuAdam

CFG = ArchConfig(name="inplace-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
MB, S, M, STEPS = 1, 16, 2, 2
#: half of every parameter vector on SSD, so the low-precision write
#: has a host share and an SSD share
PARAM = 0.5
SPAN_SPLIT = Path(__file__).resolve().parents[1] / "benchmarks" / \
    "span_split.py"


class CopyPath:
    """The segment body before the in-place path, as patches of the
    coordinator's helpers: no vector has a view, so each is read into a
    copy and written back, and the parameters get a full-length cast."""

    @staticmethod
    def _views(coord, l, lo, hi):
        return [None, None, None]

    @staticmethod
    def _write_params(coord, l, mast, lo, hi):
        vec = coord.params[l]
        vec.write_seg(mast.astype(vec.dtype), lo)


def _copy_path(monkeypatch):
    monkeypatch.setattr(OptimizerStepCoordinator, "_views", CopyPath._views)
    monkeypatch.setattr(OptimizerStepCoordinator, "_write_params",
                        CopyPath._write_params)


def _ocfg(sched, opt, alpha, **kw):
    return OffloadConfig(schedule=sched, num_microbatches=M, micro_batch=MB,
                         seq_len=S, alpha=alpha,
                         ratios=StorageRatios(ckpt=0.0, param=PARAM,
                                              opt=opt), **kw)


def _run(sched, opt, alpha, ranks=1):
    """Two steps, then ``finish``: losses, every rank's master/m/v and
    parameters, the metrics snapshot and the plan."""
    with tempfile.TemporaryDirectory() as d:
        ocfg = _ocfg(sched, opt, alpha)
        key = jax.random.PRNGKey(7)
        eng = make_engine(CFG, ocfg, key, d, num_ranks=ranks)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        losses = [eng.train_step(data.batch(M * MB, S))
                  for _ in range(STEPS)]
        eng.finish()
        snap = eng.metrics_snapshot()
        state = [[vec.read().copy() for vecs in (rk.m_master, rk.m_m,
                                                 rk.m_v, rk.p_vecs)
                  for vec in vecs] for rk in eng.ranks]
        sizes = [[vec.n for vec in rk.m_master] for rk in eng.ranks]
        plan = eng.plan
        eng.close()
    return losses, state, snap, plan, sizes


def _expected_counts(sizes, opt, alpha):
    """(in place, staged) master/m/v elements of STEPS steps: a segment
    is in place when it lies wholly in the host share of its vectors."""
    inplace = staged = 0
    for n in sizes:
        k_early, k = int(round((1.0 - alpha) * n)), int(round(opt * n))
        for lo, hi in ((0, k_early), (k_early, n)):
            if hi > lo:
                if 0 < k and hi <= k:
                    inplace += 3 * (hi - lo)
                else:
                    staged += 3 * (hi - lo)
    return STEPS * inplace, STEPS * staged


GRID = [(sched, opt, alpha)
        for sched in ("vertical", "horizontal")
        for opt in (1.0, 0.75, 0.5, 0.0)
        for alpha in (0.0, 0.25)]


@pytest.mark.parametrize("sched,opt,alpha", GRID)
def test_inplace_matches_copy_path_bit_for_bit(monkeypatch, sched, opt,
                                               alpha):
    losses, state, snap, plan, sizes = _run(sched, opt, alpha)
    with monkeypatch.context() as mp:
        _copy_path(mp)
        ref_losses, ref_state, ref_snap, _, _ = _run(sched, opt, alpha)
    assert losses == ref_losses
    for got, ref in zip(state[0], ref_state[0]):
        assert got.dtype == ref.dtype and np.array_equal(got, ref)
    # the bytes do not change: meters == the copy path's == the plan
    assert snap["traffic"] == ref_snap["traffic"]
    rec = reconcile(plan, snap)
    assert rec.ok, [r for r in rec.rows if not r.match]
    inplace, staged = _expected_counts(sizes[0], opt, alpha)
    assert snap["opt_inplace_elems"] == [inplace]
    assert snap["opt_staged_elems"] == [staged]
    assert ref_snap["opt_inplace_elems"] == [0]
    if opt == 1.0:
        assert staged == 0 and inplace == STEPS * 3 * sum(sizes[0])
    if opt == 0.0:
        assert inplace == 0
    if (opt, alpha) == (0.75, 0.25):
        # the host share is the early segment: in place; the tail staged
        assert inplace == STEPS * 3 * sum(int(round(0.75 * n))
                                          for n in sizes[0])
        assert inplace and staged


def test_dp_ranks_match_copy_path_bit_for_bit(monkeypatch):
    """Two ranks, each its own coordinator over its shard of every
    vector, all of it in the host tier."""
    losses, state, snap, plan, sizes = _run("vertical", 1.0, 0.25, ranks=2)
    with monkeypatch.context() as mp:
        _copy_path(mp)
        ref_losses, ref_state, ref_snap, _, _ = _run("vertical", 1.0, 0.25,
                                                     ranks=2)
    assert losses == ref_losses
    for rank, ref_rank in zip(state, ref_state):
        for got, ref in zip(rank, ref_rank):
            assert np.array_equal(got, ref)
    assert snap["traffic"] == ref_snap["traffic"]
    assert reconcile(plan, snap).ok
    assert snap["opt_inplace_elems"] == [STEPS * 3 * sum(s) for s in sizes]
    assert snap["opt_staged_elems"] == [0, 0]


def _coordinator(root, n, opt, alpha, param_dtype=np.float32, tracer=None):
    """One layer's optimizer over fresh tiered vectors."""
    io = IOEngine(IOConfig(paths=[f"{root}/nvme0"]))
    meter = TrafficMeter()
    host, ssd = HostStore(meter), SSDStore(io.paths[0], meter, engine=io)
    rng = np.random.default_rng(n)
    init = rng.standard_normal(n, dtype=np.float32)

    def vec(name, x, dtype, arr, category="opt"):
        tv = TieredVector(name, n, dtype, x, host, ssd, category)
        tv.write_full(arr.astype(dtype))
        return [tv]

    coord = OptimizerStepCoordinator(
        vec("master:0", opt, np.float32, init),
        vec("m:0", opt, np.float32, np.zeros(n, np.float32)),
        vec("v:0", opt, np.float32, np.zeros(n, np.float32)),
        vec("param:0", PARAM, param_dtype, init, "param"), host, meter, io,
        CpuAdam(lr=1e-3), alpha)
    coord.tracer = tracer
    return coord, io, ssd


def _cast_values(n):
    rng = np.random.default_rng(3)
    x = rng.standard_normal(n, dtype=np.float32) * np.float32(1e3)
    special = np.array([0.0, -0.0, np.inf, -np.inf, np.nan, 65504.0,
                        65520.0, 1e6, -1e6, 6e-8, 1e-40, 1.00390625,
                        1.01171875, 3.4e38], np.float32)
    x[:special.size] = special
    # halfway between neighbouring bf16 values: rounds to even
    x[special.size:special.size + 8] = (
        np.arange(8, dtype=np.uint32) << 16 | 0x3F808000).view(np.float32)
    return x


@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float16],
                         ids=["bf16", "f16"])
@pytest.mark.parametrize("lo,hi", [(0, 2000), (300, 1000), (1100, 1900),
                                   (0, 1000)])
def test_cast_into_host_view_rounds_as_astype(tmp_path, dtype, lo, hi):
    """Host share cast straight into its view, SSD share through a
    buffer: the same bits as a full-length ``astype``, on either side
    of the parameters' tier boundary (element 1000 of 2000)."""
    n = 2000
    coord, io, ssd = _coordinator(tmp_path, n, 1.0, 0.0, dtype)
    mast = _cast_values(n)
    before = coord.params[0].read().copy()
    with np.errstate(over="ignore"):     # 65520 and up are inf in f16
        coord._write_params(0, mast[lo:hi], lo, hi)
        want = before.copy()
        want[lo:hi] = mast[lo:hi].astype(dtype)
    got = coord.params[0].read()
    assert np.array_equal(got.view(np.uint16), want.view(np.uint16))
    ssd.close()
    io.shutdown()


@pytest.mark.parametrize("outcome", ["hit", "unused"])
def test_hint_on_host_resident_tail_settles_as_before(tmp_path, monkeypatch,
                                                      outcome):
    """``prefetch_late`` over a tail wholly in the host tier hands the
    flush views (no bytes move) and settles as the copy path's hint
    does: ``hit`` when it landed, ``unused`` when there is no tail."""
    n, step = 5000, 1
    g = jnp.asarray(np.random.default_rng(1).standard_normal(
        n, dtype=np.float32) * np.float32(1e-2))

    def run(root):
        tr = Tracer()
        tr.enable()
        coord, io, ssd = _coordinator(root, n, 1.0, 0.25, tracer=tr)
        if outcome == "hit":
            coord.submit_early(0, g, step)
        coord.prefetch_late(0)
        pre = coord._late_pre[0]
        state = pre.result()
        coord.flush_late(0, step)
        coord.wait_all()
        hints = sorted(s[1] for s in tr.spans() if s[0] == "hints/opt")
        out = (hints, coord.la_hits, coord.la_misses,
               dict(coord.meter.snapshot()),
               [v[0].read().copy() for v in (coord.masters, coord.ms,
                                             coord.vs, coord.params)])
        host = coord.masters[0].host.get("master:0:h")
        shared = np.shares_memory(state[0], host)
        ssd.close()
        io.shutdown()
        return out, shared

    got, shared = run(tmp_path / "view")
    assert shared
    with monkeypatch.context() as mp:
        _copy_path(mp)
        ref, ref_shared = run(tmp_path / "copy")
    assert not ref_shared
    assert got[:4] == ref[:4]
    assert got[0] == [f"opt:{outcome}"]
    for a, b in zip(got[4], ref[4]):
        assert np.array_equal(a, b)


def test_segment_failing_mid_update_unwinds_and_next_step_runs():
    """An Adam segment raising halfway: the step fails, ``clear()``
    leaves no retained α-tail gradient, the prefix it updated through
    the view stays applied, and the next step runs."""
    with tempfile.TemporaryDirectory() as d:
        eng = OffloadEngine(CFG, _ocfg("vertical", 1.0, 0.25),
                            jax.random.PRNGKey(7), d)
        data = SyntheticLM(CFG.vocab_size, seed=0)
        eng.train_step(data.batch(M * MB, S))
        adam = eng.opt_c.adam
        real = adam.update
        seen = {}
        lock = threading.Lock()

        def half_then_fail(master, m, v, grad, step, lo=0, hi=None):
            with lock:
                first = not seen
                seen["called"] = True
            if not first:        # only the first segment fails
                return real(master, m, v, grad, step, lo, hi)
            mid = master.size // 2
            seen["master"], seen["before"] = master, master.copy()
            real(master, m, v, grad, step, 0, mid)
            seen["mid"] = mid
            raise RuntimeError("adam fault")

        adam.update = half_then_fail
        with pytest.raises(RuntimeError, match="adam fault"):
            eng.train_step(data.batch(M * MB, S))
        adam.update = real
        assert not any(f"pending_grad:{l}" in eng.host
                       for l in range(eng.L))
        master, before, mid = seen["master"], seen["before"], seen["mid"]
        assert not np.array_equal(master[:mid], before[:mid])
        assert np.array_equal(master[mid:], before[mid:])
        loss = eng.train_step(data.batch(M * MB, S))
        assert np.isfinite(loss)
        eng.finish()
        assert eng.ioe.metrics_snapshot()["inflight_bytes"] == 0
        eng.close()


@pytest.fixture
def span_split(monkeypatch):
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec = importlib.util.spec_from_file_location("span_split", SPAN_SPLIT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_span_split_reads_opt_inplace_share(span_split):
    """Hand-made spans: in-place elements over three vectors of ``n``;
    None when no ``opt.state_read`` span carries ``inplace``."""
    def sp(name, **a):
        return ("cpu-adam_0", name, "work", 0.0, 1.0, a)

    spans = [sp("opt.state_read", l=0, seg="early", n=300, inplace=900),
             sp("opt.state_read", l=0, seg="late", n=100, inplace=0),
             sp("opt.adam", l=0, seg="early", n=300, threads=1)]
    assert span_split.opt_inplace_share(spans) == 0.75
    assert span_split.opt_inplace_share(
        [sp("opt.state_read", l=0, seg="early", n=300)]) is None
    assert span_split.opt_inplace_share([]) is None


def test_counters_lose_no_update_under_concurrent_segments(tmp_path):
    """Segments of different layers count from different request
    workers at once; the counters' read-modify-write holds its lock."""
    coord, io, ssd = _coordinator(tmp_path, 64, 1.0, 0.25)
    views = [np.zeros(1), None, np.zeros(1)]
    workers, rounds = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda: [
            coord._count(views, 7) for _ in range(rounds)])
            for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        ssd.close()
        io.shutdown()
    assert coord.inplace_elems == workers * rounds * 14
    assert coord.staged_elems == workers * rounds * 7
