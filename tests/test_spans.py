"""The host's critical-path spans: ``Tracer.span`` / ``Tracer.totals``
and the sites that time the waits where they happen.

* Off, a span is one flag test: no clock read, no profiler annotation,
  nothing in the ring — and a whole engine step reads the clock from no
  site but the executor's op loop, exactly as often as before.
* On, every span name appears on a tiny α > 0 engine, each Adam segment
  once per layer per step, the executor-thread leaves inside their op
  span, and ``io.budget_wait`` only when the in-flight budget is tight.
"""
import collections
import tempfile
import time as _time

import jax
import pytest

import repro.io.engine as io_engine
import repro.obs.tracer as tracer_mod
import repro.offload.coordinators as coordinators
import repro.offload.executor as executor
from repro.configs.base import ArchConfig
from repro.core.perfmodel import StorageRatios
from repro.data import SyntheticLM
from repro.io import IOConfig
from repro.obs import NO_SPAN, Tracer
from repro.offload import OffloadConfig, OffloadEngine

CFG = ArchConfig(name="spans-tiny", family="dense", source="test",
                 num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                 head_dim=16, d_ff=64, vocab_size=256, act="gelu")
M, MB, S = 2, 1, 16

#: the executor thread's leaf spans, each inside one op span
EXEC_LEAVES = ("exec.h2d", "exec.device_wait", "exec.d2h",
               "exec.param_wait", "opt.early_wait")
WORKER_SPANS = ("param.gate", "param.read", "opt.state_read", "opt.adam",
                "opt.state_write")


class _CountingClock:
    """Stands in for a module's ``time``: counts ``perf_counter``."""

    def __init__(self):
        self.calls = 0

    def perf_counter(self):
        self.calls += 1
        return _time.perf_counter()

    def __getattr__(self, name):
        return getattr(_time, name)


class _FakeAnnotation:
    """Records what ``TraceAnnotation`` was asked to do."""
    log = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        self.log.append(("enter", self.name, self.args))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.args))


def _engine(workdir, trace, alpha=0.5, io=None):
    ocfg = OffloadConfig(schedule="vertical", num_microbatches=M,
                         micro_batch=MB, seq_len=S, alpha=alpha,
                         ratios=StorageRatios(0.0, 0.0, 0.0), io=io,
                         trace=trace)
    return OffloadEngine(CFG, ocfg, jax.random.PRNGKey(3), workdir)


def _steps(eng, n):
    data = SyntheticLM(CFG.vocab_size, seed=0)
    for _ in range(n):
        eng.train_step(data.batch(M * MB, S))


# ---------------------------------------------------------------- unit

def test_span_off_reads_no_clock_and_records_nothing(monkeypatch):
    tr = Tracer()
    clock = _CountingClock()
    monkeypatch.setattr(tracer_mod, "time", clock)
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", None)   # unused
    sp = tr.span("t", "x", "c", l=1)
    assert sp is NO_SPAN
    with sp:
        pass
    assert clock.calls == 0 and len(tr) == 0


def test_span_on_records_ring_span_and_profiler_annotation(monkeypatch):
    monkeypatch.setattr(tracer_mod, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.log = []
    tr = Tracer()
    tr.enable()
    with tr.span("exec", "exec.h2d", "copy", bytes=8, l=0) as sp:
        assert _FakeAnnotation.log == [("enter", "exec.h2d",
                                        {"bytes": 8, "l": 0})]
        t_in = _time.perf_counter()
    assert _FakeAnnotation.log[-1] == ("exit", "exec.h2d",
                                       {"bytes": 8, "l": 0})
    (track, name, cat, t0, t1, args), = tr.spans()
    assert (track, name, cat, args) == ("exec", "exec.h2d", "copy",
                                        {"bytes": 8, "l": 0})
    assert t0 == sp.t0 <= t_in <= t1
    # a body that raises still closes both, and the error propagates
    with pytest.raises(KeyError):
        with tr.span("w", "opt.adam", "work"):
            raise KeyError("x")
    assert [s[1] for s in tr.spans()] == ["exec.h2d", "opt.adam"]
    assert _FakeAnnotation.log[-1][:2] == ("exit", "opt.adam")


def test_totals_sums_complete_spans_by_name():
    tr = Tracer()
    tr.enable()
    tr.record("a", "opt.adam", "work", 1.0, 1.5)
    tr.record("b", "opt.adam", "work", 1.2, 2.2)     # concurrent: both count
    tr.record("a", "exec.h2d", "copy", 3.0, 3.25)
    tr.instant("a", "mark", "hint")
    assert tr.totals() == pytest.approx({"opt.adam": 1.5, "exec.h2d": 0.25})
    tr.clear()
    assert tr.totals() == {}


# ---------------------------------------------------------------- engine

def test_tracing_off_step_adds_no_clock_read_and_no_span(monkeypatch):
    clocks = {}
    for mod in (tracer_mod, coordinators, io_engine, executor):
        clocks[mod.__name__] = _CountingClock()
        monkeypatch.setattr(mod, "time", clocks[mod.__name__])
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, trace=False)
        _steps(eng, 1)          # compiles; the counted step follows
        monkeypatch.setattr(tracer_mod, "TraceAnnotation", None)
        for c in clocks.values():
            c.calls = 0
        _steps(eng, 1)
        eng.finish()
        n_spans = len(eng.tracer)
        ops = eng.plan.ops
        eng.close()
    assert n_spans == 0
    assert clocks["repro.obs.tracer"].calls == 0
    assert clocks["repro.offload.coordinators"].calls == 0
    assert clocks["repro.io.engine"].calls == 0
    # the op loop's own measurement: the start, two reads per op, one
    # per phase flip and the closing flip — nothing more
    n_phase = sum(op.op.name == "PHASE" for op in ops)
    assert clocks["repro.offload.executor"].calls \
        == 2 + 2 * len(ops) + n_phase


def test_tracing_on_names_every_wait_where_it_happens():
    steps = 2
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, trace=True)
        _steps(eng, steps)
        eng.finish()            # the last step's late segments land
        spans = eng.tracer.spans()
        dropped = eng.tracer.dropped
        eng.close()
    assert dropped == 0
    names = {s[1] for s in spans}
    for name in EXEC_LEAVES + WORKER_SPANS:
        assert name in names, name
    # one Adam update per layer and segment per step
    adam = collections.Counter((s[5]["l"], s[5]["seg"]) for s in spans
                               if s[1] == "opt.adam")
    assert adam == {(l, seg): steps for l in range(CFG.num_layers)
                    for seg in ("early", "late")}
    for s in spans:
        if s[1] in ("opt.state_read", "opt.state_write"):
            assert s[5]["seg"] in ("early", "late") and s[5]["n"] > 0
    # the executor thread's leaves lie inside their op span
    ops = [s for s in spans if s[0] == "exec" and s[2] == "plan"]
    leaves = [s for s in spans if s[1] in EXEC_LEAVES]
    assert leaves and all(s[0] == "exec" for s in leaves)
    for leaf in leaves:
        assert any(o[3] <= leaf[3] and leaf[4] <= o[4] for o in ops), leaf
    h2d = [s[5] for s in spans if s[1] == "exec.h2d"]
    assert {a["stream"] for a in h2d} >= {"param", "ckpt"}
    assert all(a["bytes"] > 0 and {"l", "m"} <= set(a) for a in h2d)
    d2h = {s[5]["stream"] for s in spans if s[1] == "exec.d2h"}
    assert "grad" in d2h
    assert "io.budget_wait" not in names      # 1 GiB budget: never full


def test_budget_wait_only_when_the_inflight_budget_is_tight():
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, trace=False)
        grad_bytes = eng.P * 4            # one layer's f32 gradient
        eng.close()
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, trace=True,
                      io=IOConfig(inflight_bytes=grad_bytes))
        _steps(eng, 2)
        eng.finish()
        waits = [s for s in eng.tracer.spans() if s[1] == "io.budget_wait"]
        eng.close()
    assert waits
    for _track, _name, cat, t0, t1, args in waits:
        assert cat == "wait" and t1 >= t0
        assert {"category", "nbytes", "inflight"} <= set(args)
        assert args["inflight"] + args["nbytes"] > grad_bytes


def test_request_submitted_before_tracing_gets_a_transfer_span(tmp_path):
    """A request queued before the tracer was enabled still shows where
    its body ran (the waits it ends must be nameable); it has no
    queue-wait span, whose start was never stamped."""
    import threading

    from repro.io import IOEngine, IOPriority
    tr = Tracer()
    eng = IOEngine(IOConfig(workers=1), default_root=str(tmp_path),
                   tracer=tr)
    try:
        gate = threading.Event()
        first = eng.submit(gate.wait, priority=IOPriority.OPTIMIZER_STATE,
                           category="opt", nbytes=8)
        second = eng.submit(lambda: 7, priority=IOPriority.OPTIMIZER_STATE,
                            category="opt", nbytes=8)
        for _ in range(1000):           # the one worker holds the first
            if first.running():
                break
            _time.sleep(0.01)
        assert first.running()
        tr.enable()
        gate.set()
        first.result(timeout=10)
        assert second.result(timeout=10) == 7
    finally:
        eng.shutdown()
    names = [s[1] for s in tr.spans()]
    assert names == ["opt"]
