"""The trace reduction on a synthesized trace with known answers."""
import pytest

import trace_reduce as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"
S = 1e9   # ns per second


def _trace():
    ev = [(HOST, "python3", "bench.sync", 0.5 * S, 1e3),
          (HOST, "python3", "bench.step", 1 * S, 4 * S),
          (HOST, "python3", "bench.step", 5 * S, 5 * S)]
    # ops: [0.5, 1.5] is clipped to [1, 1.5]; [2, 3] and [2.5, 3.5] merge;
    # [6, 7]; [9.5, 11] is clipped to [9.5, 10]
    for t0, t1 in ((0.5, 1.5), (2, 3), (2.5, 3.5), (6, 7), (9.5, 11)):
        ev.append((DEV, tr.OPS_LINE, "fusion", t0 * S, (t1 - t0) * S))
    ev.append((DEV, tr.MODULES_LINE, "jit_layer_bwd_res(7)", 2 * S, 1.5 * S))
    ev.append((DEV, tr.MODULES_LINE, "jit_head_bwd(3)", 6 * S, 1 * S))
    ev.append((DEV, tr.MODULES_LINE, "jit_head_bwd(3)", 9.5 * S, 1.5 * S))
    return ev


def test_union_clip_and_gaps():
    assert tr.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]
    assert tr.clip([(0, 2), (5, 9)], 1, 6) == [(1, 2), (5, 6)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_busy_window_and_modules():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(9.0)
    # 0.5 + 1.5 + 1.0 + 0.5
    assert r["busy_s"] == pytest.approx(3.5)
    assert r["devices"] == 1
    assert r["device_ops"] == [["jit_head_bwd", pytest.approx(1.5)],
                               ["jit_layer_bwd_res", pytest.approx(1.5)]] \
        or r["device_ops"] == [["jit_layer_bwd_res", pytest.approx(1.5)],
                               ["jit_head_bwd", pytest.approx(1.5)]]


def test_idle_gaps_longest_first_and_named_by_host_span():
    spans = [("FETCH_PARAM l=1", 3.6 * S, 5.9 * S),
             ("WRITEBACK_GRAD l=0", 7.2 * S, 9.4 * S)]
    r = tr.reduce(_trace(), spans)
    # gaps: [1.5, 2] 0.5, [3.5, 6] 2.5, [7, 9.5] 2.5
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([2.5, 2.5, 0.5])
    assert {g[0] for g in r["idle_gaps"][:2]} == {"FETCH_PARAM l=1",
                                                  "WRITEBACK_GRAD l=0"}
    assert r["idle_gaps"][2][0] == "no host span"


def test_busy_averages_over_devices():
    ev = _trace() + [("/device:TPU:1", tr.OPS_LINE, "f", 1 * S, 9 * S)]
    r = tr.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((3.5 + 9.0) / 2)


def test_nothing_to_read():
    assert tr.reduce([(HOST, "python3", "other", 0, 1)]) is None
    no_ops = [e for e in _trace() if e[0] == HOST]
    assert tr.reduce(no_ops) is None


def test_host_spans_put_on_the_trace_clock():
    # bench.sync opened at trace time 0.5 s, host time 100.0 s
    spans = [("exec", "FETCH_PARAM", "plan", 101.0, 102.0, {"l": 2}),
             ("exec", "PHASE", "plan", 103.0, 103.5, {"l": -1}),
             ("exec", "open", "plan", 104.0, None, {})]
    out = tr.host_spans_on_trace_clock(_trace(), 100.0, spans)
    assert out == [("FETCH_PARAM l=2", pytest.approx(1.5 * S),
                    pytest.approx(2.5 * S)),
                   ("PHASE", pytest.approx(3.5 * S), pytest.approx(4 * S))]
