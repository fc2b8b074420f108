"""repro.serve tests: the KV plan lint (meta-test of the serve hint
contract), the three-way KV byte-exactness sweep (plan prediction ==
measured meters == ``traffic.kv_traffic`` closed form), admission
control (eager budget refusal, preempt-to-SSD-and-bitwise-resume),
``metrics_snapshot()`` as the one metrics surface, and the eager
config-validation parity contract."""
import json
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import Op, Plan, PlanOp, PlanSpec
from repro.core.traffic import kv_blocks, kv_traffic
from repro.io import IOConfig
from repro.models import model as mdl
from repro.offload import OffloadConfig, OffloadEngine, make_engine
from repro.serve import (ServeConfig, ServeEngine, compile_serve_step,
                         lint_kv_plan)

CFG = get_config("gpt-tiny")
MAX_LEN = 12            # engine-wide: fixed so jit caches stay warm
PROMPT_LEN = 4
BB = 4096               # kv block size for every serve test


def _blocks_per_request(max_len=MAX_LEN, bb=BB):
    template = mdl.init_caches(CFG, 1, max_len, dtype=jnp.float32)
    return sum(kv_blocks(nb, bb)
               for nb in mdl.cache_unit_nbytes(CFG, template))


def _engine(workdir, *, capacity_requests=8, **kw):
    """ServeEngine with a KV budget of exactly ``capacity_requests``
    requests' worth of blocks."""
    budget = capacity_requests * _blocks_per_request() * BB
    scfg = ServeConfig(max_len=MAX_LEN, kv_block_bytes=BB,
                       kv_budget_bytes=budget, **kw)
    return ServeEngine(CFG, scfg, jax.random.PRNGKey(0), workdir)


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, CFG.vocab_size, PROMPT_LEN)]
            for _ in range(n)]


def _drain(eng, preempt_rid=None, preempt_after=2):
    """Step to completion, optionally preempting one request once."""
    steps = 0
    while eng.pending():
        eng.step()
        steps += 1
        if preempt_rid is not None and steps == preempt_after and \
                eng.requests[preempt_rid].state == "running":
            eng.preempt(preempt_rid)
        assert steps < 200, "serve loop did not converge"


# ---------------------------------------------------------------------------
# KV plan lint (meta-test)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("evict,resume,prefill,decode", [
    ((), (), (0, 1), ()),
    ((), (), (2,), (0, 1)),
    ((0,), (), (), (1, 2)),
    ((0,), (0,), (), (1,)),          # same-step evict + resume
    ((0, 1), (2, 3), (4,), (5,)),
])
def test_compiled_plans_pass_lint(depth, evict, resume, prefill, decode):
    plan = compile_serve_step(4, evict=evict, resume=resume,
                              prefill=prefill, decode=decode,
                              prefetch_depth=depth)
    assert lint_kv_plan(plan) == []


def test_every_fetch_kv_has_exactly_one_hint():
    plan = compile_serve_step(4, evict=(0,), resume=(0, 1), decode=(2,),
                              prefetch_depth=2)
    hints, fetches = {}, {}
    for op in plan.ops:
        if op.op is Op.PREFETCH_KV:
            hints[(op.l, op.m)] = hints.get((op.l, op.m), 0) + 1
        elif op.op is Op.FETCH_KV:
            fetches[(op.l, op.m)] = fetches.get((op.l, op.m), 0) + 1
    assert fetches and set(hints) == set(fetches)
    assert all(n == 1 for n in hints.values())
    assert all(n == 1 for n in fetches.values())


def test_depth_zero_plan_is_hint_free_and_legal():
    plan = compile_serve_step(3, evict=(0,), resume=(0,), decode=(1,),
                              prefetch_depth=0)
    assert plan.count(Op.PREFETCH_KV) == 0
    assert plan.count(Op.FETCH_KV) == 3
    assert lint_kv_plan(plan) == []


def _raw(ops):
    return Plan(schedule="serve", spec=PlanSpec(L=2, M=1), W=1,
                ops=tuple(ops))


def test_lint_catches_hint_crossing_eviction():
    # hint issued BEFORE a SPILL_KV that its fetch then reads past
    plan = _raw([PlanOp(Op.PREFETCH_KV, l=0, m=1),
                 PlanOp(Op.SPILL_KV, l=0, m=2),
                 PlanOp(Op.FETCH_KV, l=0, m=1)])
    assert any("crosses" in e for e in lint_kv_plan(plan))


def test_lint_catches_orphan_and_missing_hints():
    orphan = _raw([PlanOp(Op.PREFETCH_KV, l=0, m=1)])
    assert any("orphan" in e for e in lint_kv_plan(orphan))
    # a hinted plan where one fetch has no hint
    missing = _raw([PlanOp(Op.PREFETCH_KV, l=0, m=1),
                    PlanOp(Op.FETCH_KV, l=0, m=1),
                    PlanOp(Op.FETCH_KV, l=1, m=1)])
    assert any("0 hint" in e for e in lint_kv_plan(missing))


def test_lint_catches_hint_after_fetch():
    plan = _raw([PlanOp(Op.FETCH_KV, l=0, m=1),
                 PlanOp(Op.PREFETCH_KV, l=0, m=1)])
    errs = lint_kv_plan(plan)
    assert any("not before" in e for e in errs)


# ---------------------------------------------------------------------------
# three-way byte exactness: plan == meter == closed form
# ---------------------------------------------------------------------------
def _assert_three_way(eng):
    measured = {k: int(v) for k, v in eng.meter.bytes.items()}
    predicted = {k: int(v) for k, v in eng.predicted_traffic.items()}
    for k in set(measured) | set(predicted):
        assert measured.get(k, 0) == predicted.get(k, 0), \
            (k, measured, predicted)
    kt = kv_traffic(eng.kv_unit_nbytes, eng.scfg.kv_block_bytes,
                    eng.scfg.kv_x_host, eng.kv_spills, eng.kv_fetches)
    assert measured.get(("kv", "gpu->cpu"), 0) == kt.spill
    assert measured.get(("kv", "cpu->ssd"), 0) == kt.ssd_spill
    assert measured.get(("kv", "cpu->gpu"), 0) == kt.fetch
    assert measured.get(("kv", "ssd->cpu"), 0) == kt.ssd_fetch
    # param closed form: every executed step fetches every unit once
    steps = eng.step_num
    assert measured.get(("param", "cpu->gpu"), 0) == \
        steps * sum(eng.param_unit_nbytes)
    assert measured.get(("param", "ssd->cpu"), 0) == \
        steps * sum(nb - int(round(eng.scfg.param_x_host * nb))
                    for nb in eng.param_unit_nbytes)


@pytest.mark.parametrize("kv_x,p_x", [(0.0, 1.0), (0.5, 0.5), (1.0, 0.0)])
@pytest.mark.parametrize("batch,gen", [(1, 2), (3, 3)])
def test_three_way_exactness_sweep(batch, gen, kv_x, p_x):
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, capacity_requests=max(1, batch - 1),
                      kv_x_host=kv_x, param_x_host=p_x)
        rids = [eng.submit(p, gen) for p in _prompts(batch)]
        _drain(eng, preempt_rid=rids[0] if batch > 1 and gen > 2 else None)
        assert all(len(eng.result(r)) == gen for r in rids)
        _assert_three_way(eng)
        if batch > 1 and gen > 2:        # the preempt really happened
            assert eng.preempted >= 1 and sum(eng.kv_fetches) > 0
        eng.close()


def test_three_way_exactness_under_queueing_and_preempt():
    """Capacity 2 < 3 requests: head-of-line queueing + an explicit
    preempt round-trip, still byte-exact on every (category, route)."""
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, capacity_requests=2)
        rids = [eng.submit(p, 4) for p in _prompts(3)]
        eng.step()
        assert eng.requests[rids[2]].state == "waiting"
        eng.preempt(rids[1])
        _drain(eng)
        assert all(len(eng.result(r)) == 4 for r in rids)
        assert eng.preempted >= 1
        _assert_three_way(eng)
        eng.close()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------
def test_submit_refuses_oversized_request():
    """A request whose block footprint alone exceeds the KV budget is
    refused eagerly — before any I/O happens."""
    with tempfile.TemporaryDirectory() as d:
        budget = (_blocks_per_request() - 1) * BB
        scfg = ServeConfig(max_len=MAX_LEN, kv_block_bytes=BB,
                           kv_budget_bytes=budget)
        eng = ServeEngine(CFG, scfg, jax.random.PRNGKey(0), d)
        with pytest.raises(ValueError, match="budget"):
            eng.submit(_prompts(1)[0], 2)
        eng.close()


def test_submit_validates_length_and_prompt():
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(list(range(PROMPT_LEN)), MAX_LEN)
        with pytest.raises(ValueError):
            eng.submit([], 2)
        with pytest.raises(ValueError):
            eng.submit([1, 2], 0)
        eng.close()


def test_preempt_requires_running_request():
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d)
        rid = eng.submit(_prompts(1)[0], 2)
        with pytest.raises(ValueError):
            eng.preempt(rid)            # still waiting
        _drain(eng)
        with pytest.raises(ValueError):
            eng.preempt(rid)            # finished
        eng.close()


def test_two_concurrent_under_partial_budget():
    """>= 2 requests run concurrently under a KV budget smaller than
    the total KV footprint of all admitted requests."""
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, capacity_requests=2)
        rids = [eng.submit(p, 3) for p in _prompts(3)]
        total_blocks = 3 * _blocks_per_request()
        assert eng.capacity_blocks < total_blocks
        eng.step()
        running = [r for r in rids
                   if eng.requests[r].state == "running"]
        assert len(running) == 2
        assert eng.used_blocks == 2 * _blocks_per_request()
        _drain(eng)
        assert all(len(eng.result(r)) == 3 for r in rids)
        assert eng.used_blocks == 0
        eng.close()


def _reference_logits(prompt, gen):
    """Pure-jit in-memory B=1 decode — the bitwise f32 reference."""
    params = mdl.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    prefill = jax.jit(lambda p, b, c: mdl.prefill(p, CFG, b, c))
    decode = jax.jit(lambda p, t, pos, c: mdl.decode_step(p, CFG, t, pos, c))
    caches = mdl.init_caches(CFG, 1, MAX_LEN, dtype=jnp.float32)
    logits, caches = prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, caches)
    out, toks = [np.asarray(logits)], [int(jnp.argmax(logits[0]))]
    for i in range(gen - 1):
        logits, caches = decode(
            params, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray(len(prompt) + i, jnp.int32), caches)
        out.append(np.asarray(logits))
        toks.append(int(jnp.argmax(logits[0])))
    return out, toks


def test_preempt_to_ssd_and_resume_is_bitwise():
    """The acceptance invariant: a request preempted to the tiers and
    resumed produces BITWISE-identical f32 logits (and tokens) to an
    uninterrupted in-memory decode."""
    prompts = _prompts(2)
    gen = 5
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, record_logits=True)
        rids = [eng.submit(p, gen) for p in prompts]
        eng.step()
        eng.step()
        eng.preempt(rids[0])             # spill mid-generation
        _drain(eng)
        assert eng.requests[rids[0]].evictions >= 1
        for rid, prompt in zip(rids, prompts):
            ref_logits, ref_toks = _reference_logits(prompt, gen)
            assert eng.result(rid) == ref_toks
            got = eng.requests[rid].logits
            assert len(got) == len(ref_logits) == gen
            for g, r in zip(got, ref_logits):
                np.testing.assert_array_equal(g, r)
        _assert_three_way(eng)
        eng.close()


def test_serve_snapshot_round_trips_json():
    with tempfile.TemporaryDirectory() as d:
        eng = _engine(d, capacity_requests=1)
        rids = [eng.submit(p, 3) for p in _prompts(2)]
        _drain(eng, preempt_rid=rids[0])
        snap = eng.metrics_snapshot()
        again = json.loads(json.dumps(snap))
        assert again["version"] == snap["version"] >= 1
        assert again["schedule"] == "serve"
        assert again["kv"]["capacity_blocks"] == _blocks_per_request()
        assert 0.0 <= again["kv"]["hit_rate"] <= 1.0
        assert again["tokens_decoded"] == eng.tokens_decoded > 0
        # predicted side rides along for offline reconciliation
        meas = {k: int(v) for k, v in again["traffic"][0].items()}
        assert meas == {k: int(v) for k, v in again["predicted"].items()}
        eng.close()


# ---------------------------------------------------------------------------
# eager config validation parity (OffloadConfig / IOConfig / ServeConfig)
# ---------------------------------------------------------------------------
def test_offload_config_rejects_unknown_schedule_eagerly():
    with pytest.raises(ValueError, match="schedule"):
        OffloadConfig(schedule="diagonal")


def test_offload_config_rejects_unknown_activation_policy_eagerly():
    with pytest.raises(ValueError, match="activation_policy"):
        OffloadConfig(activation_policy="teleport")


def test_io_config_rejects_unknown_path_policy_eagerly():
    with pytest.raises(ValueError, match="path_policy"):
        IOConfig(paths=["/tmp/x"], path_policy="psychic")


@pytest.mark.parametrize("kw", [
    {"kv_block_bytes": 0}, {"kv_budget_bytes": -1}, {"kv_x_host": 1.5},
    {"param_x_host": -0.1}, {"prefetch_depth": -1}, {"max_len": 1},
])
def test_serve_config_rejects_bad_values_eagerly(kw):
    with pytest.raises(ValueError):
        ServeConfig(**kw)


# ---------------------------------------------------------------------------
# make_engine factory + the one metrics surface
# ---------------------------------------------------------------------------
_OCFG = dict(num_microbatches=2, micro_batch=1, seq_len=32)


def test_make_engine_dispatch_and_io_override():
    with tempfile.TemporaryDirectory() as d:
        io_cfg = IOConfig(paths=[d], chunk_bytes=128 << 10)
        eng = make_engine(CFG, OffloadConfig(**_OCFG), jax.random.PRNGKey(0),
                          d, io_cfg=io_cfg)
        assert isinstance(eng, OffloadEngine)
        assert eng.ocfg.io is io_cfg
        eng.close()
    with pytest.raises(ValueError, match="num_ranks"):
        make_engine(CFG, OffloadConfig(**_OCFG), jax.random.PRNGKey(0),
                    "/tmp/x", num_ranks=0)


def test_make_engine_builds_dp():
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, OffloadConfig(**_OCFG), jax.random.PRNGKey(0),
                          d, num_ranks=2)
        assert isinstance(eng, OffloadEngine)
        assert eng.R == len(eng.ranks) == 2
        eng.close()


@pytest.mark.parametrize("R", [1, 2])
def test_metrics_snapshot_is_the_only_metrics_surface(R):
    """``metrics_snapshot()`` raises no ``DeprecationWarning`` on the
    engine or on any rank's I/O engine, and the removed ``stats()``
    shims are gone from both."""
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(CFG, OffloadConfig(**_OCFG), jax.random.PRNGKey(0),
                          d, num_ranks=R)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            snap = eng.metrics_snapshot()
            for rk in eng.ranks:
                rk.ioe.metrics_snapshot()
        assert snap["version"] >= 1 and snap["ranks"] == R
        assert not hasattr(eng, "stats")
        assert not any(hasattr(rk.ioe, "stats") for rk in eng.ranks)
        eng.close()
