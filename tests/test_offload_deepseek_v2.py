"""DeepSeek-V2 through the offload engine, at a tiny shape on the CPU.

A stack of one dense layer and two MoE layers (MLA with YaRN, 8 routed
experts of which a layer holds 4, top-2, one shared expert) trains
through ``make_engine`` and agrees with the chip benchmark's plain
float32 reference (``benchmarks/chip/models/deepseek_v2.py``, loaded by
path); its layers differ in size, and the plan, the closed forms and
the meters still agree byte for byte. The MoE layer's published routing
(no renormalisation, dropless) and its shares of the experts, and the
YaRN closed form, are checked on their own.
"""
import dataclasses
import json
import math
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.perfmodel import StorageRatios
from repro.core.plan import PlanCosts, plan_traffic
from repro.core.traffic import act_spill_traffic, wave_ckpt_traffic
from repro.models import moe as moe_lib
from repro.models.common import rope_setup, yarn_freqs, yarn_mscale
from repro.offload import (DataParallelOffloadEngine, OffloadConfig,
                           make_engine)

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

#: the tiny shape, in the benchmark configuration file's keys
TINY = {"hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "kv_lora_rank": 32, "intermediate_size": 96,
        "moe_intermediate_size": 32, "router_experts": 8,
        "n_routed_experts": 4, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "num_hidden_layers": 3, "vocab_size": 250,
        "table_rows": 256, "name": "tiny-dsv2"}
M, MB, S = 2, 1, 32
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def bench():
    """The benchmark's harness, reference and DeepSeek-V2 module."""
    sys.path.insert(0, str(CHIP))
    try:
        import compare
        import harness
        import program
        import reference
        import traffic_gen
        mod = harness.Registry(CHIP).model("deepseek_v2")
        yield {"compare": compare, "program": program, "model": mod,
               "reference": reference, "traffic_gen": traffic_gen}
    finally:
        sys.path.remove(str(CHIP))


def _file():
    c = json.loads((CHIP / "configs" / "deepseek-v2-lite.json").read_text())
    return dict(c, **TINY)


def _cfg(**kw):
    """The program's config of the tiny shape (a 1-dense + 2-MoE stack)."""
    base = dataclasses.replace(
        get_config("deepseek-v2-lite-16b"), num_layers=3, d_model=64,
        num_heads=2, num_kv_heads=2, head_dim=16, d_ff=96, vocab_size=250,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, moe_experts_held=4, moe_top_k=2,
        moe_d_ff=32, num_shared_experts=1)
    return dataclasses.replace(base, **kw)


# ------------------------------------------------------------- training
@pytest.fixture(scope="module")
def trained(bench):
    """Three steps of the engine and of the reference on the same rows."""
    model, program = bench["model"], bench["program"]
    c = _file()
    a = model.Arch.from_config(c)
    cfg = model.arch_config(c)
    ocfg = OffloadConfig(num_microbatches=M, micro_batch=MB, seq_len=S,
                         alpha=0.25, param_dtype="bfloat16", lr=1e-3,
                         ratios=StorageRatios(param=0.5, ckpt=0.0, opt=1.0,
                                              act=0.0))
    key = program.seed_key(SEED)
    batches = bench["traffic_gen"].batches(
        {"micro_batches": M, "micro_batch": MB, "seq_len": S}, 250, SEED, 3)
    segs = [program._segments(model.layer_leaves(a, l))
            for l in range(a.layers)]
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(cfg, ocfg, key, d)
        sizes = [v.n for v in eng.p_vecs]
        losses, grads, tokens = [], {}, []
        for i, b in enumerate(batches):
            losses.append(eng.train_step(b))
            tokens.append(dict(eng.moe_tokens))
            if i == 0:
                eng.finish()
                for l, tv in enumerate(eng.m_m):
                    v = tv.read()
                    for name, lo, hi in segs[l]:
                        grads[bench["reference"].leaf_name(l, name)] = \
                            program._norm(v[lo:hi]) / (1 - program.B1)
                for t in bench["reference"].HEAD_LEAVES:
                    m = eng.head_state[t]["m"]
                    grads[t] = float(jnp.sqrt(jnp.sum(jnp.square(m)))) \
                        / (1 - program.B1)
        eng.close()
    ref = bench["reference"].run(model, a, key, 1e-3, M, batches)
    return {"sizes": sizes, "losses": losses, "grads": grads, "ref": ref,
            "tokens": tokens, "a": a, "segs": segs}


def test_layer_sizes_follow_the_model_leaves(trained):
    declared = [s[-1][2] for s in trained["segs"]]
    assert trained["sizes"] == declared
    assert declared[0] != declared[1] == declared[2]


def test_engine_matches_reference(bench, trained):
    """Losses and per-leaf first-gradient norms against the plain f32
    reference: bfloat16 weights and matmul operands put the engine
    within 1.5e-3 of the reference's loss and 1e-2 of its gradient norms
    (the benchmark's own tiny-cell limits); a wrong equation, a missing
    expert or a renormalised gate moves them far past that."""
    nums = bench["compare"].numbers(
        {"losses": trained["losses"], "grad_norms": trained["grads"],
         "change_norms": trained["grads"]},
        dict(trained["ref"], change_norms=trained["ref"]["grad_norms"]))
    assert nums["loss_gap"] < 1.5e-3, nums
    assert nums["grad_gap"] < 1e-2, nums


def test_moe_token_counter(trained):
    """Two MoE layers x 2 micro-batches x 32 tokens x top-2: 256 routed
    assignments a step, of which the 4 held experts take some; no held
    expert can take more than every token of both micro-batches."""
    for t in trained["tokens"]:
        assert 0 < t["moe.tokens_held"] <= 2 * M * S * 2
        assert 0 < t["moe.tokens_max_expert"] <= M * S


def test_dp_engine_rejects_a_mixed_stack():
    ocfg = OffloadConfig(num_microbatches=2, micro_batch=1, seq_len=8)
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="data-parallel"):
            DataParallelOffloadEngine(_cfg(), ocfg, jax.random.PRNGKey(0), d,
                                      ranks=2)


# ------------------------------------------------------------- bytes
@pytest.mark.parametrize("policy", ["recompute", "spill"])
def test_plan_traffic_meters_unequal_layers(policy):
    """plan_traffic == closed forms == measured meters, byte for byte,
    on a stack whose layers differ in size (f32, every tier on SSD)."""
    cfg = _cfg()
    ocfg = OffloadConfig(num_microbatches=M, micro_batch=MB, seq_len=S,
                         alpha=0.25, param_dtype="float32",
                         activation_policy=policy,
                         ratios=StorageRatios(0.0, 0.0, 0.0))
    with tempfile.TemporaryDirectory() as d:
        eng = make_engine(cfg, ocfg, jax.random.PRNGKey(1), d)
        tok = np.random.default_rng(0).integers(0, 250, (M * MB, S),
                                                dtype=np.int32)
        for _ in range(2):
            eng.train_step(tok)
        eng.finish()
        measured = {k: v / 2 for k, v in eng.meter.bytes.items()}
        pred = plan_traffic(eng.plan, PlanCosts.from_engine(eng))
        sizes, acts = eng.layer_P, eng.layer_act_nbytes
        eng.close()
    assert len(set(sizes)) == 2
    ms = 4 * sum(sizes)
    u = MB * S * cfg.d_model * 4
    ct = wave_ckpt_traffic(cfg.num_layers * u, M, M, cfg.num_layers,
                           act_spill=policy == "spill")
    want = {("param", "ssd->cpu"): 2 * ms, ("param", "cpu->gpu"): 2 * ms,
            ("param", "cpu->ssd"): ms, ("grad", "gpu->cpu"): ms,
            ("opt", "ssd->cpu"): 3 * ms, ("opt", "cpu->ssd"): 3 * ms,
            ("ckpt", "gpu->cpu"): ct.write, ("ckpt", "cpu->gpu"): ct.read,
            ("ckpt", "cpu->ssd"): ct.ssd_spill,
            ("ckpt", "ssd->cpu"): ct.ssd_reread,
            ("inter_grad", "gpu->cpu"): ct.inter_grad / 2,
            ("inter_grad", "cpu->gpu"): ct.inter_grad / 2}
    if policy == "spill":
        at = act_spill_traffic(acts, M, cfg.num_layers)
        assert len(set(acts)) == 2
        want.update({("act", "gpu->cpu"): at.spill,
                     ("act", "cpu->gpu"): at.fetch,
                     ("act", "cpu->ssd"): at.ssd_spill,
                     ("act", "ssd->cpu"): at.ssd_reread})
    want = {k: v for k, v in want.items() if v}
    assert pred == want
    assert measured == pred


# ------------------------------------------------------------- the MoE layer
def _moe_input(cfg, seed=3):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 16, cfg.d_model),
                          jnp.float32)
    p = moe_lib.moe_init(jax.random.PRNGKey(seed + 1), cfg, jnp.float32)
    return x, p


def _dense_loop(p, x, cfg):
    """Every routed expert on every token, weighted by its top-k gate."""
    xf = x.reshape(-1, cfg.d_model)
    _, gates, idx = moe_lib.route(p, xf, cfg)
    y = jnp.zeros_like(xf)
    for e in range(cfg.held_experts):
        g = jnp.sum(jnp.where(idx == cfg.moe_expert_offset + e, gates, 0.0),
                    -1)
        h = jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e])
        y = y + g[:, None] * (h @ p["w_down"][e])
    return y.reshape(x.shape)


def test_shares_of_the_experts_add_up_to_the_layer():
    """Routed parts of the shares {0-3} and {4-7}, plus the shared
    expert once, equal the uncut layer."""
    cfg = _cfg(moe_experts_held=0)
    x, p = _moe_input(cfg)
    whole, _ = moe_lib.moe_apply(p, x, cfg)
    parts = []
    for lo in (0, 4):
        share = _cfg(moe_experts_held=4, moe_expert_offset=lo)
        ps = dict(p, **{k: p[k][lo:lo + 4]
                        for k in ("w_gate", "w_up", "w_down")})
        parts.append(moe_lib.moe_routed(ps, x, share)[0])
    shared = moe_lib.mlp_apply(p["shared"], x, cfg.act)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0
    assert float(jnp.max(jnp.abs(parts[1]))) > 0


def test_gates_are_not_renormalised():
    cfg = _cfg(moe_experts_held=0)
    x, p = _moe_input(cfg)
    xf = x.reshape(-1, cfg.d_model)
    probs, gates, idx = moe_lib.route(p, xf, cfg)
    np.testing.assert_allclose(gates, jnp.take_along_axis(probs, idx, -1))
    assert float(jnp.max(gates.sum(-1))) < 1.0
    _, g_norm, _ = moe_lib.route(p, xf, _cfg(moe_norm_topk=True))
    np.testing.assert_allclose(g_norm.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(moe_lib.moe_routed(p, x, cfg)[0],
                               _dense_loop(p, x, cfg), rtol=1e-5, atol=1e-5)


def test_dropless_keeps_every_token_under_skewed_routing():
    """A router that sends every token to experts 0 and 1: each takes all
    32 tokens, nothing is lost, and the output is the dense loop's; a
    capacity of 1.25 would have kept 10 of them."""
    cfg = _cfg(moe_experts_held=0)
    x, p = _moe_input(cfg)
    x = jnp.abs(x) + 0.1
    p["router"] = p["router"].at[:, :2].add(1.0)
    y, _, counts = moe_lib.moe_routed(p, x, cfg)
    assert counts.tolist() == [32, 32, 0, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(y, _dense_loop(p, x, cfg), rtol=1e-5,
                               atol=1e-5)
    _, _, cap = moe_lib.moe_routed(p, x, cfg, capacity_factor=1.25)
    assert cap.tolist()[:2] == [32, 32]     # counted before the drop
    y_cap = moe_lib.moe_routed(p, x, cfg, capacity_factor=1.25)[0]
    assert float(jnp.max(jnp.abs(y_cap - y))) > 1e-3


def _unwritten_tail(ragged_dot):
    """``ragged_dot`` as the TPU runs it: the rows past the groups, of the
    result and of the lhs cotangent, hold whatever memory held (NaN
    here) instead of zeros."""
    def tail(out, sizes):
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    @jax.custom_vjp
    def rd(a, w, sizes):
        return tail(ragged_dot(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return rd(a, w, sizes), (a, w, sizes)

    def bwd(res, dy):
        a, w, sizes = res
        dy = jnp.where(jnp.isnan(dy), 0.0, dy)
        _, vjp = jax.vjp(lambda aa, ww: ragged_dot(aa, ww, sizes), a, w)
        da, dw = vjp(dy)
        return tail(da, sizes), dw, None
    rd.defvjp(fwd, bwd)
    return lambda a, w, sizes, preferred_element_type=None: rd(
        a.astype(jnp.float32), w.astype(jnp.float32), sizes)


def test_rows_past_the_groups_never_reach_a_value_or_a_gradient(monkeypatch):
    """Dropless dispatch sorts every assignment and leaves the rows of
    experts held elsewhere past the groups; what the grouped matmul
    leaves there must not reach the layer's output or any gradient."""
    cfg = _cfg()
    x, p = _moe_input(cfg)
    p = dict(p, **{k: p[k][:4] for k in ("w_gate", "w_up", "w_down")})

    def loss(pp, xx):
        return jnp.sum(jnp.square(moe_lib.moe_routed(pp, xx, cfg)[0]))
    want = jax.grad(loss, (0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _unwritten_tail(jax.lax.ragged_dot))
    got = jax.grad(loss, (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- YaRN
def test_yarn_closed_form():
    """DeepSeek-V2-Lite's rope: 64 dims, theta 1e4, factor 40 over 4096,
    beta 32/1, mscale = mscale_all_dim = 0.707."""
    def dim(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))
    lo, hi = math.floor(dim(32)), math.ceil(dim(1))
    assert (lo, hi) == (10, 23)
    i = np.arange(32)
    f_extra = 1e4 ** (-2 * i / 64)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    want = f_extra / 40 * ramp + f_extra * (1 - ramp)
    got = np.asarray(yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    m = yarn_mscale(40.0, 0.707)
    assert m * m == pytest.approx(1.58963, abs=1e-5)
    inv, rot, temp = rope_setup(get_config("deepseek-v2-lite-16b"), 64, 1e4)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    assert rot == 1.0 and temp == pytest.approx(1.58963, abs=1e-5)
