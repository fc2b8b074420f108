"""The DeepSeek-V2 family as the benchmark knows it: its leaves add up to
the program's per-layer vectors at the published cut, its FLOP count,
its two per-layer metrics, and a whole run of a tiny DeepSeek-V2 cell on
the CPU, sound and with the published routing broken in the reference."""
import json

import jax
import pytest

import harness
import run
import tiny

CELL = "tiny-dsv2.ssd"
#: the tiny shape, in the configuration file's keys
TINY = {"hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "kv_lora_rank": 32, "intermediate_size": 96,
        "moe_intermediate_size": 32, "router_experts": 8,
        "n_routed_experts": 4, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "num_hidden_layers": 3, "vocab_size": 250,
        "table_rows": 256, "name": "tiny-dsv2"}


@pytest.fixture(scope="module")
def real():
    reg = harness.Registry()
    cfg = reg.config("deepseek-v2-lite")
    return reg, cfg, reg.model_of(cfg)


def test_leaves_sum_to_the_program_vectors(real):
    """81,007,104 for the dense layer, 169,611,776 for an MoE layer
    holding 16 experts: the program's block_init at the published cut."""
    import numpy as np
    from repro.models import blocks as blk
    reg, cfg, fam = real
    a = fam.Arch.from_config(cfg)
    sizes = [sum(int(np.prod(s)) for _, s in fam.layer_leaves(a, l))
             for l in range(a.layers)]
    assert sizes == [81_007_104] + [169_611_776] * 4
    prog = fam.arch_config(cfg)
    kinds = blk.build_plan(prog).all_kinds()
    for l in (0, 1):
        shapes = jax.eval_shape(lambda k: blk.block_init(k, prog, kinds[l]),
                                jax.random.PRNGKey(0))
        assert sum(x.size for x in jax.tree.leaves(shapes)) == sizes[l]
        names = ["/".join(str(getattr(p, "key", p)) for p in path)
                 for path, _ in jax.tree_util.tree_leaves_with_path(shapes)]
        assert names == [n for n, _ in fam.layer_leaves(a, l)]


def test_flops_per_token(real):
    """flops.py's rule: 6 x 467,402,752 matrix parameters a token (five
    layers' MLA projections, the dense MLP, four MoE layers' router,
    shared experts and 1.5 held experts a token, the unembedding) plus
    6 x 2048 x 5 x 16 x (192 + 128) for the attention scores."""
    _, cfg, fam = real
    assert fam.flops_per_token(cfg, 2048) == 3_118_989_312
    assert fam.moe_layer_flops_per_token(cfg, 2048) == 1_311_768_576


def _rec(ops, steps=1):
    return {"trace": {"device_ops": ops}, "window": {"steps": steps},
            "tokens_per_step": 32768, "chips": 1,
            "peaks": {"bf16_flops_per_s": 197e12}}


def test_moe_metrics_read_the_moe_jits():
    reg = harness.Registry()
    dev = reg.reader("moe.layer_device_s")
    mfu = reg.reader("moe.layer_mfu_pct")
    rec = _rec([["jit_layer_bwd_res_moe", 3.0], ["jit_head_bwd", 1.0],
                ["jit_layer_fwd_moe", 1.0], ["jit_layer_fwd_dense", 0.5]],
               steps=2)
    assert dev(rec) == pytest.approx(2.0)
    want = 100 * 1_311_768_576 * 32768 / (2.0 * 197e12)
    assert mfu(rec) == pytest.approx(want)
    for r in (_rec([["jit_layer_fwd", 1.0]]), {"trace": None}):
        assert dev(r) is None and mfu(r) is None


@pytest.fixture
def reg(tmp_path):
    c = dict(json.loads((tiny.CHIP / "configs" / "deepseek-v2-lite.json")
                        .read_text()), **TINY)
    bench = tiny.make(tmp_path, configs=(("tiny-dsv2", c),),
                      cells=((CELL, "tiny-dsv2", "ssd"),))
    return harness.Registry(tmp_path, bench)


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices()[0])


def _run(reg, capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2**34 + 21),
                   "--seconds", "0.5"], reg)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_dsv2_cell_runs_correct(reg, on_cpu, capsys):
    res = _run(reg, capsys)
    assert res["correct"], res["checks"]
    assert res["checks"]["bytes_mismatch"]["value"] == 0


def test_renormalised_gates_in_the_reference_are_not_correct(
        reg, on_cpu, capsys, monkeypatch):
    """The reference with DeepSeek-V3's renormalised top-k gates in
    place of V2's raw ones: the comparison sees the routing differ."""
    import jax.numpy as jnp
    fam = reg.model("deepseek_v2")

    def renormed(x, k):
        v, i = jax.lax.top_k(x, k)
        return v / jnp.sum(v, -1, keepdims=True), i

    class Lax:
        top_k = staticmethod(renormed)

        def __getattr__(self, name):
            return getattr(jax.lax, name)

    class Jax:
        lax = Lax()

        def __getattr__(self, name):
            return getattr(jax, name)
    monkeypatch.setattr(fam, "jax", Jax())     # the reference's alone
    res = _run(reg, capsys)
    assert not res["correct"], res["checks"]
