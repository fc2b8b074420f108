"""Compile the main path's programs for a described TPU v5e, at real widths.

Nothing runs here: the TPU compiler, which is installed alongside JAX,
compiles for a chip that is described and not attached. It refuses what
the chip would refuse (a Pallas op Mosaic cannot lower, a block not
aligned to the tiling, a program larger than device memory), so these
tests catch such faults without a chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers all import every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_adam import fused_adam
from repro.kernels.selective_scan import selective_scan_fwd
from repro.models import blocks as blk
from repro.offload.engine import (_make_unflatten, build_block_fns,
                                  build_layer_fns)

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 << 30

#: phase B of chip_smoke.py: StarCoder2-7B, micro-batch 1, seq 2048, bf16
MB, SEQ = 1, 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-device compiles are written to, but cannot be read back
    # from, a persistent cache; keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _total_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _assert_mosaic(compiled):
    """The kernel was compiled, not interpreted."""
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    # StarCoder2-7B attention: 36 heads x 2048 x 128
    q = _sds((1, 36, SEQ, 128), jnp.bfloat16, one_chip)
    c = jax.jit(lambda q, k, v: flash_attention_fwd(
        q, k, v, interpret=False)).lower(q, q, q).compile()
    _assert_mosaic(c)


def test_selective_scan_compiles(one_chip):
    # Falcon-Mamba-7B scan: d_inner 8192, state 16
    cfg = get_config("falcon-mamba-7b")
    di, st = cfg.d_inner, cfg.ssm_state
    assert (di, st) == (8192, 16)
    x = _sds((1, SEQ, di), jnp.float32, one_chip)
    bc = _sds((1, SEQ, st), jnp.float32, one_chip)
    c = jax.jit(lambda x, dt, A, B, C, D: selective_scan_fwd(
        x, dt, A, B, C, D, interpret=False)).lower(
        x, x, _sds((di, st), jnp.float32, one_chip), bc, bc,
        _sds((di,), jnp.float32, one_chip)).compile()
    _assert_mosaic(c)


@pytest.fixture(scope="module")
def starcoder2_layer():
    """StarCoder2-7B's per-layer flat parameter count and jitted block
    functions, built from shapes alone (no parameter is materialised)."""
    cfg = get_config("starcoder2-7b")
    kind = blk.build_plan(cfg).period[0]
    tree = jax.eval_shape(
        lambda k: blk.block_init(k, cfg, kind, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [leaf.shape for leaf in leaves]
    P = sum(int(np.prod(s)) for s in shapes)
    fns = build_block_fns(cfg, kind,
                          _make_unflatten(treedef, shapes, jnp.bfloat16))
    return cfg, P, fns


def test_fused_adam_compiles_over_a_starcoder2_layer(one_chip,
                                                     starcoder2_layer):
    _, P, _ = starcoder2_layer
    vec = _sds((P,), jnp.float32, one_chip)
    step = _sds((), jnp.int32, one_chip)
    c = jax.jit(lambda p, m, v, g, t: fused_adam(
        p, m, v, g, t, interpret=False)).lower(vec, vec, vec, vec,
                                                step).compile()
    _assert_mosaic(c)


def test_layer_fwd_and_bwd_fit_one_v5e(one_chip, starcoder2_layer):
    cfg, P, fns = starcoder2_layer
    p = _sds((P,), jnp.bfloat16, one_chip)
    x = _sds((MB, SEQ, cfg.d_model), jnp.bfloat16, one_chip)
    fwd = fns["layer_fwd"].lower(p, x).compile()
    assert _total_bytes(fwd) < V5E_HBM_BYTES
    # BWD under the recompute policy: the residual forward, then the vjp
    fwd_bwd = jax.jit(lambda p, x, dy: fns["layer_bwd_res"](
        fns["layer_fwd_res"](p, x)[1], dy)).lower(p, x, x).compile()
    assert _total_bytes(fwd_bwd) < V5E_HBM_BYTES


def test_head_bwd_fits_one_v5e(one_chip, starcoder2_layer):
    cfg, _, fns = starcoder2_layer
    V, d = cfg.padded_vocab, cfg.d_model
    tok = _sds((MB, SEQ), jnp.int32, one_chip)
    c = fns["head_bwd"].lower(
        _sds((d, V), jnp.bfloat16, one_chip),
        _sds((d,), jnp.float32, one_chip),
        _sds((MB, SEQ, d), jnp.bfloat16, one_chip),
        tok, _sds((MB, SEQ), jnp.float32, one_chip),
        _sds((), jnp.float32, one_chip)).compile()
    assert _total_bytes(c) < V5E_HBM_BYTES


def test_deepseek_v2_moe_layer_fits_one_v5e(one_chip):
    """DeepSeek-V2-Lite's MoE layer at published widths, holding 16 of 64
    experts (the benchmark's share): MLA with YaRN, dropless grouped
    matmuls (``ragged_dot``) and the recompute-policy backward compile
    for the chip and fit its memory."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"),
                              moe_experts_held=16)
    kind = blk.build_plan(cfg).all_kinds()[1]
    assert kind.moe
    tree = jax.eval_shape(
        lambda k: blk.block_init(k, cfg, kind, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [leaf.shape for leaf in leaves]
    P = sum(int(np.prod(s)) for s in shapes)
    assert P == 169_611_776
    fns = build_layer_fns(cfg, kind,
                          _make_unflatten(treedef, shapes, jnp.bfloat16),
                          "_moe")
    p = _sds((P,), jnp.bfloat16, one_chip)
    x = _sds((MB, SEQ, cfg.d_model), jnp.bfloat16, one_chip)
    fwd_bwd = jax.jit(lambda p, x, dy: fns["layer_bwd_res"](
        fns["layer_fwd_res"](p, x)[1], dy)).lower(p, x, x).compile()
    assert _total_bytes(fwd_bwd) < V5E_HBM_BYTES
