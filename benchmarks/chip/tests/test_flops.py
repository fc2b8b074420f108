"""The FLOP count against a hand count for one StarCoder2-7B layer, as
the dense family's module counts it by the shared rule."""
import json

import pytest

import flops
from harness import HERE, Registry

dense = Registry().model("dense")


def test_starcoder2_layer_by_hand():
    c = json.loads((HERE / "configs" / "starcoder2-7b.json").read_text())
    d, ff = 4608, 18432
    q = 36 * 128                     # = d
    kv = 4 * 128
    # wq, wk, wv, wo; then GELU MLP in and out
    by_hand = d * q + d * kv + d * kv + q * d + d * ff + ff * d
    assert by_hand == 217_055_232
    assert dense.layer_matrix_params(c) == by_hand
    per_token = 6 * (4 * by_hand + d * 49152) + 12 * 4 * 2048 * q
    assert dense.flops_per_token(c, 2048) == per_token
    assert per_token == pytest.approx(7.021e9, rel=1e-3)


def test_swiglu_counts_three_matrices():
    c = json.loads((HERE / "configs" / "phi3-medium-14b.json").read_text())
    d, ff = 5120, 17920
    attn = d * 5120 * 2 + 2 * d * 1280
    assert dense.layer_matrix_params(c) == attn + 3 * d * ff


def test_the_shared_rule_counts_unequal_head_sizes():
    # 16 heads scoring with 192-wide queries and keys, weighing 128-wide
    # values, over 4096 positions; 1000 matrix parameters a token
    assert flops.per_token(1000, 16 * (192 + 128), 4096) \
        == 6.0 * 1000 + 6 * 4096 * 16 * 320
