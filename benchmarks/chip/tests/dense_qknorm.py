"""A model family the committed benchmark does not have, for the tests:
the dense decoder with a per-head RMSNorm on queries and keys before the
rotary embedding, as the program runs it under ``use_qk_norm`` (Qwen3
and Gemma 3 have it). Its leaves ``attn/k_norm`` and ``attn/q_norm``,
``head_dim`` wide with zero scales applied as 1 + scale, sit beside the
dense ones.

A test copies this file into its own benchmark's ``models/`` and names it
in a configuration: a family is added as a file, with no edit to the
harness. The rest is the dense family's, loaded from beside it.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import jax.numpy as jnp

import harness
from reference import _rms

dense = harness.load_module(Path(__file__).with_name("dense.py"),
                            f"{__name__}_dense")

Arch = dense.Arch
layer_kind = dense.layer_kind
#: the norms hold no matrix and score nothing: the dense count stands
flops_per_token = dense.flops_per_token
NORMS = ("attn/k_norm", "attn/q_norm")


def layer_leaves(a, l):
    return sorted(dense.layer_leaves(a, l)
                  + [(n, (a.head_dim,)) for n in NORMS])


def init_params(a, key):
    """The dense weights; the program draws no key for the zero norms."""
    p = dense.init_params(a, key)
    for l in range(a.layers):
        p[l].update({n: jnp.zeros((a.head_dim,), jnp.float32)
                     for n in NORMS})
    return p


def block(a, mode: str, p, x, kind):
    """The dense layer with q and k normed per head before the rotary."""
    return dense.block(a, mode, p, x, kind, qk=lambda pp, q, k: (
        _rms(q, pp["attn/q_norm"], a.eps), _rms(k, pp["attn/k_norm"], a.eps)))


def arch_config(c: dict):
    return dataclasses.replace(dense.arch_config(c), use_qk_norm=True)
