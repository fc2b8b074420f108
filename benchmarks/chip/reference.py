"""Plain float32 reference of the dense decoder the offload trainer runs.

It imports nothing of the program under test and takes nothing it made:
the weights are drawn again from the seed, by the same recipe the
program's initialiser follows (truncated normal, fan-in scale, rounded
to bfloat16, zero norm scales), and every later value is computed here.

The layer equations (each departure from the published models is listed
in the configuration files under ``departures``):

    h  = rmsnorm(x) * (1 + norm1)                  f32 statistics
    q, k, v = h Wq, h Wk, h Wv                     grouped-query heads
    q, k = rope(q), rope(k)                        split-halves rotary
    x  = x + causal_softmax(q k^T / sqrt(hd)) v Wo
    h  = rmsnorm(x) * (1 + norm2)
    x  = x + gelu_tanh(h W_in) W_out               or SwiGLU
    loss = sum_tokens xent(rmsnorm(x) * (1 + final_norm) U) / tokens

Matrix products run at ``Precision.HIGHEST``. The step is plain
synchronous Adam (b1 0.9, b2 0.95, eps 1e-8): the program's alpha-delay
only moves when the tail of each update runs, never what it computes.

Memory: parameters stay on the device in float32; the Adam moments live
in host memory and visit the device one leaf at a time, so a 4-layer
StarCoder2-7B cut (1.1 G parameters, 13 GB of f32 state) fits one chip.

``mode="control"`` is the same computation one precision step below what
the configuration states: matrix-product operands rounded to float8
(e4m3) where the configuration computes in bfloat16, and the gradient
handed to Adam, the weights and both moments kept in bfloat16 where the
configuration keeps float32. It exists to show that the comparison would
catch such a step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.95, 1e-8
HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "control")


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int              # rows of the embedding table as run
    layers: int
    theta: float
    eps: float
    act: str                # "gelu_tanh" | "swiglu"

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["table_rows"],
                   layers=c["num_hidden_layers"], theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), act=c["mlp"])


def layer_leaves(a: Arch) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name, shape) of one layer's tensors, in the order the program
    lays them out in its flat per-layer vector (sorted keys)."""
    qd, kd = a.heads * a.head_dim, a.kv_heads * a.head_dim
    mlp = ([("mlp/w_in", (a.d, a.d_ff)), ("mlp/w_out", (a.d_ff, a.d))]
           if a.act == "gelu_tanh" else
           [("mlp/w_down", (a.d_ff, a.d)), ("mlp/w_gate", (a.d, a.d_ff)),
            ("mlp/w_up", (a.d, a.d_ff))])
    return ([("attn/wk", (a.d, kd)), ("attn/wo", (qd, a.d)),
             ("attn/wq", (a.d, qd)), ("attn/wv", (a.d, kd))]
            + mlp + [("norm1", (a.d,)), ("norm2", (a.d,))])


HEAD_LEAVES = ("embed", "unembed", "final_norm")


# ---------------------------------------------------------------- init
def _trunc(key, shape, fan_in):
    std = 1.0 / np.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              jnp.float32)).astype(jnp.bfloat16)


def init_params(a: Arch, key) -> Dict[object, Dict[str, jax.Array]]:
    """Initial weights, as float32 arrays holding bfloat16 values, keyed
    by group (layer index, or "head") and leaf name. Drawn op by op, the
    way the program's initialiser draws them."""
    keys = jax.random.split(key, a.layers + 1)
    out: Dict[object, Dict[str, jax.Array]] = {}
    for l in range(a.layers):
        ks = jax.random.split(keys[l], 4)
        kq = jax.random.split(ks[0], 4)
        qd, kd = a.heads * a.head_dim, a.kv_heads * a.head_dim
        p = {"attn/wq": _trunc(kq[0], (a.d, qd), a.d),
             "attn/wk": _trunc(kq[1], (a.d, kd), a.d),
             "attn/wv": _trunc(kq[2], (a.d, kd), a.d),
             "attn/wo": _trunc(kq[3], (qd, a.d), qd)}
        if a.act == "swiglu":
            k1, k2, k3 = jax.random.split(ks[3], 3)
            p["mlp/w_gate"] = _trunc(k1, (a.d, a.d_ff), a.d)
            p["mlp/w_up"] = _trunc(k2, (a.d, a.d_ff), a.d)
            p["mlp/w_down"] = _trunc(k3, (a.d_ff, a.d), a.d_ff)
        else:
            k1, k2 = jax.random.split(ks[3], 2)
            p["mlp/w_in"] = _trunc(k1, (a.d, a.d_ff), a.d)
            p["mlp/w_out"] = _trunc(k2, (a.d_ff, a.d), a.d_ff)
        p["norm1"] = jnp.zeros((a.d,), jnp.float32)
        p["norm2"] = jnp.zeros((a.d,), jnp.float32)
        out[l] = {n: v.astype(jnp.float32) for n, v in p.items()}
    ek = jax.random.split(keys[a.layers], 2)
    out["head"] = {
        "embed": _trunc(ek[0], (a.vocab, a.d), a.d).astype(jnp.float32),
        "unembed": _trunc(ek[1], (a.vocab, a.d), a.d).T.astype(jnp.float32),
        "final_norm": jnp.zeros((a.d,), jnp.float32)}
    return out


# ---------------------------------------------------------------- model
def _round(x, mode):
    """Matrix-product operand; in the control, rounded to float8 on the
    way forward. The backward pass sees the rounding as the identity, as
    a scaled low-precision trainer would, rather than rounding its small
    cotangents to nothing."""
    if mode == "control":
        return x + jax.lax.stop_gradient(
            x.astype(jnp.float8_e4m3fn).astype(jnp.float32) - x)
    return x


def _mm(x, w, mode):
    return jnp.matmul(_round(x, mode), _round(w, mode), precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rope(x, theta):
    """x: (S, H, hd), split-halves convention."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(a: Arch, mode: str, p, x):
    """One decoder layer on x: (B, S, d) float32."""
    def one(xb):
        s = xb.shape[0]
        h = _rms(xb, p["norm1"], a.eps)
        q = _mm(h, p["attn/wq"], mode).reshape(s, a.heads, a.head_dim)
        k = _mm(h, p["attn/wk"], mode).reshape(s, a.kv_heads, a.head_dim)
        v = _mm(h, p["attn/wv"], mode).reshape(s, a.kv_heads, a.head_dim)
        q, k = _rope(q, a.theta), _rope(k, a.theta)
        g = a.heads // a.kv_heads
        k = jnp.repeat(k, g, axis=1)          # q head h reads kv head h // g
        v = jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", _round(q, mode), _round(k, mode),
                        precision=HIGHEST) / math.sqrt(a.head_dim)
        causal = jnp.tril(jnp.ones((s, s), bool))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", _round(pr, mode), _round(v, mode),
                       precision=HIGHEST).reshape(s, a.heads * a.head_dim)
        xb = xb + _mm(o, p["attn/wo"], mode)
        h = _rms(xb, p["norm2"], a.eps)
        if a.act == "swiglu":
            y = jax.nn.silu(_mm(h, p["mlp/w_gate"], mode)) \
                * _mm(h, p["mlp/w_up"], mode)
            return xb + _mm(y, p["mlp/w_down"], mode)
        y = jax.nn.gelu(_mm(h, p["mlp/w_in"], mode), approximate=True)
        return xb + _mm(y, p["mlp/w_out"], mode)
    return jax.vmap(one)(x)


def head_loss(a: Arch, mode: str, unembed, final_norm, x, labels, weights,
              denom):
    logits = _mm(_rms(x, final_norm, a.eps), unembed, mode)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - ll) * weights) / denom


def _adam(p, m, v, g, t, lr, mode):
    if mode == "control":
        g = g.astype(jnp.bfloat16).astype(jnp.float32)
    m = B1 * m + (1 - B1) * g
    v = B2 * v + (1 - B2) * g * g
    up = (m / (1 - B1 ** t)) / (jnp.sqrt(v / (1 - B2 ** t)) + EPS)
    p = p - lr * up
    if mode == "control":
        p, m, v = (z.astype(jnp.bfloat16).astype(jnp.float32)
                   for z in (p, m, v))
    return p, m, v


def labels_weights(tok: np.ndarray):
    """Next-token labels, last position of each row masked."""
    lab = np.concatenate([tok[:, 1:], np.zeros((tok.shape[0], 1),
                                               tok.dtype)], 1)
    w = np.ones(tok.shape, np.float32)
    w[:, -1] = 0.0
    return lab, w


def _sqnorm(x):
    return jnp.sum(jnp.square(x))


class Reference:
    """Trains the model from ``key`` with plain Adam, micro-batch by
    micro-batch, and records what the comparison reads: each step's loss,
    each leaf's first-gradient norm, and each leaf's change since init.

    ``half_batch=True`` plants a fault for the harness's own checks: each
    step trains on the first half of its rows only, the mean taken over
    those."""

    def __init__(self, a: Arch, key, lr: float, micro_batches: int,
                 mode: str = "f32", half_batch: bool = False):
        assert mode in MODES, mode
        self.a, self.key, self.lr, self.M = a, key, lr, micro_batches
        self.mode, self.half_batch = mode, half_batch
        self.p = init_params(a, key)
        self.mv = {g: {n: (np.zeros(x.shape, np.float32),
                           np.zeros(x.shape, np.float32))
                       for n, x in leaves.items()}
                   for g, leaves in self.p.items()}
        self.t = 0
        self.losses: List[float] = []
        self.grad_norms: Dict[str, float] = {}
        self._fwd = jax.jit(lambda p, x: block(a, mode, p, x))
        self._bwd = jax.jit(lambda p, x, dy: jax.vjp(
            lambda pp, xx: block(a, mode, pp, xx), p, x)[1](dy))
        self._head = jax.jit(lambda u, n, x, lab, w, den: jax.value_and_grad(
            lambda uu, nn, xx: head_loss(a, mode, uu, nn, xx, lab, w, den),
            argnums=(0, 1, 2))(u, n, x))
        self._embed_bwd = jax.jit(lambda tok, dy: jnp.zeros(
            (a.vocab, a.d), jnp.float32).at[tok.reshape(-1)].add(
                dy.reshape(-1, a.d)))
        self._adam = jax.jit(lambda p, m, v, g, t, lr: _adam(
            p, m, v, g, t, lr, mode))
        self._sq = jax.jit(_sqnorm)
        self._sqdiff = jax.jit(lambda x, y: _sqnorm(x - y))

    # ------------------------------------------------------------------
    def _update(self, group, grads: Dict[str, jax.Array]):
        """Adam on one group, its moments brought over leaf by leaf."""
        t = jnp.asarray(self.t, jnp.float32)
        lr = jnp.asarray(self.lr, jnp.float32)
        for n, g in grads.items():
            if self.t == 1:
                self.grad_norms[leaf_name(group, n)] = float(
                    np.sqrt(float(self._sq(g))))
            m, v = self.mv[group][n]
            p2, m2, v2 = self._adam(self.p[group][n], jnp.asarray(m),
                                    jnp.asarray(v), g, t, lr)
            self.p[group][n] = p2
            self.mv[group][n] = (np.asarray(m2), np.asarray(v2))

    def step(self, tokens: np.ndarray) -> float:
        a = self.a
        self.t += 1
        if self.half_batch:
            tokens = tokens[: tokens.shape[0] // 2]
        mbs = np.array_split(tokens, min(self.M, tokens.shape[0]))
        denom = jnp.asarray(float(tokens.size - tokens.shape[0]),
                            jnp.float32)
        hp = self.p["head"]
        xs = [[hp["embed"][jnp.asarray(t)] for t in mbs]]
        for l in range(a.layers):
            xs.append([self._fwd(self.p[l], x) for x in xs[-1]])
        loss = 0.0
        g_un = g_nm = None
        dys = []
        for t, x in zip(mbs, xs[-1]):
            lab, w = labels_weights(t)
            lm, (du, dn, dx) = self._head(hp["unembed"], hp["final_norm"],
                                          x, jnp.asarray(lab),
                                          jnp.asarray(w), denom)
            loss += float(lm)
            g_un = du if g_un is None else g_un + du
            g_nm = dn if g_nm is None else g_nm + dn
            dys.append(dx)
        for l in reversed(range(a.layers)):
            g = None
            for i, x in enumerate(xs[l]):
                dp, dys[i] = self._bwd(self.p[l], x, dys[i])
                g = dp if g is None else jax.tree.map(jnp.add, g, dp)
            self._update(l, g)
        del xs
        g_em = self._embed_bwd(jnp.asarray(np.concatenate(mbs)),
                               jnp.concatenate(dys))
        self._update("head", {"unembed": g_un, "final_norm": g_nm,
                              "embed": g_em})
        self.losses.append(loss)
        return loss

    def change_norms(self) -> Dict[str, float]:
        """Each leaf's norm of (now - init); init is drawn again from the
        seed rather than kept."""
        p0 = init_params(self.a, self.key)
        return {leaf_name(g, n): float(np.sqrt(float(self._sqdiff(
                    self.p[g][n], p0[g][n]))))
                for g in self.p for n in self.p[g]}


def leaf_name(group, name: str) -> str:
    return f"layer{group}/{name}" if group != "head" else name


def run(a: Arch, key, lr: float, micro_batches: int,
        batches: Sequence[np.ndarray], mode: str = "f32",
        half_batch: bool = False) -> dict:
    """Follow ``batches`` and return the readings the comparison needs."""
    ref = Reference(a, key, lr, micro_batches, mode, half_batch)
    for tok in batches:
        ref.step(tok)
    out = {"losses": list(ref.losses), "grad_norms": dict(ref.grad_norms),
           "change_norms": ref.change_norms()}
    del ref
    return out
