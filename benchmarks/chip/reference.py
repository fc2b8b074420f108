"""Plain float32 reference of the models the offload trainer runs.

It imports nothing of the program under test and takes nothing it made:
the weights are drawn again from the seed, by the same recipe the
program's initialiser follows, and every later value is computed here.
A model family's layers (its sizes, leaves, initial weights and
equations) are in ``models/<model>.py``; this file holds what every
family shares: the operand rounding, norm and rotary helpers the layer
equations are written with, the head, Adam and the training loop.

The head, the same for every family:

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

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.95, 1e-8
HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "control")
#: the device-resident leaves every family shares, in the program's order
HEAD_LEAVES = ("embed", "unembed", "final_norm")


# ---------------------------------------------------------------- init
def _trunc(key, shape, fan_in):
    std = 1.0 / np.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              jnp.float32)).astype(jnp.bfloat16)


def head_params(a, key) -> Dict[str, jax.Array]:
    """The head's initial weights, as the program draws them from the key
    after the layers'."""
    ek = jax.random.split(key, 2)
    return {
        "embed": _trunc(ek[0], (a.vocab, a.d), a.d).astype(jnp.float32),
        "unembed": _trunc(ek[1], (a.vocab, a.d), a.d).T.astype(jnp.float32),
        "final_norm": jnp.zeros((a.d,), jnp.float32)}


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


def head_loss(a, mode: str, unembed, final_norm, x, labels, weights,
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
    """Trains ``model``'s architecture ``a`` from ``key`` with plain Adam,
    micro-batch by micro-batch, and records what the comparison reads:
    each step's loss, each leaf's first-gradient norm, and each leaf's
    change since init.

    ``half_batch=True`` plants a fault for the harness's own checks: each
    step trains on the first half of its rows only, the mean taken over
    those."""

    def __init__(self, model, a, key, lr: float, micro_batches: int,
                 mode: str = "f32", half_batch: bool = False):
        assert mode in MODES, mode
        self.model, self.a, self.key = model, a, key
        self.lr, self.M = lr, micro_batches
        self.mode, self.half_batch = mode, half_batch
        self.p = model.init_params(a, key)
        self.mv = {g: {n: (np.zeros(x.shape, np.float32),
                           np.zeros(x.shape, np.float32))
                       for n, x in leaves.items()}
                   for g, leaves in self.p.items()}
        self.t = 0
        self.losses: List[float] = []
        self.grad_norms: Dict[str, float] = {}
        self._layer_jits: Dict[object, tuple] = {}
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
    def _jits(self, l: int):
        """(forward, backward) of layer ``l``, compiled once per kind:
        the kind alone selects the equations."""
        kind = self.model.layer_kind(self.a, l)
        if kind not in self._layer_jits:
            model, a, mode = self.model, self.a, self.mode
            self._layer_jits[kind] = (
                jax.jit(lambda p, x: model.block(a, mode, p, x, kind)),
                jax.jit(lambda p, x, dy: jax.vjp(
                    lambda pp, xx: model.block(a, mode, pp, xx, kind),
                    p, x)[1](dy)))
        return self._layer_jits[kind]

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
            fwd = self._jits(l)[0]
            xs.append([fwd(self.p[l], x) for x in xs[-1]])
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
            bwd = self._jits(l)[1]
            for i, x in enumerate(xs[l]):
                dp, dys[i] = bwd(self.p[l], x, dys[i])
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
        p0 = self.model.init_params(self.a, self.key)
        return {leaf_name(g, n): float(np.sqrt(float(self._sqdiff(
                    self.p[g][n], p0[g][n]))))
                for g in self.p for n in self.p[g]}


def leaf_name(group, name: str) -> str:
    return f"layer{group}/{name}" if group != "head" else name


def run(model, a, key, lr: float, micro_batches: int,
        batches: Sequence[np.ndarray], mode: str = "f32",
        half_batch: bool = False) -> dict:
    """Follow ``batches`` with ``model``'s architecture ``a`` and return
    the readings the comparison needs."""
    ref = Reference(model, a, key, lr, micro_batches, mode, half_batch)
    for tok in batches:
        ref.step(tok)
    out = {"losses": list(ref.losses), "grad_norms": dict(ref.grad_norms),
           "change_norms": ref.change_norms()}
    del ref
    return out
