"""The plain reference: the dense decoder's loss, gradient and AdamW steps
in float32 ``jax.numpy`` at ``Precision.HIGHEST``, one sequence at a time,
with no kernel, no remat policy of the program, no offload.  As the
configuration states, the forward pass takes the weights in their
parameter type (bfloat16, rounded from the float32 master copy that
AdamW updates); everything else is float32.  It imports
nothing of the system under test and draws its own weights from the seed
(``benchlib.model``).

Variants put in the program's place for the control and the planted
faults (they are never part of a benchmark run):

- ``quant="fp8"``: computed in float8 e4m3, the precision below the
  configuration's bfloat16: every weight and activation, and in the
  backward pass every gradient of one, is rounded to e4m3 with a
  per-tensor scale where the reference would hold it, while matmuls
  accumulate and the master weights and optimizer state stay in float32;
- ``half=True``: the loss and gradient are the mean over the first half
  of the rows only;
- ``cut=n``: the exchange between ``n`` tensor-parallel chips left out:
  the attention output and MLP down projections sum over the first
  1/n of the heads and of the hidden units only, as chip 0 would without
  its all-reduce."""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.model import Dense, base_key, draw_leaf_rounded, layout

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
LOSS_CHUNK = 512
FP8_MAX = 448.0


@dataclass(frozen=True)
class Adam:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float


@dataclass
class Readings:
    """What a run of two training steps gives: the loss of each step, the
    norm of each leaf's first gradient as the optimizer takes it (after
    clipping), and the norm of each leaf's change after the two steps."""
    losses: List[float]
    grad: Dict[str, float]
    change: Dict[str, float]


def _fp8(x):
    """x rounded to float8 e4m3 with a per-tensor scale (its largest
    magnitude maps to e4m3's largest finite value).  The scaled tensor is
    clipped to that value first: e4m3fn has no infinity, and a value the
    division left a rounding above it would convert to NaN."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 2.0 ** -100, amax / FP8_MAX, 1.0)
    q = jnp.clip(x / s, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)
    return q.astype(jnp.float32) * s


@jax.custom_vjp
def _round_fp8(x):
    return _fp8(x)


_round_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def _c(x, quant):
    """A tensor as the computation holds it: float32 in the reference;
    in the fp8 control rounded to e4m3 wherever the configuration's
    bfloat16 program holds a tensor (weights, activations and, in the
    backward pass, their gradients), with float32 accumulation."""
    return _round_fp8(x) if quant == "fp8" else x


def _dot(a, b):
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


def _lin(x, w, quant):
    """x (T, K) @ w (K, N)."""
    return _c(_dot(_c(x, quant), _c(w, quant)), quant)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, n, hd): rotary embedding, rotate-half convention."""
    s, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


def _attend(q, k, v, scale, quant=None):
    """Causal attention of q (S, H, hd) over k, v (S, KV, hd), one block
    of queries at a time (recomputed in the backward pass)."""
    s, h, hd = q.shape
    kv = k.shape[1]
    blk = min(Q_BLOCK, s)
    qb = q.reshape(s // blk, blk, kv, h // kv, hd)

    @jax.checkpoint
    def one(args):
        i, qq = args
        sc = jnp.einsum("qkgd,skd->kgqs", qq, k, precision=HI) * scale
        pos = i * blk + jnp.arange(blk)
        sc = jnp.where(pos[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        p = _c(jax.nn.softmax(sc, axis=-1), quant)
        return _c(jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI), quant)

    out = jax.lax.map(one, (jnp.arange(s // blk), qb))
    return out.reshape(s, h, hd)


def _layer(x, lw, m: Dense, quant, cut):
    s, d = x.shape
    h, kv, hd = m.heads, m.kv_heads, m.head_dim
    a = _c(_rms(x, lw["ln1/scale"], m.eps), quant)
    q = _lin(a, lw["attn/wq"].reshape(d, h * hd), quant).reshape(s, h, hd)
    k = _lin(a, lw["attn/wk"].reshape(d, kv * hd), quant).reshape(s, kv, hd)
    v = _lin(a, lw["attn/wv"].reshape(d, kv * hd), quant).reshape(s, kv, hd)
    if m.qkv_bias:
        q, k, v = q + lw["attn/bq"], k + lw["attn/bk"], v + lw["attn/bv"]
    ctx = _attend(_c(_rope(q, m.rope_theta), quant),
                  _c(_rope(k, m.rope_theta), quant), v, m.attn_scale, quant)
    hk, fk = (h, m.d_ff) if cut is None else (h // cut, m.d_ff // cut)
    o = _lin(ctx[:, :hk].reshape(s, hk * hd),
             lw["attn/wo"][:hk].reshape(hk * hd, d), quant)
    x = _c(x + m.resid_mult * o, quant)
    a = _c(_rms(x, lw["ln2/scale"], m.eps), quant)
    u = _c(jax.nn.silu(_lin(a, lw["mlp/wg"], quant))
           * _lin(a, lw["mlp/wu"], quant), quant)
    return _c(x + m.resid_mult * _lin(u[:, :fk], lw["mlp/wd"][:fk], quant),
              quant)


def row_loss(w, tokens, labels, m: Dense, quant=None, cut=None):
    """Mean next-token cross-entropy of one sequence."""
    x = _c(w["embed/w"][tokens] * m.emb_mult, quant)
    layers = {k[len("layers/"):]: v for k, v in w.items()
              if k.startswith("layers/")}
    layer = jax.checkpoint(functools.partial(_layer, m=m, quant=quant,
                                             cut=cut))
    x, _ = jax.lax.scan(lambda c, lw: (layer(c, lw), None), x, layers)
    x = _c(_rms(x, w["final_norm/scale"], m.eps), quant)
    w_out = w["embed/w"].T if m.tied else w["lm_head/w"]
    s = x.shape[0]
    c = min(LOSS_CHUNK, s)

    @jax.checkpoint
    def chunk(tot, xs):
        hh, ll = xs
        logits = _lin(hh, w_out, quant) / m.logits_div
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[:, None], axis=-1)[:, 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32),
                          (x.reshape(s // c, c, -1), labels.reshape(s // c, c)))
    return tot / s


def accumulate(acc, w, tokens, labels, m: Dense, quant=None, cut=None):
    """(acc + gradient of one row's loss, that loss): one row at a time,
    so that only one row's gradient is live beside the running sum."""
    loss, g = jax.value_and_grad(row_loss)(w, tokens, labels, m, quant, cut)
    return jax.tree.map(jnp.add, acc, g), loss


def weight_shardings(m: Dense, mesh) -> Optional[Dict[str, object]]:
    """How the reference spreads its float32 weights over the cell's
    chips (``mesh`` with one axis, or None on one chip): each matrix over
    its heads, hidden units or vocabulary; norm scales replicated."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    ax = mesh.axis_names[0]
    n = mesh.shape[ax]
    spec = {"embed/w": P(ax, None), "lm_head/w": P(None, ax),
            "layers/attn/wq": P(None, None, ax, None),
            "layers/attn/wk": P(None, None, ax, None),
            "layers/attn/wv": P(None, None, ax, None),
            "layers/attn/wo": P(None, ax, None, None),
            "layers/attn/bq": P(None, ax, None),
            "layers/attn/bk": P(None, ax, None),
            "layers/attn/bv": P(None, ax, None),
            "layers/mlp/wg": P(None, None, ax), "layers/mlp/wu": P(None, None, ax),
            "layers/mlp/wd": P(None, ax, None)}
    out = {}
    for name, (shape, _) in layout(m).items():
        p = spec.get(name, P())
        dims = [i for i, a in enumerate(p) if a is not None]
        if any(shape[i] % n for i in dims):
            p = P()
        out[name] = NamedSharding(mesh, p)
    return out


class Reference:
    """Two AdamW steps of the plain reference on one batch pair."""

    def __init__(self, m: Dense, adam: Adam, mesh=None, quant=None,
                 cut=None, half=False):
        self.m, self.adam, self.half = m, adam, half
        sh = weight_shardings(m, mesh)
        on = {} if sh is None else {"out_shardings": sh}
        self._acc = jax.jit(
            functools.partial(accumulate, m=m, quant=quant, cut=cut),
            donate_argnums=(0,),
            **({} if sh is None else {"in_shardings": (sh, sh, None, None),
                                       "out_shardings": (sh, None)}))
        self._zeros = jax.jit(lambda: {n: jnp.zeros(s, jnp.float32)
                                       for n, (s, _) in layout(m).items()},
                              **on)
        self._weights = jax.jit(
            lambda key: {n: draw_leaf_rounded(m, key, n) for n in layout(m)},
            **on)
        self._as_params = jax.jit(lambda w: jax.tree.map(
            lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                               mantissa_bits=7), w), **on) \
            if m.param_dtype == "bfloat16" else (lambda w: w)
        a = adam

        @jax.jit
        def sumsq(g, rows):
            return sum(jnp.sum(jnp.square(x / rows)) for x in g.values())

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def first(w, g, rows, clip):
            """Step 1 from zero moments; the clipped mean gradient and the
            square of each leaf's norm."""
            g = jax.tree.map(lambda x: x / rows * clip, g)
            w = jax.tree.map(lambda wn, gn: wn - a.lr * (
                gn / (jnp.sqrt(gn * gn) + a.eps) + a.weight_decay * wn), w, g)
            return w, g, jax.tree.map(lambda x: jnp.sum(x * x), g)

        def second(name):
            @jax.jit
            def step(w, g1, g2, rows, clip, key):
                """Step 2 of leaf ``name`` from step 1's clipped gradient
                ``g1``; the square of the leaf's change since drawn."""
                g2 = g2 / rows * clip
                mu = a.b1 * (1 - a.b1) * g1 + (1 - a.b1) * g2
                nu = a.b2 * (1 - a.b2) * g1 * g1 + (1 - a.b2) * g2 * g2
                upd = (mu / (1 - a.b1 ** 2)) / (
                    jnp.sqrt(nu / (1 - a.b2 ** 2)) + a.eps)
                w = w - a.lr * (upd + a.weight_decay * w)
                w0 = draw_leaf_rounded(m, key, name)
                if sh is not None:
                    w0 = jax.lax.with_sharding_constraint(w0, sh[name])
                return jnp.sum(jnp.square(w - w0))
            return step

        self._sumsq, self._first = sumsq, first
        self._second = {n: second(n) for n in layout(m)}

    def _grad(self, w, batch):
        """(mean loss, gradient summed over the batch's rows, rows)."""
        t, l = batch["tokens"], batch["labels"]
        if self.half:
            t, l = t[: len(t) // 2], l[: len(l) // 2]
        acc, losses = self._zeros(), []
        for i in range(len(t)):
            acc, loss = self._acc(acc, w, jnp.asarray(t[i]), jnp.asarray(l[i]))
            losses.append(loss)
        return sum(float(x) for x in losses) / len(t), acc, len(t)

    def _clip(self, g, rows) -> float:
        norm = math.sqrt(float(self._sumsq(g, rows)))
        return min(1.0, self.adam.grad_clip / (norm + 1e-12))

    def run(self, seed: int, batches) -> Readings:
        """Readings of steps 1 and 2 on ``batches[0]`` and ``batches[1]``
        from the weights ``seed`` draws.  The forward pass takes the
        weights rounded to their parameter type; the drawn weights are
        exact in it.  Step 1's clipped gradient and step 2's starting
        master weights wait on the host while step 2's gradient is
        computed, so that one gradient and one copy of the weights are on
        the chips at a time."""
        key = base_key(seed)
        w = self._weights(key)
        loss1, g, rows = self._grad(w, batches[0])
        clip = self._clip(g, rows)
        w, g, ss = self._first(w, g, rows, clip)
        grad_norms = {n: math.sqrt(float(v)) for n, v in ss.items()}
        g1 = {n: np.asarray(v) for n, v in g.items()}
        del g
        params = self._as_params(w)
        shardings = {n: v.sharding for n, v in w.items()}
        w1 = {n: np.asarray(v) for n, v in w.items()}
        del w
        loss2, g, rows = self._grad(params, batches[1])
        del params
        clip = self._clip(g, rows)
        change = {}
        for n in list(g):
            put = functools.partial(jax.device_put, device=shardings[n])
            change[n] = math.sqrt(float(self._second[n](
                put(w1.pop(n)), put(g1.pop(n)), g.pop(n), rows, clip, key)))
        return Readings([loss1, loss2], grad_norms, change)
