"""Jitted kernel wrappers with implementation dispatch.

``attention(q, k, v, impl=...)``:
  - "naive":   full S^2 softmax (ref.py) — the no-FlashAttention baseline.
  - "blocked": flash-style online softmax over KV blocks in pure jnp with a
               custom-VJP blocked backward (O(block) intermediates) — the
               lowering-compatible stand-in for the Pallas kernel (used by
               the dry-run on the CPU host platform).
  - "pallas":  the Pallas TPU kernels (interpret=True off-TPU): the forward,
               and a backward of lse, dK/dV and dQ kernels that keep every
               score tile in VMEM.  Shapes those kernels do not tile
               (Sq != Sk, head_dim or a block off the 128-lane tiling) take
               the blocked backward; ``backward_paths`` counts, at trace
               time, which backward each ``_flash`` took.
"""
from __future__ import annotations

import collections
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd, flash_lse)
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

DEFAULT_BLOCK = 512
LANES = 128

# trace-time count of the backward each ``_flash`` took: "pallas" or "jnp"
backward_paths: collections.Counter = collections.Counter()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# blocked flash attention (jnp, custom VJP)
# ---------------------------------------------------------------------------


def _blocked_fwd(q, k, v, causal: bool, scale: float, block: int):
    """q/k/v (BH,S,D) -> out, lse.  Scan over KV blocks, online softmax."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nk = sk // block
    kb = k.reshape(bh, nk, block, d).transpose(1, 0, 2, 3)
    vb = v.reshape(bh, nk, block, d).transpose(1, 0, 2, 3)
    qpos = jnp.arange(sq)

    def body(carry, inp):
        acc, m, l = carry
        kcur, vcur, ki = inp
        s = jnp.einsum("bqd,bkd->bqk", q, kcur,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = ki * block + jnp.arange(block)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, ref.NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqk,bkd->bqd", p.astype(v.dtype), vcur).astype(jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((bh, sq, d), jnp.float32)
    m0 = jnp.full((bh, sq), ref.NEG_INF, jnp.float32)
    l0 = jnp.zeros((bh, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nk)))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).astype(q.dtype)
    lse = m + jnp.log(l)
    return out, lse


def _blocked_bwd(q, k, v, out, lse, dout, causal: bool, scale: float,
                 block: int):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nk = sk // block
    kb = k.reshape(bh, nk, block, d).transpose(1, 0, 2, 3)
    vb = v.reshape(bh, nk, block, d).transpose(1, 0, 2, 3)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)
    qpos = jnp.arange(sq)

    def body(dq, inp):
        kcur, vcur, ki = inp
        s = jnp.einsum("bqd,bkd->bqk", q, kcur,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = ki * block + jnp.arange(block)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, ref.NEG_INF)
        p = jnp.exp(s - lse[..., None])                      # (BH,Sq,blk)
        dv = jnp.einsum("bqk,bqd->bkd", p.astype(dout.dtype), dout)
        dp = jnp.einsum("bqd,bkd->bqk", dout, vcur,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds.astype(q.dtype), kcur
                             ).astype(jnp.float32)
        dk = jnp.einsum("bqk,bqd->bkd", ds.astype(q.dtype), q)
        return dq, (dk, dv)

    dq0 = jnp.zeros((bh, sq, d), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nk)))
    dk = dkb.transpose(1, 0, 2, 3).reshape(bh, sk, d)
    dv = dvb.transpose(1, 0, 2, 3).reshape(bh, sk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal: bool, scale: float, q_block: int, kv_block: int,
           use_pallas: bool):
    if use_pallas:
        return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                   q_block=min(q_block, q.shape[1]),
                                   kv_block=min(kv_block, k.shape[1]),
                                   interpret=not _on_tpu())
    out, _ = _blocked_fwd(q, k, v, causal, scale,
                          min(kv_block, k.shape[1]))
    return out


def _pallas_bwd(q, k, q_block: int, kv_block: int) -> bool:
    """Whether the Pallas backward kernels tile these shapes: self-attention
    (Sq == Sk), head_dim and both blocks on the 128-lane tiling, blocks
    dividing the sequence."""
    s, d = q.shape[1:]
    return (k.shape[1] == s and d % LANES == 0 and q_block % LANES == 0
            and kv_block % LANES == 0 and s % q_block == 0
            and s % kv_block == 0)


def _flash_fwd_rule(q, k, v, causal, scale, q_block, kv_block, use_pallas):
    q_block, kv_block = min(q_block, q.shape[1]), min(kv_block, k.shape[1])
    if not use_pallas:
        out, lse = _blocked_fwd(q, k, v, causal, scale, kv_block)
        return out, (q, k, v, out, lse)
    out = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                              q_block=q_block, kv_block=kv_block,
                              interpret=not _on_tpu())
    # flash-style recompute: the backward needs each row's log-sum-exp
    if _pallas_bwd(q, k, q_block, kv_block):
        lse = flash_lse(q, k, causal=causal, scale=scale, q_block=q_block,
                        kv_block=kv_block, interpret=not _on_tpu())
    else:
        _, lse = _blocked_fwd(q, k, v, causal, scale, kv_block)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, q_block, kv_block, use_pallas, res, dout):
    q, k, v, out, lse = res
    q_block, kv_block = min(q_block, q.shape[1]), min(kv_block, k.shape[1])
    if use_pallas and _pallas_bwd(q, k, q_block, kv_block):
        backward_paths["pallas"] += 1
        return flash_attention_bwd(q, k, v, out, lse, dout, causal=causal,
                                   scale=scale, q_block=q_block,
                                   kv_block=kv_block,
                                   interpret=not _on_tpu())
    backward_paths["jnp"] += 1
    return _blocked_bwd(q, k, v, out, lse, dout, causal, scale, kv_block)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _fit_block(blk: int, size: int) -> int:
    blk = min(blk, size)
    while size % blk:
        blk //= 2
    return blk


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, impl: str = "blocked",
              block: int = DEFAULT_BLOCK,
              q_block: Optional[int] = None,
              kv_block: Optional[int] = None,
              scale: Optional[float] = None) -> jax.Array:
    """q (B,Sq,H,hd); k/v (B,Sk,KV,hd) with H = KV*G (GQA) -> (B,Sq,H,hd).

    ``q_block``/``kv_block`` set the flash tiles independently (the tuned
    kernel-config dimension); ``block`` is the legacy shared default for
    callers that don't distinguish them.  Blocks that don't divide the
    sequence are halved until they do (legality is best-effort here; the
    tuner only emits divisible configs)."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    # expand KV over the group dim (vjp of broadcast sums dk/dv correctly)
    kx = jnp.broadcast_to(k[:, :, :, None, :], (b, k.shape[1], kv, g, hd))
    vx = jnp.broadcast_to(v[:, :, :, None, :], (b, v.shape[1], kv, g, hd))
    qf = q.reshape(b, sq, kv, g, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(b * h, sq, hd)
    kf = kx.transpose(0, 2, 3, 1, 4).reshape(b * h, k.shape[1], hd)
    vf = vx.transpose(0, 2, 3, 1, 4).reshape(b * h, v.shape[1], hd)
    if impl == "naive":
        of = ref.naive_attention(qf, kf, vf, causal=causal, scale=scale)
    else:
        qblk = _fit_block(q_block if q_block is not None else block, sq)
        kblk = _fit_block(kv_block if kv_block is not None else block,
                          kf.shape[1])
        of = _flash(qf, kf, vf, causal, scale, qblk, kblk, impl == "pallas")
    return of.reshape(b, kv, g, sq, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# rmsnorm / ssd
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, *, impl: str = "pallas",
            eps: float = 1e-6, block: int = 256) -> jax.Array:
    if impl == "pallas":
        return rmsnorm_pallas(x, scale, eps=eps, row_block=block,
                              interpret=not _on_tpu())
    return ref.rmsnorm_ref(x, scale, eps)


def ssd_scan(xh, dt, a, bb, cc, *, chunk: int = 256
             ) -> Tuple[jax.Array, None]:
    """Pallas SSD chunk scan; returns (y, None) — final state is produced by
    the reference path when a serving handoff needs it."""
    y = ssd_scan_pallas(xh, dt, a, bb, cc, chunk=chunk,
                        interpret=not _on_tpu())
    return y, None
