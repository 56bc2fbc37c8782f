"""Pallas TPU flash attention: online-softmax forward, and the
FlashAttention-2 backward as three kernels.

Forward grid: (batch*kv_heads*q_per_kv, num_q_blocks, num_kv_blocks) with
the KV block dim innermost (sequential on TPU), carrying (acc, m, l) in
VMEM scratch across KV iterations.  Block shapes are MXU-aligned (q_block
x head_dim and kv_block x head_dim tiles).

The backward (``jax.custom_vjp`` in ops.py) never writes a score tile to
HBM:

- ``flash_lse`` gives each query row's log-sum-exp, which the forward
  kernel does not return (its single-output signature is what the
  benchmark's roofline reader finds it by);
- ``flash_attention_bwd`` runs a dK/dV pass over KV blocks
  (``flash_bwd_dkv``) and a dQ pass over Q blocks (``flash_bwd_dq``).

The lse and dK/dV kernels work on transposed scores ``k . q^T``
(kv_block x q_block), so the per-query values lse and delta are lane
rows, read from and written to ``(BH, 1, S)`` f32 arrays.  Causal blocks
above the diagonal are skipped, and their index maps repeat the last
block fetched so that no copy is issued for them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale: float, causal: bool, q_block: int, kv_block: int,
               kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * q_block
    k_start = ki * kv_block

    run = True
    if causal:
        # skip blocks strictly above the diagonal
        run = k_start <= q_start + q_block - 1

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (q_block, d)
        k = k_ref[0].astype(jnp.float32)            # (kv_block, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (q_block, kv_block), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (q_block, kv_block), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                         # (q_block, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, q_block: int = 256,
                        kv_block: int = 256, scale: float | None = None,
                        interpret: bool = True) -> jax.Array:
    """q (BH, Sq, D), k/v (BH, Sk, D) -> (BH, Sq, D).

    BH = batch * heads (GQA expansion done by the wrapper in ops.py).
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    assert sq % q_block == 0 and sk % kv_block == 0, (sq, sk, q_block, kv_block)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    grid = (bh, sq // q_block, sk // kv_block)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, q_block=q_block,
        kv_block=kv_block, kv_len=sk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kv_block, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, kv_block, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            # VMEM carries across the sequential kv grid dim
            pltpu.VMEM((q_block, d), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward: lse, dK/dV and dQ kernels
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _visit(compute, *, causal: bool, q_start, k_start, q_block: int,
           kv_block: int):
    """Run ``compute(masked)`` on one (q block, kv block) pair: not at all
    above the causal diagonal, with the mask only where the pair crosses
    it."""
    if not causal:
        compute(False)
        return
    below = k_start + kv_block - 1 <= q_start     # no key after any query
    crosses = jnp.logical_and(k_start <= q_start + q_block - 1,
                              jnp.logical_not(below))
    pl.when(below)(lambda: compute(False))
    pl.when(crosses)(lambda: compute(True))


def _scores(a, b, a_start, b_start, *, scale: float, masked: bool,
            a_is_q: bool):
    """Scores ``a . b^T * scale`` in f32, where one of a, b is a q block
    and the other a k block; keys after their query are set to NEG_INF."""
    s = jax.lax.dot_general(a, b, _NT,
                            preferred_element_type=jnp.float32) * scale
    if masked:
        apos = a_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        bpos = b_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(apos >= bpos if a_is_q else bpos >= apos, s, NEG_INF)
    return s


def _lse_kernel(q_ref, k_ref, lse_ref, m_ref, l_ref, *, scale: float,
                causal: bool, q_block: int, kv_block: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_start = qi * q_block
    k_start = ki * kv_block

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute(masked):
        st = _scores(k_ref[0], q_ref[0], k_start, q_start, scale=scale,
                     masked=masked, a_is_q=False)   # (kv_block, q_block)
        m_prev = m_ref[...]                         # (1, q_block)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        l_ref[...] = jnp.exp(m_prev - m_new) * l_ref[...] + jnp.sum(
            jnp.exp(st - m_new), axis=0, keepdims=True)
        m_ref[...] = m_new

    _visit(compute, causal=causal, q_start=q_start, k_start=k_start,
           q_block=q_block, kv_block=kv_block)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale: float, causal: bool,
                q_block: int, kv_block: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    q_start = qi * q_block
    k_start = ki * kv_block

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute(masked):
        q = q_ref[0]
        do = do_ref[0]
        st = _scores(k_ref[0], q, k_start, q_start, scale=scale,
                     masked=masked, a_is_q=False)
        pt = jnp.exp(st - lse_ref[0])               # (kv_block, q_block)
        dv_acc[...] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0]) * scale
        dk_acc[...] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    _visit(compute, causal=causal, q_start=q_start, k_start=k_start,
           q_block=q_block, kv_block=kv_block)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale: float, causal: bool, q_block: int,
               kv_block: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_start = qi * q_block
    k_start = ki * kv_block

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        s = _scores(q, k, q_start, k_start, scale=scale, masked=masked,
                    a_is_q=True)                    # (q_block, kv_block)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0, 0], -1))
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(delta_ref[0, 0], -1)) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), k, _NN, preferred_element_type=jnp.float32)

    _visit(compute, causal=causal, q_start=q_start, k_start=k_start,
           q_block=q_block, kv_block=kv_block)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def flash_lse(q: jax.Array, k: jax.Array, *, causal: bool = True,
              q_block: int = 256, kv_block: int = 256,
              scale: float | None = None,
              interpret: bool = True) -> jax.Array:
    """q (BH, S, D), k (BH, S, D) -> log-sum-exp of each query's scaled
    scores, f32 (BH, S).  Scores are bf16 dots accumulated in f32, as in
    the forward."""
    bh, s, d = q.shape
    assert k.shape == q.shape and s % q_block == 0 and s % kv_block == 0, \
        (q.shape, k.shape, q_block, kv_block)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def k_index(b, qi, ki):
        if causal:      # above the diagonal: keep the last block needed
            ki = jnp.minimum(ki, ((qi + 1) * q_block - 1) // kv_block)
        return (b, ki, 0)

    lse = pl.pallas_call(
        functools.partial(_lse_kernel, scale=scale, causal=causal,
                          q_block=q_block, kv_block=kv_block),
        grid=(bh, s // q_block, s // kv_block),
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, kv_block, d), k_index),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block), lambda b, qi, ki: (b, 0, qi)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, q_block), jnp.float32),
                        pltpu.VMEM((1, q_block), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_lse",
    )(q, k)
    return lse.reshape(bh, s)


def flash_attention_bwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        out: jax.Array, lse: jax.Array, dout: jax.Array, *,
                        causal: bool = True, q_block: int = 256,
                        kv_block: int = 256, scale: float | None = None,
                        interpret: bool = True
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gradients (dq, dk, dv) of attention, from the forward's inputs, its
    output and lse (BH, S).  All of q/k/v/out/dout are (BH, S, D).

    Precisions are those of the blocked-jnp backward: scores from the
    inputs' dtype with f32 accumulation, p = exp(s - lse) in f32 and cast
    to the inputs' dtype for dV, dP in f32, dS = p (dP - delta) scale in
    f32 and cast for the dQ and dK products, every gradient accumulated
    in f32."""
    bh, s, d = q.shape
    assert k.shape == v.shape == q.shape and s % q_block == 0 \
        and s % kv_block == 0, (q.shape, k.shape, q_block, kv_block)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    -1).reshape(bh, 1, s)
    lse = lse.reshape(bh, 1, s)
    nq, nk = s // q_block, s // kv_block
    kernel_kw = dict(scale=scale, causal=causal, q_block=q_block,
                     kv_block=kv_block)

    # dK/dV: grid (BH, kv blocks, q blocks), q innermost
    def q_index(b, ki, qi):
        if causal:      # below the diagonal: wait on the first block needed
            qi = jnp.maximum(qi, (ki * kv_block) // q_block)
        return (b, qi, 0)

    def q_row(b, ki, qi):
        return (b, 0, q_index(b, ki, qi)[1])

    q_spec = pl.BlockSpec((1, q_block, d), q_index)
    kv_spec = pl.BlockSpec((1, kv_block, d), lambda b, ki, qi: (b, ki, 0))
    row_spec = pl.BlockSpec((1, 1, q_block), q_row)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kernel_kw),
        grid=(bh, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((kv_block, d), jnp.float32),
                        pltpu.VMEM((kv_block, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, dout, lse, delta)

    # dQ: grid (BH, q blocks, kv blocks), kv innermost
    def k_index(b, qi, ki):
        if causal:      # above the diagonal: keep the last block needed
            ki = jnp.minimum(ki, ((qi + 1) * q_block - 1) // kv_block)
        return (b, ki, 0)

    q_spec = pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0))
    kv_spec = pl.BlockSpec((1, kv_block, d), k_index)
    row_spec = pl.BlockSpec((1, 1, q_block), lambda b, qi, ki: (b, 0, qi))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kernel_kw),
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((q_block, d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv
