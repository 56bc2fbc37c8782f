"""Pallas TPU Mamba2 SSD chunk scan.

Grid: (batch, heads, num_chunks) with the chunk dim innermost (sequential on
TPU); the running state h (P x N, f32) lives in VMEM scratch and carries
across chunk iterations.  Per chunk the kernel computes the intra-chunk
(diagonal-block) contribution, the inter-chunk contribution from the carried
state, and the state update — one fused pass instead of the multi-einsum
reference (ref.py / models/ssm.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, hstate_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        hstate_ref[...] = jnp.zeros_like(hstate_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)     # (Q, P)
    dt_row = dt_ref[0, 0, 0]                   # (1, Q) f32
    da_row = da_ref[0, 0, 0]                   # (1, Q) f32: dt * a
    bb = b_ref[0, 0, 0].astype(jnp.float32)    # (Q, N)
    cc = c_ref[0, 0, 0].astype(jnp.float32)    # (Q, N)

    # dt/da arrive as lane rows (the TPU block tiling refuses the former
    # (1, 1, 1, Q) block); their column forms and prefix sums are masked
    # 2-D reductions, so every value stays in a 2-D (sublane, lane) layout
    q = x.shape[0]
    t_ix = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s_ix = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = t_ix >= s_ix

    def col(row):                              # (1, Q) -> (Q, 1)
        return jnp.sum(jnp.where(t_ix == s_ix, row, 0.0), axis=1,
                       keepdims=True)

    dt_col, da_col = col(dt_row), col(da_row)
    cum_col = jnp.sum(jnp.where(tri, da_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(t_ix <= s_ix, da_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(da_row, axis=1, keepdims=True)          # (1, 1)
    # intra-chunk
    rel = cum_col - cum_row                    # (Qt, Qs)
    # mask BEFORE exp: above the diagonal rel > 0 can overflow (and the
    # where-after-exp pattern NaNs the backward via inf*0)
    lmat = jnp.exp(jnp.where(tri, rel, -jnp.inf))
    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = scores * lmat * dt_row
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # inter-chunk from carried state: y += (C * exp(cum)) @ h
    h = hstate_ref[...]                        # (N, P)
    cdec = cc * jnp.exp(cum_col)
    y = y + jax.lax.dot_general(cdec, h, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # state update: h' = exp(sum da) * h + sum_s exp(cum_Q - cum_s) dt_s B_s x_s^T
    w = jnp.exp(total - cum_col) * dt_col      # (Q, 1)
    new_state = jax.lax.dot_general(bb * w, x,
                                    (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    hstate_ref[...] = h * jnp.exp(total) + new_state
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_scan_pallas(xh: jax.Array, dt: jax.Array, a: jax.Array,
                    bb: jax.Array, cc: jax.Array, *, chunk: int = 256,
                    interpret: bool = True) -> jax.Array:
    """xh (B,S,H,P); dt (B,S,H) f32; a (H,) f32; bb/cc (B,S,H,N).

    Returns y (B,S,H,P).  (Final state is recomputed by the reference path
    when needed for serving handoff.)
    """
    bsz, s, h, p = xh.shape
    n = bb.shape[-1]
    q = min(chunk, s)
    assert s % q == 0
    nc = s // q
    # layout: (B, H, C, Q, .); dt and dt*a as (B, H, C, 1, Q) lane rows
    xt = xh.transpose(0, 2, 1, 3).reshape(bsz, h, nc, q, p)
    dtt = dt.transpose(0, 2, 1).reshape(bsz, h, nc, 1, q).astype(jnp.float32)
    dat = dtt * a.astype(jnp.float32)[None, :, None, None, None]
    bt = bb.transpose(0, 2, 1, 3).reshape(bsz, h, nc, q, n)
    ct = cc.transpose(0, 2, 1, 3).reshape(bsz, h, nc, q, n)

    grid = (bsz, h, nc)
    row = pl.BlockSpec((1, 1, 1, 1, q), lambda b, hh, c: (b, hh, c, 0, 0))
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda b, hh, c: (b, hh, c, 0, 0)),
            row,
            row,
            pl.BlockSpec((1, 1, 1, q, n), lambda b, hh, c: (b, hh, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n), lambda b, hh, c: (b, hh, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, q, p),
                               lambda b, hh, c: (b, hh, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, nc, q, p), xh.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, dat, bt, ct)
    return out.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
