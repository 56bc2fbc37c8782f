"""Core layers: norms, rotary embeddings, MLPs, GQA and MLA attention.

Every layer is an (init, apply) pair over flat-dict params.  Attention
supports train/prefill (full sequence, causal or bidirectional) and decode
(one token against a KV cache) with MLA using the absorbed-matmul decode path.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import (ExecConfig, Params, ScopedBuilder, shard_act)

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(b: ScopedBuilder, cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    b.add("scale", (d,), ("embed",), init="ones")
    if cfg.norm_type == "layernorm":
        b.add("bias", (d,), ("embed",), init="zeros")


def norm(p: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + 1e-6) * p["scale"].astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        x = x + p["bias"].astype(jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary
# ---------------------------------------------------------------------------


def rope_freqs(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin (..., dim//2), f32."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., S, H, d); cos/sin (..., S, d//2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(b: ScopedBuilder, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_gated:
        b.add("wg", (d, f), ("embed", "mlp"))
        b.add("wu", (d, f), ("embed", "mlp"))
    else:
        b.add("wu", (d, f), ("embed", "mlp"))
        b.add("bu", (f,), ("mlp",), init="zeros")
        b.add("bd", (d,), ("embed",), init="zeros")
    b.add("wd", (f, d), ("mlp", "embed"))


def _act(x: jax.Array, kind: str) -> jax.Array:
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


def mlp(p: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    if cfg.mlp_gated:
        h = _act(x @ p["wg"], cfg.act) * (x @ p["wu"])
        h = shard_act(h, ("dp", None, "tp"))
        return h @ p["wd"]
    h = _act(x @ p["wu"] + p["bu"], cfg.act)
    h = shard_act(h, ("dp", None, "tp"))
    return h @ p["wd"] + p["bd"]


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def init_attention(b: ScopedBuilder, cfg: ArchConfig):
    if cfg.attention_type == "mla":
        return init_mla(b, cfg)
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    b.add("wq", (d, h, hd), ("embed", "heads", "head_dim"),
          scale=1.0 / math.sqrt(d))
    b.add("wk", (d, kv, hd), ("embed", "kv_heads", "head_dim"),
          scale=1.0 / math.sqrt(d))
    b.add("wv", (d, kv, hd), ("embed", "kv_heads", "head_dim"),
          scale=1.0 / math.sqrt(d))
    b.add("wo", (h, hd, d), ("heads", "head_dim", "embed"),
          scale=1.0 / math.sqrt(h * hd))
    if cfg.qkv_bias:
        b.add("bq", (h, hd), ("heads", "head_dim"), init="zeros")
        b.add("bk", (kv, hd), ("kv_heads", "head_dim"), init="zeros")
        b.add("bv", (kv, hd), ("kv_heads", "head_dim"), init="zeros")


def _sdpa(q, k, v, cfg: ArchConfig, mask_kind: str, q_pos0=None,
          kv_valid_len=None, acc_dtype=jnp.float32) -> jax.Array:
    """q (B,Sq,KV,G,hd), k/v (B,Sk,KV,hd) -> (B,Sq,KV,G,hd).  f32 softmax.

    acc_dtype: QK^T accumulation type.  The decode path passes the cache
    dtype: on the CPU host-compile target an f32-accumulating dot makes XLA
    legalize bf16 operands with a convert that LICM hoists out of the layer
    scan — materializing a full f32 copy of the KV cache.  (On TPU the MXU
    accumulates bf16 x bf16 -> f32 natively; softmax stats stay f32 here
    either way.)"""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=acc_dtype
                        ).astype(jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    if mask_kind == "causal":
        qp = jnp.arange(sq) + (q_pos0 if q_pos0 is not None else 0)
        mask = qp[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    if kv_valid_len is not None:
        valid = jnp.arange(sk)[None, :] < kv_valid_len
        scores = jnp.where(valid[:, None, None, None] if valid.ndim == 2
                           else valid[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v)


def _per_device(fn, batch: int, kv_heads: int):
    """Run an attention kernel on each device's block of (B, S, heads, hd)
    operands.  GSPMD cannot partition a Pallas (Mosaic) kernel, so the
    call is shard_mapped over the axes the batch ('dp') and heads ('tp')
    are sharded on; an axis that does not divide its dim stays
    replicated."""
    from jax.sharding import PartitionSpec as P
    from repro.models.common import current_rules
    from repro.parallel.sharding import axis_size
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return fn
    dp, tp = rules.mapping.get("dp"), rules.mapping.get("tp")
    if dp is not None and batch % axis_size(rules.mesh, dp):
        dp = None
    if tp is not None and kv_heads % axis_size(rules.mesh, tp):
        tp = None
    spec = P(dp, None, tp, None)
    manual = {a for ax in (dp, tp) if ax is not None
              for a in ((ax,) if isinstance(ax, str) else ax)}
    if not manual:
        return fn
    mesh = jax.sharding.get_abstract_mesh()
    return jax.shard_map(fn, mesh=rules.mesh if mesh.empty else mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         axis_names=manual, check_vma=False)


def attention(p: Params, x: jax.Array, cfg: ArchConfig, exec_cfg: ExecConfig,
              *, positions: Optional[jax.Array] = None, mask_kind="causal",
              cache: Optional[Dict] = None, kv_x: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Dict]]:
    """GQA attention.  train/prefill: cache=None; decode: cache holds
    {"k","v","pos"} and x is (B,1,D).  kv_x: cross-attention source."""
    if cfg.attention_type == "mla":
        return mla_attention(p, x, cfg, exec_cfg, positions=positions,
                             cache=cache)
    b_, s, d = x.shape
    h, kv, hd, g = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.q_per_kv
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    has_kv_cache = cache is not None and "pos" not in cache  # cross-attn cache
    if not has_kv_cache:
        k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
        if cfg.qkv_bias:
            k, v = k + p["bk"], v + p["bv"]
    q = shard_act(q, ("dp", None, "tp", None))

    is_cross = kv_x is not None or (cache is not None and "pos" not in cache)
    use_rope = mask_kind == "causal" and not is_cross
    if use_rope:
        if positions is not None:
            pos_q = positions
        elif cache is None:
            pos_q = jnp.arange(s)[None, :]
        elif cache["pos"].ndim == 1:
            # per-request decode positions (continuous batching): every
            # row rotates by its own offset
            pos_q = cache["pos"][:, None]
        else:
            pos_q = jnp.full((1, 1), cache["pos"], jnp.int32)
        cos_q, sin_q = rope_freqs(pos_q, hd, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)

    new_cache = None
    if cache is None:
        if use_rope:
            cos_k, sin_k = rope_freqs(jnp.arange(s)[None, :], hd, cfg.rope_theta)
            k = apply_rope(k, cos_k, sin_k)
        k = shard_act(k, ("dp", None, "tp", None))
        if exec_cfg.attn_impl != "naive":
            from repro.kernels import ops as kops

            def attn(q_, k_, v_):
                return kops.attention(q_, k_, v_,
                                      causal=(mask_kind == "causal"),
                                      impl=exec_cfg.attn_impl,
                                      q_block=exec_cfg.attn_q_block,
                                      kv_block=exec_cfg.attn_kv_block)
            if exec_cfg.attn_impl == "pallas":
                attn = _per_device(attn, q.shape[0], kv)
            ctx = attn(q, k, v).reshape(b_, s, kv, g, hd)
        else:
            qg = q.reshape(b_, s, kv, g, hd)
            ctx = _sdpa(qg, k, v, cfg, mask_kind)
    elif is_cross:  # cross-attention with precomputed k/v cache
        k, v = cache["k"], cache["v"]
        qg = q.reshape(b_, s, kv, g, hd)
        ctx = _sdpa(qg, k, v, cfg, "full")
        new_cache = cache
    else:  # self-attention decode
        pos = cache["pos"]
        vec = pos.ndim == 1  # per-request positions (continuous batching)
        if use_rope:
            pos_k = pos[:, None] if vec else jnp.full((1, 1), pos, jnp.int32)
            cos_k, sin_k = rope_freqs(pos_k, hd, cfg.rope_theta)
            k = apply_rope(k, cos_k, sin_k)
        new_cache = {"pos": pos + 1}
        if cache["k"].dtype == jnp.int8:   # quantized KV cache
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            ck = cache_write(cache["k"], kq, pos, 1, exec_cfg)
            cv = cache_write(cache["v"], vq, pos, 1, exec_cfg)
            kss = cache_write(cache["k_scale"], ks, pos, 1, exec_cfg)
            vss = cache_write(cache["v_scale"], vs, pos, 1, exec_cfg)
            new_cache.update(k=ck, v=cv, k_scale=kss, v_scale=vss)
            kd = dequantize_kv(ck, kss, x.dtype)
            vd = dequantize_kv(cv, vss, x.dtype)
        else:
            kd = ck = cache_write(cache["k"], k, pos, 1, exec_cfg)
            vd = cv = cache_write(cache["v"], v, pos, 1, exec_cfg)
            new_cache.update(k=ck, v=cv)
        qg = q.reshape(b_, s, kv, g, hd).astype(kd.dtype)
        ctx = _sdpa(qg, kd, vd, cfg, "full",
                    kv_valid_len=(pos[:, None] + 1) if vec else pos + 1,
                    acc_dtype=kd.dtype)

    ctx = ctx.reshape(b_, s, h, hd)
    out = jnp.einsum("bshk,hkd->bsd", ctx, p["wo"])
    return out, new_cache


def cache_write(buf: jax.Array, upd: jax.Array, pos: jax.Array,
                seq_dim: int, exec_cfg: ExecConfig) -> jax.Array:
    """Write a one-token update into the cache at `pos` along `seq_dim`.

    pos may be a scalar (lock-step decode) or a (B,) vector of per-request
    positions (continuous batching); the vector form always lowers to the
    one-hot masked write because DUS cannot express per-row start offsets.
    Both forms write the same values bitwise: `where` selects exact
    operands, so only the untouched-row representation differs."""
    upd = upd.astype(buf.dtype)
    if getattr(pos, "ndim", 0) == 1:
        assert upd.shape[seq_dim] == 1, "one-token decode writes only"
        oh = jnp.arange(buf.shape[seq_dim])[None, :] == pos[:, None]  # (B,S)
        shape = [1] * buf.ndim
        shape[0] = buf.shape[0]
        shape[seq_dim] = buf.shape[seq_dim]
        oh = oh.reshape(shape)
        return jnp.where(oh, jnp.broadcast_to(upd, buf.shape), buf)
    if exec_cfg.cache_update == "dus":
        start = [0] * buf.ndim
        start[seq_dim] = pos
        return jax.lax.dynamic_update_slice(buf, upd, tuple(start))
    # one-hot masked write: elementwise, so a 'model'-sharded sequence dim
    # stays fully local (GSPMD would replicate the equivalent DUS)
    assert upd.shape[seq_dim] == 1, "one-token decode writes only"
    oh = (jnp.arange(buf.shape[seq_dim]) == pos)
    shape = [1] * buf.ndim
    shape[seq_dim] = buf.shape[seq_dim]
    oh = oh.reshape(shape)
    return jnp.where(oh, jnp.broadcast_to(upd, buf.shape), buf)


def init_self_kv_cache(cfg: ArchConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16) -> Dict:
    if cfg.attention_type == "mla":
        return {
            "latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    out = {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if dtype == jnp.int8:
        # quantized KV: dynamic per-(token, head) scales (beyond-paper —
        # the only way 32k x 128 MHA caches fit a 16 GiB-chip pod)
        out["k_scale"] = jnp.zeros((batch, max_len, cfg.num_kv_heads),
                                   jnp.float32)
        out["v_scale"] = jnp.zeros((batch, max_len, cfg.num_kv_heads),
                                   jnp.float32)
    return out


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x (..., KV, hd) -> (int8 values, f32 per-(.., KV) scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q: jax.Array, scale: jax.Array,
                  dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


# ---------------------------------------------------------------------------
# MLA attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------


def init_mla(b: ScopedBuilder, cfg: ArchConfig):
    d, h = cfg.d_model, cfg.num_heads
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    b.add("wq_down", (d, ql), ("embed", "q_lora"))
    b.add("q_norm", (ql,), ("q_lora",), init="ones")
    b.add("wq_up", (ql, h, dn + dr), ("q_lora", "heads", "head_dim"))
    b.add("wkv_down", (d, kl + dr), ("embed", "kv_lora"))
    b.add("kv_norm", (kl,), ("kv_lora",), init="ones")
    b.add("wkv_up", (kl, h, dn + dv), ("kv_lora", "heads", "head_dim"))
    b.add("wo", (h, dv, d), ("heads", "head_dim", "embed"),
          scale=1.0 / math.sqrt(h * dv))


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def mla_attention(p: Params, x: jax.Array, cfg: ArchConfig,
                  exec_cfg: ExecConfig, *, positions=None, cache=None):
    b_, s, d = x.shape
    h = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kl = cfg.kv_lora_rank
    scale = 1.0 / math.sqrt(dn + dr)

    q = _rms(x @ p["wq_down"], p["q_norm"])
    q = jnp.einsum("bsl,lhk->bshk", q, p["wq_up"])
    q = shard_act(q, ("dp", None, "tp", None))
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    kv = x @ p["wkv_down"]
    latent, k_rope = kv[..., :kl], kv[..., kl:]
    latent = _rms(latent, p["kv_norm"])

    if cache is None:
        pos = jnp.arange(s)[None, :] if positions is None else positions
        cos, sin = rope_freqs(pos, dr, cfg.rope_theta)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
        kvu = jnp.einsum("bsl,lhk->bshk", latent, p["wkv_up"])
        kvu = shard_act(kvu, ("dp", None, "tp", None))
        k_nope, v = kvu[..., :dn], kvu[..., dn:]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None, :], (b_, s, h, dr))], -1)
        qf = jnp.concatenate([q_nope, q_rope], -1)
        scores = jnp.einsum("bqhk,bskh->bhqs", qf,
                            k.transpose(0, 1, 3, 2),
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        w = jax.nn.softmax(scores, -1)
        ctx = jnp.einsum("bhqs,bshv->bqhv", w.astype(v.dtype), v)
        new_cache = None
    else:
        # absorbed decode: score via latent cache, never expand K/V
        pos = cache["pos"]
        vec = pos.ndim == 1  # per-request positions (continuous batching)
        pos_r = pos[:, None] if vec else jnp.full((1, 1), pos, jnp.int32)
        cos, sin = rope_freqs(pos_r, dr, cfg.rope_theta)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
        lat_c = cache_write(cache["latent"], latent, pos, 1, exec_cfg)
        kr_c = cache_write(cache["k_rope"], k_rope, pos, 1, exec_cfg)
        wk = p["wkv_up"][..., :dn]  # (kl, h, dn)
        q_lat = jnp.einsum("bqhk,lhk->bqhl", q_nope, wk)
        scores = (jnp.einsum("bqhl,bsl->bhqs", q_lat, lat_c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr_c,
                               preferred_element_type=jnp.float32)) * scale
        valid = jnp.arange(lat_c.shape[1])[None, :] <= (
            pos[:, None] if vec else pos)
        scores = jnp.where(valid[:, None, None] if vec else valid[None, None],
                           scores, NEG_INF)
        w = jax.nn.softmax(scores, -1)
        ctx_lat = jnp.einsum("bhqs,bsl->bqhl", w.astype(lat_c.dtype), lat_c)
        wv = p["wkv_up"][..., dn:]  # (kl, h, dv)
        ctx = jnp.einsum("bqhl,lhv->bqhv", ctx_lat, wv)
        new_cache = {"latent": lat_c, "k_rope": kr_c, "pos": pos + 1}

    out = jnp.einsum("bshv,hvd->bsd", ctx, p["wo"])
    return out, new_cache
