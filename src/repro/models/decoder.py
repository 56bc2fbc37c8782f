"""Generic stacked decoder LM: dense / MoE / mLSTM / Mamba2 uniform stacks.

Provides init / forward(train|prefill) / decode over flat-dict params with
scan-over-layers + segmented remat (CKPT_i, AO_i) + logical-axis sharding.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import xlstm as XL
from repro.models.common import (Axes, ExecConfig, ParamBuilder, Params,
                                 StackedBuilder, name_act, segmented_layer_scan,
                                 shard_act, softmax_xent, subtree)

# ---------------------------------------------------------------------------
# Block kinds
# ---------------------------------------------------------------------------


def block_kind(cfg: ArchConfig) -> str:
    if cfg.family == "ssm" and cfg.xlstm_heads:
        return "mlstm"
    if cfg.family == "ssm":
        return "mamba2"
    return "attn"  # dense / moe / vlm


def init_block(b: StackedBuilder, cfg: ArchConfig):
    kind = block_kind(cfg)
    if kind == "attn":
        L.init_norm(b.scope("ln1"), cfg)
        L.init_attention(b.scope("attn"), cfg)
        L.init_norm(b.scope("ln2"), cfg)
        if cfg.is_moe:
            MOE.init_moe(b.scope("moe"), cfg)
        else:
            L.init_mlp(b.scope("mlp"), cfg)
    elif kind == "mamba2":
        L.init_norm(b.scope("ln1"), cfg)
        SSM.init_mamba2(b.scope("mixer"), cfg)
    elif kind == "mlstm":
        L.init_norm(b.scope("ln1"), cfg)
        XL.init_mlstm(b.scope("mixer"), cfg)
    else:
        raise ValueError(kind)


def apply_block(p: Params, h: jax.Array, cfg: ArchConfig, ec: ExecConfig,
                cache: Optional[Dict] = None, mask_kind: str = "causal",
                return_cache: bool = False
                ) -> Tuple[jax.Array, jax.Array, Any]:
    """Returns (h, aux_loss, new_cache)."""
    kind = block_kind(cfg)
    aux = jnp.zeros((), jnp.float32)
    h = name_act(h, "layer_in")
    if kind == "attn":
        hn = L.norm(subtree(p, "ln1"), h, cfg)
        a, new_cache = L.attention(subtree(p, "attn"), hn, cfg, ec,
                                   cache=cache, mask_kind=mask_kind)
        if return_cache and cache is None and new_cache is None:
            new_cache = _fresh_attn_cache(subtree(p, "attn"), hn, cfg)
        h = h + a
        h = shard_act(h, ("dp", "sp", None))
        hn = L.norm(subtree(p, "ln2"), h, cfg)
        if cfg.is_moe:
            m, aux = MOE.moe(subtree(p, "moe"), hn, cfg, ec)
        else:
            m = L.mlp(subtree(p, "mlp"), hn, cfg)
        h = h + m
    elif kind == "mamba2":
        hn = L.norm(subtree(p, "ln1"), h, cfg)
        m, new_cache = SSM.mamba2_mixer(
            subtree(p, "mixer"), hn, cfg, ec, cache=cache,
            return_state=return_cache and cache is None)
        h = h + m
    else:  # mlstm
        hn = L.norm(subtree(p, "ln1"), h, cfg)
        m, new_cache = XL.mlstm_mixer(
            subtree(p, "mixer"), hn, cfg, ec, cache=cache,
            return_state=return_cache and cache is None)
        h = h + m
    h = shard_act(h, ("dp", "sp", None))
    h = name_act(h, "resid")
    return h, aux, new_cache


def _fresh_attn_cache(p_attn: Params, hn: jax.Array, cfg: ArchConfig) -> Dict:
    """Build a populated KV cache from a prefill pass (for serving handoff)."""
    b_, s, _ = hn.shape
    if cfg.attention_type == "mla":
        kv = hn @ p_attn["wkv_down"]
        latent = L._rms(kv[..., :cfg.kv_lora_rank], p_attn["kv_norm"])
        k_rope = kv[..., cfg.kv_lora_rank:]
        cos, sin = L.rope_freqs(jnp.arange(s)[None, :], cfg.qk_rope_head_dim,
                                cfg.rope_theta)
        k_rope = L.apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0]
        return {"latent": latent, "k_rope": k_rope,
                "pos": jnp.asarray(s, jnp.int32)}
    k = jnp.einsum("bsd,dhk->bshk", hn, p_attn["wk"])
    v = jnp.einsum("bsd,dhk->bshk", hn, p_attn["wv"])
    if cfg.qkv_bias:
        k, v = k + p_attn["bk"], v + p_attn["bv"]
    cos, sin = L.rope_freqs(jnp.arange(s)[None, :], cfg.head_dim,
                            cfg.rope_theta)
    k = L.apply_rope(k, cos, sin)
    return {"k": k, "v": v, "pos": jnp.asarray(s, jnp.int32)}


def init_block_cache(cfg: ArchConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Dict:
    kind = block_kind(cfg)
    if kind == "attn":
        return L.init_self_kv_cache(cfg, batch, max_len, dtype)
    if kind == "mamba2":
        return SSM.init_mamba2_cache(cfg, batch, dtype)
    return XL.init_mlstm_cache(cfg, batch, dtype)


# ---------------------------------------------------------------------------
# LM assembly
# ---------------------------------------------------------------------------


def init_lm(rng: jax.Array, cfg: ArchConfig, dtype=jnp.bfloat16,
            abstract: bool = False) -> Tuple[Params, Axes]:
    pb = ParamBuilder(rng, dtype, abstract=abstract)
    pb.add("embed/w", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
           scale=0.02)
    sb = StackedBuilder(pb, "layers", cfg.num_layers)
    init_block(sb, cfg)
    L.init_norm(pb.scope("final_norm"), cfg)
    if not cfg.tie_embeddings:
        pb.add("lm_head/w", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
               scale=1.0 / math.sqrt(cfg.d_model))
    return pb.params, pb.axes


def embed_tokens(params: Params, tokens: jax.Array, cfg: ArchConfig,
                 ec: ExecConfig) -> jax.Array:
    x = jnp.take(params["embed/w"], tokens, axis=0).astype(ec.compute_dtype)
    return shard_act(x, ("dp", "sp", None))


def unembed_matrix(params: Params, cfg: ArchConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed/w"].T
    return params["lm_head/w"]


def run_layers(params: Params, x: jax.Array, cfg: ArchConfig, ec: ExecConfig
               ) -> Tuple[jax.Array, jax.Array]:
    """Scan all layers (train/prefill).  Returns (h, aux_loss)."""
    stacked = subtree(params, "layers")

    def body(carry, lp):
        h, aux = carry
        h, a, _ = apply_block(lp, h, cfg, ec)
        return (h, aux + a)

    h, aux = segmented_layer_scan(body, (x, jnp.zeros((), jnp.float32)),
                                  stacked, cfg.num_layers, ec)
    return L.norm(subtree(params, "final_norm"), h, cfg), aux


@jax.named_scope("lm_head")   # the vocabulary-parallel head's exchange
def chunked_xent(h: jax.Array, w_out: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None, chunk: int = 512
                 ) -> jax.Array:
    """Cross-entropy without materializing full (B,S,V) logits: scan over
    sequence chunks, recomputing chunk logits in bwd (checkpointed)."""
    b_, s, d = h.shape
    c = min(chunk, s)
    nc = s // c
    if nc * c != s:  # fall back for ragged smoke shapes
        logits = (h @ w_out).astype(jnp.float32)
        logits = shard_act(logits, ("dp", None, "tp"))
        return softmax_xent(logits, labels, mask)
    hc = h.reshape(b_, nc, c, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b_, nc, c).transpose(1, 0, 2)
    mc = (mask.reshape(b_, nc, c).transpose(1, 0, 2) if mask is not None
          else jnp.ones((nc, b_, c), jnp.float32))

    @jax.checkpoint
    def chunk_fn(carry, xs):
        tot, cnt = carry
        hh, ll, mm = xs
        logits = (hh @ w_out).astype(jnp.float32)
        logits = shard_act(logits, ("dp", None, "tp"))
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mm
        return (tot + nll.sum(), cnt + mm.sum()), None

    (tot, cnt), _ = jax.lax.scan(chunk_fn, (jnp.zeros((), jnp.float32),
                                            jnp.zeros((), jnp.float32)),
                                 (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


AUX_COEF = 0.01


def lm_loss(params: Params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            ec: ExecConfig) -> jax.Array:
    x = embed_tokens(params, batch["tokens"], cfg, ec)
    h, aux = run_layers(params, x, cfg, ec)
    loss = chunked_xent(h, unembed_matrix(params, cfg), batch["labels"],
                        batch.get("loss_mask"))
    return loss + AUX_COEF * aux / cfg.num_layers


def lm_prefill(params: Params, batch: Dict[str, jax.Array], cfg: ArchConfig,
               ec: ExecConfig, return_cache: bool = False):
    """Forward over the prompt; returns last-position logits (+ caches)."""
    x = embed_tokens(params, batch["tokens"], cfg, ec)
    if not return_cache:
        h, _ = run_layers(params, x, cfg, ec)
        logits = (h[:, -1:] @ unembed_matrix(params, cfg)).astype(jnp.float32)
        return shard_act(logits, ("dp", None, "tp"))
    # cache-populating path (no scan-remat; used by serving examples/tests)
    stacked = subtree(params, "layers")

    def body(carry, lp):
        h, = carry
        h, _, nc = apply_block(lp, h, cfg, ec, return_cache=True)
        return (h,), nc

    (h,), caches = jax.lax.scan(body, (x,), stacked)
    h = L.norm(subtree(params, "final_norm"), h, cfg)
    logits = (h[:, -1:] @ unembed_matrix(params, cfg)).astype(jnp.float32)
    return shard_act(logits, ("dp", None, "tp")), caches


def lm_decode(params: Params, tokens: jax.Array, caches, cfg: ArchConfig,
              ec: ExecConfig):
    """One decode step: tokens (B,1) + stacked caches -> (logits, new caches)."""
    x = embed_tokens(params, tokens, cfg, ec)
    stacked = subtree(params, "layers")

    def body(h, xs):
        lp, lc = xs
        h, _, nc = apply_block(lp, h, cfg, ec, cache=lc)
        return h, nc

    h, new_caches = jax.lax.scan(body, x, (stacked, caches))
    h = L.norm(subtree(params, "final_norm"), h, cfg)
    logits = (h @ unembed_matrix(params, cfg)).astype(jnp.float32)
    return shard_act(logits, ("dp", None, "tp")), new_caches


def init_lm_caches(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    one = init_block_cache(cfg, batch, max_len, dtype)
    return jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (cfg.num_layers,) + v.shape), one)
