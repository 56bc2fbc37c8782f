"""A dense decoder as a configuration file states it, and its weights.

The configuration files use the keys of the model's published
``config.json``.  ``layout`` names every weight tensor with the stacked-
layer naming the system under test uses, and ``draw`` makes them from
the seed: one program, on the device, in bfloat16.  The plain reference
draws the same weights with the same code, so it takes nothing that the
program has made."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Dense:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    eps: float
    attn_scale: float     # multiplies q.k
    emb_mult: float       # multiplies the embedding lookup
    resid_mult: float     # multiplies each block's output
    logits_div: float     # divides the logits
    init_std: float
    param_dtype: str      # the type the forward pass takes the weights in

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads


def from_doc(doc: dict) -> Dense:
    """The decoder a configuration file describes.  Keys the file leaves
    out take the value that the published architecture implies."""
    if doc.get("hidden_act", "silu") != "silu":
        raise ValueError(f"unsupported activation {doc['hidden_act']!r}")
    if doc["precision"]["params"] not in ("bfloat16", "float32"):
        raise ValueError(f"unsupported weight type {doc['precision']}")
    d, h = int(doc["hidden_size"]), int(doc["num_attention_heads"])
    hd = int(doc.get("head_dim", d // h))
    return Dense(
        layers=int(doc["num_hidden_layers"]), d_model=d, heads=h,
        kv_heads=int(doc["num_key_value_heads"]), head_dim=hd,
        d_ff=int(doc["intermediate_size"]), vocab=int(doc["vocab_size"]),
        tied=bool(doc["tie_word_embeddings"]),
        qkv_bias=bool(doc["attention_bias"]),
        rope_theta=float(doc["rope_theta"]), eps=float(doc["rms_norm_eps"]),
        attn_scale=float(doc.get("attention_multiplier",
                                 1.0 / math.sqrt(hd))),
        emb_mult=float(doc.get("embedding_multiplier", 1.0)),
        resid_mult=float(doc.get("residual_multiplier", 1.0)),
        logits_div=float(doc.get("logits_scaling", 1.0)),
        init_std=float(doc["initializer_range"]),
        param_dtype=doc["precision"]["params"])


def layout(m: Dense) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init) for every weight tensor; layer tensors carry
    a leading stacked-layer dim.  init is "normal" (std ``init_std``) or
    "ones" (norm scales)."""
    L, d, h, kv, hd, f = (m.layers, m.d_model, m.heads, m.kv_heads,
                          m.head_dim, m.d_ff)
    out = {
        "embed/w": ((m.vocab, d), "normal"),
        "final_norm/scale": ((d,), "ones"),
        "layers/ln1/scale": ((L, d), "ones"),
        "layers/ln2/scale": ((L, d), "ones"),
        "layers/attn/wq": ((L, d, h, hd), "normal"),
        "layers/attn/wk": ((L, d, kv, hd), "normal"),
        "layers/attn/wv": ((L, d, kv, hd), "normal"),
        "layers/attn/wo": ((L, h, hd, d), "normal"),
        "layers/mlp/wg": ((L, d, f), "normal"),
        "layers/mlp/wu": ((L, d, f), "normal"),
        "layers/mlp/wd": ((L, f, d), "normal"),
    }
    if m.qkv_bias:
        out["layers/attn/bq"] = ((L, h, hd), "normal")
        out["layers/attn/bk"] = ((L, kv, hd), "normal")
        out["layers/attn/bv"] = ((L, kv, hd), "normal")
    if not m.tied:
        out["lm_head/w"] = ((d, m.vocab), "normal")
    return dict(sorted(out.items()))


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size, 64 bits and more."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    k = jax.random.PRNGKey(0)
    while True:
        k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return k


def draw_leaf_f32(m: Dense, key: jax.Array, name: str) -> jax.Array:
    """Weight ``name`` in float32 before its rounding to bfloat16;
    ``key`` is ``base_key(seed)``, passed in so that one compiled program
    serves every seed."""
    names = list(layout(m))
    shape, init = layout(m)[name]
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(key, names.index(name))
    return jax.random.normal(key, shape, jnp.float32) * m.init_std


def draw(m: Dense, key: jax.Array) -> Dict[str, jax.Array]:
    """Every weight in bfloat16, as the model is trained; call under jit."""
    return {n: draw_leaf_f32(m, key, n).astype(jnp.bfloat16)
            for n in layout(m)}


def draw_leaf_rounded(m: Dense, key: jax.Array, name: str) -> jax.Array:
    """Weight ``name`` in float32, rounded as ``draw`` rounds it.  The
    rounding is forced by ``reduce_precision``: a compiler allowed excess
    precision may drop an f32 -> bf16 -> f32 round trip inside one
    program."""
    return jax.lax.reduce_precision(draw_leaf_f32(m, key, name),
                                    exponent_bits=8, mantissa_bits=7)
