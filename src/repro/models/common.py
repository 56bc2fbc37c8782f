"""Shared model machinery.

Params are a *flat dict* ``{"path/to/param": Array}`` with a parallel
``{"path/to/param": (logical_axis | None, ...)}`` axes table.  Flat dicts make
sharding rules, ZeRO partitioning, host offloading slices, and checkpoint
manifests trivial, and stacked-layer arrays (leading ``L`` dim) keep HLO size
O(1) in depth via ``lax.scan``.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

Params = Dict[str, jax.Array]
Axes = Dict[str, Tuple[Optional[str], ...]]

# ---------------------------------------------------------------------------
# Execution config (runtime knobs; plan-dependent, never changes the math)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ExecConfig:
    ckpt_layers: int = 10**9          # layers rematerialized (clamped to L)
    attn_impl: str = "naive"          # naive | blocked | pallas
    offload_layers: int = 0           # of the remat'd layers, how many offload acts
    remat_policy: str = "full"        # full | dots | none
    use_pallas: bool = False          # Pallas kernels (TPU); jnp ref path otherwise
    moe_group_size: int = 4096
    # kernel tile/block sizes (plan.kernel -> stage_exec_config); the
    # defaults match core/plan.DEFAULT_KERNEL_CONFIG
    attn_q_block: int = 512
    attn_kv_block: int = 512
    rmsnorm_block: int = 256
    ssd_chunk: int = 256
    mlstm_chunk: int = 256
    compute_dtype: Any = jnp.bfloat16
    logits_dtype: Any = jnp.float32
    sequence_parallel: bool = True
    # decode KV-cache write: "dus" (dynamic-update-slice; optimal when the
    # sequence dim is unsharded) or "onehot" (elementwise masked write; stays
    # local when the cache sequence dim is sharded over 'model' — GSPMD
    # replicates a DUS whose updated dim is sharded)
    cache_update: str = "dus"

    def replace(self, **kw) -> "ExecConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Logical-axis sharding context
# ---------------------------------------------------------------------------
# Models annotate activations with *logical* axes; the step builder installs a
# rules object mapping logical -> physical mesh axes.  Without rules installed
# (pure CPU smoke tests) annotations are no-ops.


@dataclass(frozen=True)
class ShardRules:
    """logical axis name -> physical mesh axis (or tuple of axes)."""

    mapping: Dict[str, Any]
    mesh: Any = None

    def spec_for(self, logical: Sequence[Optional[str]]):
        from jax.sharding import PartitionSpec as P

        return P(*[self.mapping.get(a) if a else None for a in logical])


_RULES: contextvars.ContextVar[Optional[ShardRules]] = contextvars.ContextVar(
    "shard_rules", default=None)


@contextlib.contextmanager
def use_rules(rules: Optional[ShardRules]):
    tok = _RULES.set(rules)
    try:
        yield
    finally:
        _RULES.reset(tok)


def current_rules() -> Optional[ShardRules]:
    return _RULES.get()


def shard_act(x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
    """Constrain activation sharding by logical axes; no-op without rules."""
    rules = _RULES.get()
    if rules is None:
        return x
    spec = rules.spec_for(logical)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Param builder
# ---------------------------------------------------------------------------


class ParamBuilder:
    """Creates params and records their logical axes as it goes.

    ``abstract=True`` records ShapeDtypeStructs instead of allocating —
    used by the dry-run / tuner, which never materialize weights.
    """

    def __init__(self, rng: Optional[jax.Array], dtype=jnp.bfloat16,
                 abstract: bool = False):
        self._rng = rng
        self.dtype = dtype
        self.abstract = abstract
        self.params: Params = {}
        self.axes: Axes = {}

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def add(self, name: str, shape: Tuple[int, ...],
            axes: Tuple[Optional[str], ...], init: str = "normal",
            scale: Optional[float] = None, dtype=None) -> None:
        assert len(shape) == len(axes), (name, shape, axes)
        assert name not in self.params, f"duplicate param {name}"
        dtype = dtype or self.dtype
        if self.abstract:
            self.params[name] = jax.ShapeDtypeStruct(shape, dtype)
            self.axes[name] = tuple(axes)
            return
        if init == "zeros":
            v = jnp.zeros(shape, dtype)
        elif init == "ones":
            v = jnp.ones(shape, dtype)
        elif init == "normal":
            if scale is None:  # fan-in scaling
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                scale = 1.0 / math.sqrt(max(1, fan_in))
            v = (jax.random.normal(self._next_rng(), shape, jnp.float32)
                 * scale).astype(dtype)
        else:
            raise ValueError(init)
        self.params[name] = v
        self.axes[name] = tuple(axes)

    def scope(self, prefix: str) -> "ScopedBuilder":
        return ScopedBuilder(self, prefix)


class ScopedBuilder:
    def __init__(self, parent, prefix: str):
        self._p = parent
        self._prefix = prefix

    def add(self, name, *a, **kw):
        self._p.add(f"{self._prefix}/{name}", *a, **kw)

    def scope(self, prefix: str):
        return ScopedBuilder(self._p, f"{self._prefix}/{prefix}")

    @property
    def dtype(self):
        return self._p.dtype


class StackedBuilder(ScopedBuilder):
    """Adds params with leading stacked-layer dim(s) (for lax.scan).

    ``num_layers`` may be an int or a tuple (nested scans, e.g. Zamba2's
    (groups, layers-per-group)).
    """

    def __init__(self, parent, prefix: str, num_layers):
        super().__init__(parent, prefix)
        self._L = (num_layers,) if isinstance(num_layers, int) else tuple(num_layers)

    def add(self, name, shape, axes, **kw):
        lead_axes = tuple(f"layers{i if i else ''}" for i in range(len(self._L)))
        super().add(name, self._L + tuple(shape), lead_axes + tuple(axes), **kw)

    def scope(self, prefix: str):
        return StackedBuilder(self._p, f"{self._prefix}/{prefix}", self._L)


# -- flat-dict utilities -----------------------------------------------------


def subtree(params: Params, prefix: str) -> Params:
    """View of all params under ``prefix/`` with the prefix stripped."""
    pl = prefix + "/"
    return {k[len(pl):]: v for k, v in params.items() if k.startswith(pl)}


def stack_layer_tree(trees: Sequence[Params]) -> Params:
    """Stack per-layer flat dicts into one dict of (L, ...) arrays."""
    keys = trees[0].keys()
    return {k: jnp.stack([t[k] for t in trees]) for k in keys}


# ---------------------------------------------------------------------------
# Segmented scan with per-segment remat wrapping (CKPT_i / AO_i realization)
# ---------------------------------------------------------------------------


def _remat_wrap(body: Callable, policy: str, offload: bool) -> Callable:
    if policy == "none" and not offload:
        return body
    if offload:
        from repro import compat
        pol = jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["resid", "layer_in"],
            offload_src="device", offload_dst=compat.host_memory_kind())
    elif policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    elif policy == "full":
        pol = None  # full remat: save nothing
    else:
        raise ValueError(policy)
    return jax.checkpoint(body, policy=pol, prevent_cse=False)


def segmented_layer_scan(body: Callable, carry, stacked: Params,
                         num_layers: int, exec_cfg: ExecConfig,
                         extra_xs: Optional[Params] = None):
    """scan over stacked layers, split into [offload-remat | remat | saved].

    ``body(carry, layer_params, layer_idx_offset) -> carry`` is the layer fn.
    The first ``offload_layers`` rematerialize *and* offload their saved
    inputs to host; the next ``ckpt - offload`` only rematerialize; the rest
    save all intermediates (no remat).  This realizes Mist's (CKPT_i, AO_i)
    knobs as scan-split points.
    """
    ckpt = min(exec_cfg.ckpt_layers, num_layers)
    off = min(exec_cfg.offload_layers, ckpt)
    segments = []  # (start, stop, policy, offload)
    if off:
        segments.append((0, off, exec_cfg.remat_policy, True))
    if ckpt - off:
        segments.append((off, ckpt, exec_cfg.remat_policy, False))
    if num_layers - ckpt:
        segments.append((ckpt, num_layers, "none", False))

    def sliced(tree, lo, hi):
        return {k: v[lo:hi] for k, v in tree.items()}

    for lo, hi, policy, offload in segments:
        seg_body = _remat_wrap(
            lambda c, xs: (body(c, xs), None), policy, offload)
        xs = sliced(stacked, lo, hi)
        if extra_xs is not None:
            xs = (xs, sliced(extra_xs, lo, hi))
        carry, _ = jax.lax.scan(seg_body, carry, xs)
    return carry


def name_act(x: jax.Array, name: str) -> jax.Array:
    """Tag an activation for offload-aware remat policies."""
    return checkpoint_name(x, name)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross-entropy; logits (..., V) f32, labels int."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
