"""Mixed-precision AdamW with ZeRO-partitionable, host-offloadable state.

State layout (flat dicts keyed by param name):
  state = {"step": i32, "params": bf16, "master": f32, "mu": f32, "nu": f32}

Any of master/mu/nu may be *split* along the stacked-layer dim into
``{"host": arr[:k], "dev": arr[k:]}`` to realize Mist's WO/OO offload ratios:
the host part carries a ``pinned_host`` memory-kind sharding, and the
optimizer update stages it through HBM one tensor at a time — the TPU
analogue of Mist's repositioned optimizer steps (paper §5.1).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.plan import StageConfig
from repro.models.common import Axes, Params
from repro.parallel.sharding import LAYER_AXES


@dataclass(frozen=True)
class AdamConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def is_split(leaf) -> bool:
    return isinstance(leaf, dict) and set(leaf) == {"host", "dev"}


def split_k(name: str, shape, axes_table: Axes, ratio: float) -> int:
    """How many leading (stacked-layer) slices go to host for this tensor."""
    if ratio <= 0.0 or not shape:
        return 0
    axes = axes_table.get(name, ())
    if not axes or axes[0] not in LAYER_AXES:
        return 0
    return int(round(ratio * shape[0]))


def _split(x, k):
    return {"host": x[:k], "dev": x[k:]} if k else x


def _join(leaf):
    if is_split(leaf):
        return jnp.concatenate([leaf["host"], leaf["dev"]], axis=0)
    return leaf


# ---------------------------------------------------------------------------
# state init (concrete + abstract) and shardings
# ---------------------------------------------------------------------------


def init_opt_entry(params: Params, axes_table: Axes, ratio: float,
                   like: str) -> Dict[str, Any]:
    """like: 'master' copies params to f32; 'zeros' makes f32 zeros."""
    out = {}
    for name, p in params.items():
        k = split_k(name, p.shape, axes_table, ratio)
        if like == "master":
            v = p.astype(jnp.float32) if not isinstance(p, jax.ShapeDtypeStruct) \
                else jax.ShapeDtypeStruct(p.shape, jnp.float32)
        else:
            v = jnp.zeros(p.shape, jnp.float32) if not isinstance(
                p, jax.ShapeDtypeStruct) else \
                jax.ShapeDtypeStruct(p.shape, jnp.float32)
        if k and isinstance(p, jax.ShapeDtypeStruct):
            out[name] = {"host": jax.ShapeDtypeStruct((k,) + p.shape[1:],
                                                      jnp.float32),
                         "dev": jax.ShapeDtypeStruct((p.shape[0] - k,)
                                                     + p.shape[1:],
                                                     jnp.float32)}
        else:
            out[name] = _split(v, k)
    return out


def init_state(params: Params, axes_table: Axes, stage: StageConfig
               ) -> Dict[str, Any]:
    return {
        "step": jnp.zeros((), jnp.int32) if not isinstance(
            next(iter(params.values())), jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct((), jnp.int32),
        "params": dict(params),
        "master": init_opt_entry(params, axes_table, stage.wo, "master"),
        "mu": init_opt_entry(params, axes_table, stage.oo, "zeros"),
        "nu": init_opt_entry(params, axes_table, stage.oo, "zeros"),
    }


# NOTE: the NamedSharding tree mirroring this state layout is produced by
# ``repro.lowering.LoweredPlan.state_shardings()`` — the single
# plan-interpretation pass (docs/plan-lowering.md).


# ---------------------------------------------------------------------------
# update
# ---------------------------------------------------------------------------


def global_norm(grads) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


def adam_update(state: Dict[str, Any], grads: Params, acfg: AdamConfig,
                shardings: Optional[Dict[str, Any]] = None,
                ) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """One AdamW step.  grads: f32 flat dict (same keys as params).

    ``shardings`` (same structure as state) is required when any state leaf
    is host-offloaded: host slices are explicitly staged through device
    memory for the update, then placed back.  With any leaf offloaded the
    tensors are updated one at a time (an ``optimization_barrier`` chain),
    so only one tensor's staged slices and f32 temporaries occupy device
    memory at once; plans with no offload leave the order to XLA."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, acfg.grad_clip / (gnorm + 1e-12))
    c1 = 1.0 - acfg.b1 ** step.astype(jnp.float32)
    c2 = 1.0 - acfg.b2 ** step.astype(jnp.float32)

    def to_dev(x, entry, name):
        sh = shardings[entry][name]["host"].with_memory_kind("device")
        return jax.device_put(x, sh)

    def to_host(x, entry, name):
        return jax.device_put(x, shardings[entry][name]["host"])

    new_params, new_master, new_mu, new_nu = {}, {}, {}, {}
    serial = any(is_split(leaf) for entry in ("master", "mu", "nu")
                 for leaf in state[entry].values())
    done = None
    for name, g in grads.items():
        m, mu, nu = state["master"][name], state["mu"][name], state["nu"][name]
        if serial and done is not None:
            # one tensor at a time: this tensor's inputs (its host slices
            # among them) wait for the previous tensor's update, so at most
            # one tensor's f32 update temporaries are live in device memory
            done, (g, m, mu, nu) = jax.lax.optimization_barrier(
                (done, (g, m, mu, nu)))
        g = g.astype(jnp.float32) * clip

        def upd(m, mu, nu, gg):
            mu = acfg.b1 * mu + (1 - acfg.b1) * gg
            nu = acfg.b2 * nu + (1 - acfg.b2) * gg * gg
            upd_ = (mu / c1) / (jnp.sqrt(nu / c2) + acfg.eps)
            m = m - acfg.lr * (upd_ + acfg.weight_decay * m)
            return m, mu, nu

        if is_split(m) or is_split(mu):
            kh = (m["host"].shape[0] if is_split(m)
                  else mu["host"].shape[0])

            def part(leaf, entry, lo, hi):
                if is_split(leaf):
                    return (to_dev(leaf["host"], entry, name) if lo == 0
                            else leaf["dev"])
                return leaf[lo:hi]

            L_ = g.shape[0]
            mh, muh, nuh = upd(part(m, "master", 0, kh),
                               part(mu, "mu", 0, kh),
                               part(nu, "nu", 0, kh), g[:kh])
            md, mud, nud = upd(part(m, "master", kh, L_),
                               part(mu, "mu", kh, L_),
                               part(nu, "nu", kh, L_), g[kh:])

            def pack(leaf, entry, h, d):
                if is_split(leaf):
                    return {"host": to_host(h, entry, name), "dev": d}
                return jnp.concatenate([h, d], axis=0)

            new_master[name] = pack(m, "master", mh, md)
            new_mu[name] = pack(mu, "mu", muh, mud)
            new_nu[name] = pack(nu, "nu", nuh, nud)
            full_m = jnp.concatenate([mh, md], axis=0)
        else:
            full_m, new_mu[name], new_nu[name] = upd(m, mu, nu, g)
            new_master[name] = full_m
        new_params[name] = full_m.astype(state["params"][name].dtype)
        done = (new_params[name], new_master[name], new_mu[name],
                new_nu[name])

    new_state = {"step": step, "params": new_params, "master": new_master,
                 "mu": new_mu, "nu": new_nu}
    metrics = {"grad_norm": gnorm}
    return new_state, metrics
