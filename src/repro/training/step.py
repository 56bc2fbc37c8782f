"""Step builders: LoweredPlan -> jitted train_step / serve_step.

``make_train_step`` realizes a single-stage plan (DP x TP x SP, ZeRO-0..3,
CKPT/AO remat segmentation, WO/OO host offload, optional int8 gradient
compression, gradient accumulation).  Pipeline (S>1) plans go through
``repro.parallel.pipeline``.

Every builder takes an optional pre-computed ``lowered`` (the output of
``repro.lowering.lower_plan``) and lowers the plan itself otherwise; all
mesh-axis mapping, sharding tables, and exec-config derivation live in
that one pass — nothing here interprets the plan directly.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro import compat
from repro.core.plan import Plan
from repro.lowering import LoweredPlan, lower_plan
from repro.models.common import ExecConfig, use_rules
from repro.models.zoo import Model
from repro.training import optimizer as OPT


@dataclass
class CompiledStep:
    fn: Callable                       # jitted
    state_shardings: Any
    batch_shardings: Any
    exec_cfg: ExecConfig


def _is_host_leaf(s) -> bool:
    return getattr(s, "memory_kind", None) == compat.HOST_MEMORY_KIND


def _constrain_device_leaves(tree, shardings):
    """Pin device-memory leaves to their planned shardings (host leaves are
    already placed by device_put inside the optimizer)."""
    def leaf(x, s):
        if isinstance(s, NamedSharding) and not _is_host_leaf(s):
            return jax.lax.with_sharding_constraint(x, s)
        return x
    return jax.tree.map(leaf, tree, shardings)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def make_train_step(model: Model, plan: Plan, mesh: Mesh,
                    adam: OPT.AdamConfig = OPT.AdamConfig(),
                    donate: bool = True,
                    lowered: Optional[LoweredPlan] = None) -> CompiledStep:
    assert plan.num_stages == 1, "use parallel.pipeline for S>1 plans"
    low = lowered or lower_plan(model.cfg, None, plan, mesh)
    ec = low.stages[0].exec_cfg
    rules = low.shard_rules()
    params_sds = low.params_sds
    state_abs = OPT.init_state(params_sds, low.axes_table, plan.stages[0])
    st_shardings = low.state_shardings()
    g_shardings = low.grad_shardings()

    G = plan.grad_accum

    def train_step(state, batch):
        with use_rules(rules):
            params = state["params"]

            def loss_of(p, mb):
                return model.loss_fn(p, mb, ec)

            if G == 1:
                loss, grads = jax.value_and_grad(loss_of)(params, batch)
                grads = {n: g.astype(jnp.float32) for n, g in grads.items()}
            else:
                mbs = jax.tree.map(
                    lambda x: x.reshape((G, x.shape[0] // G) + x.shape[1:]),
                    batch)
                zero_g = {n: jnp.zeros(s.shape, jnp.float32)
                          for n, s in params_sds.items()}
                zero_g = jax.lax.with_sharding_constraint(zero_g, g_shardings)

                def micro(acc, mb):
                    l, g = jax.value_and_grad(loss_of)(params, mb)
                    acc = {n: acc[n] + g[n].astype(jnp.float32) for n in acc}
                    acc = jax.lax.with_sharding_constraint(acc, g_shardings)
                    return acc, l

                grads, losses = jax.lax.scan(micro, zero_g, mbs)
                grads = {n: g / G for n, g in grads.items()}
                loss = jnp.mean(losses)

            grads = jax.lax.with_sharding_constraint(grads, g_shardings)
            if plan.grad_compression:
                from repro.parallel.compression import fake_compress
                grads = fake_compress(grads)
            new_state, om = OPT.adam_update(state, grads, adam, st_shardings)
            new_state = _constrain_device_leaves(new_state, st_shardings)
            metrics = {"loss": loss, **om, "step": new_state["step"]}
            return new_state, metrics

    batch_sh = None  # filled by caller via batch_shardings fn
    has_host = any(_is_host_leaf(s) for s in jax.tree.leaves(st_shardings))
    # out_shardings keep every state leaf where the plan put it, host-
    # offloaded slices included.  The CPU backend cannot place jit outputs
    # in pinned_host: there the host slices leave the step in device
    # memory, are moved back after it, and are not donated (a donated
    # pinned_host buffer would alias an output the runtime takes for
    # device memory).
    host_after = has_host and jax.default_backend() == "cpu"
    jit_kw = {} if host_after else {"out_shardings": (st_shardings, None)}
    jit_fn = jax.jit(
        train_step,
        in_shardings=(st_shardings, None),
        donate_argnums=(0,) if donate and not host_after else (),
        **jit_kw,
    )
    if host_after:
        def fn(state, batch):
            new_state, metrics = jit_fn(state, batch)
            return jax.device_put(new_state, st_shardings), metrics
        fn.lower = jit_fn.lower  # type: ignore[attr-defined]  # dry-run lowers the jitted core
    else:
        fn = jit_fn
    return CompiledStep(fn=fn, state_shardings=st_shardings,
                        batch_shardings=batch_sh, exec_cfg=ec)


def init_sharded_state(model: Model, plan: Plan, mesh: Mesh, rng: jax.Array,
                       lowered: Optional[LoweredPlan] = None
                       ) -> Tuple[Dict[str, Any], Any]:
    """Materialize a sharded TrainState on the mesh (the pipeline step's
    stage-sharded layout when the plan has several stages)."""
    low = lowered or lower_plan(model.cfg, None, plan, mesh)
    stage = plan.stages[0]
    axes_table = low.axes_table
    shardings = (low.pipeline_state_shardings() if plan.num_stages > 1
                 else low.state_shardings())

    # jit-init with device-memory shardings (XLA SPMD rejects host memory
    # kinds on freshly-created values), then move host-offloaded slices out.
    dev_shardings = jax.tree.map(
        lambda s: NamedSharding(s.mesh, s.spec) if isinstance(
            s, NamedSharding) else s, shardings,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    # the params are materialized before the master copy is cast from
    # them: within one program the TPU compiler may drop the f32 -> bf16
    # -> f32 rounding (excess precision), leaving master != params
    params = jax.jit(lambda: model.init(rng)[0],
                     out_shardings=dev_shardings["params"])()
    state = jax.jit(lambda p: OPT.init_state(p, axes_table, stage),
                    out_shardings=dev_shardings, donate_argnums=0)(params)
    needs_move = any(_is_host_leaf(s) for s in jax.tree.leaves(shardings))
    if needs_move:
        state = jax.device_put(state, shardings)
    return state, shardings


# ---------------------------------------------------------------------------
# serve steps (prefill / decode)
# ---------------------------------------------------------------------------


def make_prefill_step(model: Model, plan: Optional[Plan] = None,
                      mesh: Optional[Mesh] = None,
                      return_cache: bool = False,
                      lowered: Optional[LoweredPlan] = None) -> CompiledStep:
    if lowered is None and (plan is None or mesh is None):
        raise ValueError("make_prefill_step needs either lowered= or "
                         "(plan, mesh)")
    low = lowered or lower_plan(model.cfg, None, plan, mesh)
    ec = low.serve_exec_cfg
    rules = low.shard_rules()

    def prefill(params, batch):
        with use_rules(rules):
            return model.prefill_fn(params, batch, ec, return_cache)

    return CompiledStep(fn=jax.jit(prefill), state_shardings=None,
                        batch_shardings=None, exec_cfg=ec)


def make_serve_step(model: Model, plan: Optional[Plan] = None,
                    mesh: Optional[Mesh] = None,
                    batch: int = 1, max_len: int = 1, donate: bool = True,
                    lowered: Optional[LoweredPlan] = None) -> CompiledStep:
    """One-token decode against caches of length max_len."""
    if lowered is None and (plan is None or mesh is None):
        raise ValueError("make_serve_step needs either lowered= or "
                         "(plan, mesh)")
    low = lowered or lower_plan(model.cfg, None, plan, mesh)
    rules = low.shard_rules()

    kv_dtype = low.plan.kv_cache_dtype
    cache_dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    caches_sds = jax.eval_shape(
        lambda: model.init_caches(batch, max_len, cache_dtype))
    cache_sh, update_mode = low.cache_shardings(caches_sds, batch)
    ec = low.serve_exec_cfg.replace(cache_update=update_mode)

    def serve(params, tokens, caches):
        with use_rules(rules):
            logits, new_caches = model.decode_fn(params, tokens, caches, ec)
            return logits, new_caches

    jit_fn = jax.jit(serve, in_shardings=(None, None, cache_sh),
                     out_shardings=(None, cache_sh),
                     donate_argnums=(2,) if donate else ())
    return CompiledStep(fn=jit_fn, state_shardings=None,
                        batch_shardings=cache_sh, exec_cfg=ec)


def make_paged_serve_step(model: Model, plan: Optional[Plan] = None,
                          mesh: Optional[Mesh] = None, *, slots: int,
                          max_len: int, page_size: int, donate: bool = True,
                          lowered: Optional[LoweredPlan] = None
                          ) -> CompiledStep:
    """One-token decode for the continuous-batching engine
    (docs/continuous-batching.md): KV lives in page pools, gathered into
    dense per-slot views through a block table, decoded with per-request
    position vectors, and the written row scattered back.

    Token identity with the contiguous path is BY CONSTRUCTION: gathered
    rows below each slot's position are the exact pages the contiguous
    cache would hold, rows at-or-beyond are masked to the zeros a fresh
    contiguous cache holds — so the dense tree entering ``decode_fn`` is
    bitwise the contiguous cache state, and per-row batch invariance does
    the rest.  Inactive slots carry pos = 0 / all-trash block tables:
    their masked rows are all-zero, their scattered writes land on the
    shared trash page, their logits are ignored by the engine.
    """
    from repro.serving.pages import classify_cache_tree
    if lowered is None and (plan is None or mesh is None):
        raise ValueError("make_paged_serve_step needs either lowered= or "
                         "(plan, mesh)")
    low = lowered or lower_plan(model.cfg, None, plan, mesh)
    rules = low.shard_rules()
    if max_len % page_size:
        raise ValueError(f"page_size {page_size} must divide max_len "
                         f"{max_len}")
    npp = max_len // page_size

    kv_dtype = low.plan.kv_cache_dtype
    cache_dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    specs = classify_cache_tree(model.init_caches, slots, max_len,
                                cache_dtype)
    caches_sds = jax.eval_shape(
        lambda: model.init_caches(slots, max_len, cache_dtype))
    treedef = jax.tree.structure(caches_sds)
    # vector positions force cache_write's one-hot branch regardless of
    # the mode, so the base serve exec config is used as-is
    ec = low.serve_exec_cfg

    pos_ix = next((i for i, s in enumerate(specs) if s.is_pos), None)

    def _pos_vec(flat):
        # all pos leaves broadcast ONE per-request vector (engine
        # invariant); read it off the first one
        return flat[pos_ix].reshape(-1, slots)[0]

    def _gather(pool, block_table, pos_vec):
        lead, tail = pool.shape[0], pool.shape[3:]
        g = jnp.take(pool, block_table, axis=1)        # (lead,B,npp,ps,*t)
        g = g.reshape((lead, slots, max_len) + tail)
        # rows at-or-beyond each request's position read as the zeros a
        # fresh contiguous cache holds — page recycling and the trash
        # page never leak garbage into the dense view
        valid = jnp.arange(max_len)[None, :] < pos_vec[:, None]  # (B,S)
        valid = valid.reshape((1, slots, max_len) + (1,) * len(tail))
        return jnp.where(valid, g, jnp.zeros((), pool.dtype))

    def _scatter(pool, dense_new, block_table, pos_vec):
        # the decode step wrote exactly row pos_vec[b] of slot b; copy it
        # into the owning page (inactive/overflowing slots hit the trash
        # page via the block-table fill and the page-index clamp)
        lead, tail = pool.shape[0], pool.shape[3:]
        row = jnp.clip(pos_vec, 0, max_len - 1)                    # (B,)
        idx = row.reshape((1, slots, 1) + (1,) * len(tail))
        rows = jnp.take_along_axis(dense_new, idx, axis=2)
        rows = jnp.squeeze(rows, axis=2)                  # (lead,B,*tail)
        page = jnp.minimum(row // page_size, npp - 1)
        tgt = (block_table[jnp.arange(slots), page] * page_size
               + row % page_size)                                  # (B,)
        flat = pool.reshape((lead, pool.shape[1] * page_size) + tail)
        return flat.at[:, tgt].set(rows).reshape(pool.shape)

    def step(params, tokens, state, block_table):
        with use_rules(rules):
            flat = jax.tree.leaves(state)
            pos_vec = _pos_vec(flat) if pos_ix is not None else None
            dense = [
                _gather(leaf, block_table, pos_vec) if spec.paged else leaf
                for leaf, spec in zip(flat, specs)]
            caches = jax.tree.unflatten(treedef, dense)
            logits, new_caches = model.decode_fn(params, tokens, caches, ec)
            new_flat = jax.tree.leaves(new_caches)
            out = [
                _scatter(leaf, new, block_table, pos_vec) if spec.paged
                else new
                for leaf, new, spec in zip(flat, new_flat, specs)]
            return logits, jax.tree.unflatten(treedef, out)

    jit_fn = jax.jit(step, donate_argnums=(2,) if donate else ())
    return CompiledStep(fn=jit_fn, state_shardings=None,
                        batch_shardings=None, exec_cfg=ec)
