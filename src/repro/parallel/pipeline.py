"""Pipeline-parallel train step: shard_map over a 'stage' mesh axis with
jax.lax.ppermute microbatch handoff (GPipe fill/drain realized by AD).

TPU-native adaptation of Mist's pipeline executor (paper §5.1): instead of
per-rank torch programs with p2p sends, the stage axis is a mesh dimension.
The *stacked-layer* parameter layout (every backbone block's params carry a
leading L dim) makes stage partitioning a *sharding decision*: dim 0 of every
block param is sharded over 'stage', so each stage holds L/S layers, and XLA
SPMD continues to handle DP/TP/ZeRO *inside* each stage (the shard_map is
partial-manual: only 'stage' is manual, 'data'/'model' stay auto).

Heterogeneity notes (DESIGN.md §Arch-applicability):
 - per-stage CKPT_i is realized by a stage-indexed remat split
   (`jnp.where` over lax.axis_index) — heterogeneous recompute counts run
   in one SPMD program;
 - dp/tp/ZeRO must be uniform across stages in one SPMD program (XLA
   constraint); Mist plans tuned for execution set `uniform_shards=True`,
   while analysis-only plans may be fully heterogeneous;
 - the embed/unembed compute runs on every stage and is masked (SPMD
   uniformity); the waste is head_flops*(S-1) and is counted by the
   roofline analysis.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.plan import Plan
from repro.lowering import LoweredPlan, lower_plan
from repro.models import layers as L
from repro.models.common import Params, subtree, use_rules
from repro.models.zoo import Model
from repro.training import optimizer as OPT

PIPELINE_FAMILIES = ("dense", "moe", "ssm")   # uniform-stack decoders


def supports_pipeline(cfg: ArchConfig) -> bool:
    return cfg.family in PIPELINE_FAMILIES


# The per-param sharding tables (stacked-layer dim 0 -> 'stage', remaining
# dims via the single-stage TP/ZeRO rules) and the shard_map manual specs
# are produced by ``repro.lowering`` (`LoweredPlan.pipeline_*`); this
# module only realizes the stage programs.


# ---------------------------------------------------------------------------
# the pipelined loss
# ---------------------------------------------------------------------------


def _stage_block_fn(model: Model, cfg: ArchConfig, low: LoweredPlan):
    """(stage-local stacked params, x, stage_idx) -> x after L/S layers.

    Heterogeneous per-stage CKPT_i/AO_i are realized by `lax.switch` over
    the stage index: each branch is the same layer stack with a *different*
    remat/offload segmentation.  XLA lowers this to conditional HLO whose
    selected branch executes at runtime — each stage runs only its own
    segmentation, at the cost of S copies of the stage program in the HLO
    (compile-time, not run-time, overhead)."""
    from repro.models.decoder import apply_block
    from repro.models.common import segmented_layer_scan
    plan = low.plan

    def branch_fn(ls):
        n_local = ls.stage.layers
        ec = ls.exec_cfg   # the lowered CKPT_i/AO_i segmentation

        def run(stacked, x, aux0):
            def body(carry, lp):
                h, aux = carry
                nh, a, _ = apply_block(lp, h, cfg, ec)
                return (nh, aux + a)
            return segmented_layer_scan(body, (x, aux0), stacked, n_local,
                                        ec)
        return run

    # dedupe identical stage configs into shared branches
    keyed = [(min(s.ckpt_layers, s.layers), s.ao) for s in plan.stages]
    uniq = sorted(set(keyed))
    branch_of_stage = jnp.asarray([uniq.index(k) for k in keyed], jnp.int32)
    branches = [branch_fn(low.stages[keyed.index(k)]) for k in uniq]

    def block(stacked: Params, x: jax.Array, stage_idx: jax.Array,
              aux0: jax.Array):
        if len(branches) == 1:
            return branches[0](stacked, x, aux0)
        return jax.lax.switch(branch_of_stage[stage_idx], branches,
                              stacked, x, aux0)

    return block


def make_pipeline_loss(model: Model, plan: Plan, mesh: Mesh,
                       lowered: Optional[LoweredPlan] = None) -> Callable:
    """(params, batch) -> mean loss, running the GPipe loop inside a
    partial-manual shard_map over the 'stage' axis."""
    cfg = model.cfg
    assert supports_pipeline(cfg), f"pipeline unsupported for {cfg.family}"
    low = lowered or lower_plan(cfg, None, plan, mesh)
    S = plan.num_stages
    G = plan.grad_accum
    block = _stage_block_fn(model, cfg, low)
    rules = low.shard_rules()
    from repro.models.decoder import embed_tokens, unembed_matrix, chunked_xent

    ec = low.plan_exec_cfg   # stage-agnostic embed/unembed compute

    def pipelined(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        """Runs per-stage (manual over 'stage'; auto over data/model)."""
        # stage-replicated (non-stacked) params cross the shard_map boundary
        # in f32: their gradients are psum'ed over 'stage' by shard_map AD,
        # and the f32 reduction (a) is exact and (b) avoids an XLA:CPU
        # AllReducePromotion crash on bf16 scalars (TPU unaffected).
        params = {n: (p.astype(_orig_dtype[n])
                      if p.dtype != _orig_dtype[n] else p)
                  for n, p in params.items()}
        stage = jax.lax.axis_index("stage")
        stacked = subtree(params, "layers")
        tokens, labels = batch["tokens"], batch["labels"]   # (G, b, s)
        b, s = tokens.shape[1], tokens.shape[2]
        d = cfg.d_model

        def embed_mb(i):
            x = embed_tokens(params, tokens[i], cfg, ec)
            return x

        T = G + S - 1
        zero_x = jnp.zeros((b, s, d), ec.compute_dtype)

        def step(carry, t):
            x_in, loss_sum, aux_sum = carry
            # stage 0 ingests microbatch t (if in range)
            mb = jnp.clip(t, 0, G - 1)
            fresh = embed_mb(mb)
            x = jnp.where(stage == 0, fresh, x_in)
            active = (t - stage >= 0) & (t - stage < G)
            x, aux = block(stacked, x, stage, jnp.zeros((), jnp.float32))
            # last stage: loss of microbatch (t - S + 1)
            out_mb = jnp.clip(t - S + 1, 0, G - 1)
            h = L.norm(subtree(params, "final_norm"), x, cfg)
            lo = chunked_xent(h, unembed_matrix(params, cfg),
                              labels[out_mb])
            is_out = (stage == S - 1) & (t >= S - 1)
            loss_sum = loss_sum + jnp.where(is_out, lo, 0.0)
            aux_sum = aux_sum + jnp.where(active, aux, 0.0)
            # hand off to next stage
            x = jnp.where(active, x, jnp.zeros_like(x))
            x_next = jax.lax.ppermute(
                x, "stage", [(i, (i + 1) % S) for i in range(S)])
            return (x_next, loss_sum, aux_sum), None

        (x_last, loss_sum, aux_sum), _ = jax.lax.scan(
            step, (zero_x, jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.float32)), jnp.arange(T))
        # mean over microbatches; broadcast the last stage's loss to all
        loss = jax.lax.psum(loss_sum, "stage") / G
        from repro.models.decoder import AUX_COEF
        aux = jax.lax.psum(aux_sum, "stage") / jnp.maximum(G, 1)
        return loss + AUX_COEF * aux / cfg.num_layers

    params_sds, axes_table = low.params_sds, low.axes_table
    _orig_dtype = {n: sds.dtype for n, sds in params_sds.items()}
    _is_stacked = {n: bool(axes_table[n]) and axes_table[n][0] == "layers"
                   for n in params_sds}
    pspecs = low.pipeline_param_shardings()
    # partial-manual shard_map: specs mention ONLY the manual 'stage' axis;
    # DP/TP/ZeRO shardings over the auto axes ride through unchanged (set by
    # the outer jit in_shardings + with_sharding_constraint inside).
    manual_spec = dict(low.pipeline_manual_specs)
    in_specs = (manual_spec, {"tokens": P(), "labels": P()})
    manual = frozenset({"stage"})

    # check_vma=False: inner scans (chunked xent, layer scan) carry
    # stage-varying values from unvarying seeds; the loss output is made
    # replicated explicitly via the psum over 'stage'.
    smapped = jax.shard_map(pipelined, mesh=mesh, in_specs=in_specs,
                               out_specs=P(), axis_names=manual,
                               check_vma=False)

    def loss_fn(params, batch):
        with use_rules(rules):
            p32 = {n: (p.astype(jnp.float32) if not _is_stacked[n] else p)
                   for n, p in params.items()}
            return smapped(p32, batch)

    loss_fn.param_shardings = pspecs  # type: ignore[attr-defined]
    return loss_fn


# ---------------------------------------------------------------------------
# pipeline train step (loss + grads + AdamW), mirroring step.make_train_step
# ---------------------------------------------------------------------------


@dataclass
class PipelineStep:
    fn: Callable
    state_shardings: Any
    batch_shape: Tuple[int, ...]      # (G, b*dp, s) expected for tokens
    loss_fn: Callable


def make_pipeline_train_step(model: Model, plan: Plan, mesh: Mesh,
                             adam: OPT.AdamConfig = OPT.AdamConfig(),
                             donate: bool = True,
                             lowered: Optional[LoweredPlan] = None
                             ) -> PipelineStep:
    cfg = model.cfg
    S = plan.num_stages
    assert S > 1 and "stage" in mesh.axis_names
    st0 = plan.stages[0]
    low = lowered or lower_plan(cfg, None, plan, mesh)
    loss_fn = make_pipeline_loss(model, plan, mesh, lowered=low)

    # optimizer state mirrors the param shardings (master/mu/nu f32),
    # WO/OO splits included
    st_shardings = low.pipeline_state_shardings()

    def train_step(state, batch):
        params = state["params"]
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch))(params)
        grads = {n: g.astype(jnp.float32) for n, g in grads.items()}
        new_state, om = OPT.adam_update(state, grads, adam, st_shardings)
        return new_state, {"loss": loss, **om, "step": new_state["step"]}

    jit_fn = jax.jit(train_step, in_shardings=(st_shardings, None),
                     donate_argnums=(0,) if donate else ())
    b_local = st0.micro_batch * st0.dp
    return PipelineStep(fn=jit_fn, state_shardings=st_shardings,
                        batch_shape=(plan.grad_accum, b_local, 0),
                        loss_fn=loss_fn)
