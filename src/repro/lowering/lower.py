"""The Plan -> LoweredPlan pass.

Lowering is split in two strictly separated halves:

* **lower_plan** computes pure *metadata*: per-stage mesh-axis mapping,
  PartitionSpec tables for params / optimizer state / gradients, host
  offload split points, ExecConfigs (remat/offload segmentation, kernel
  and attention implementation selection), and — for S > 1 — the pipeline
  stage-block tables (stacked 'stage'-dim specs + the shard_map manual
  specs).  This half never touches devices, so it runs identically on
  concrete meshes and on ``jax.sharding.AbstractMesh`` shells (the
  dryrun / analysis path).

* **LoweredPlan methods** materialize that metadata into NamedShardings
  (including ``pinned_host`` memory kinds for offloaded slices) on demand —
  only execution paths pay for it, and only they need real devices.

The spec *functions* (param_spec / grad_spec / opt_spec / cache_specs /
batch_specs) stay in ``repro.parallel.sharding``; this module is their
single runtime caller.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.plan import Plan, StageConfig
from repro.models.common import Axes, ExecConfig, ShardRules
from repro.parallel import sharding as SH


def plan_mesh_axes(mesh, tp_size: int) -> SH.MeshAxes:
    """Plan-aware mesh-axis mapping: a tp=1 plan folds the 'model' axis
    into DP/FSDP (the production mesh shape is fixed; which axes mean what
    is the plan's decision — e.g. indivisible-head archs want tp=1 and
    pure-FSDP over all 256 chips)."""
    ma = SH.MeshAxes.from_mesh(mesh)
    if tp_size == 1 and ma.tp is not None:
        dp = ma.dp + (ma.tp,)
        return SH.MeshAxes(dp=dp, tp=None, fsdp=dp)
    return ma


def stage_exec_config(plan: Plan, stage: StageConfig) -> ExecConfig:
    """CKPT_i/AO_i -> remat segmentation + kernel/attention selection."""
    ck = min(stage.ckpt_layers, stage.layers)
    kc = plan.kernel
    return ExecConfig(
        ckpt_layers=ck,
        offload_layers=int(round(stage.ao * ck)),
        remat_policy=plan.remat_policy,
        attn_impl=plan.attn_impl,
        use_pallas=plan.use_pallas,
        sequence_parallel=plan.sequence_parallel,
        attn_q_block=kc.attn_q_block,
        attn_kv_block=kc.attn_kv_block,
        rmsnorm_block=kc.rmsnorm_block,
        ssd_chunk=kc.ssd_chunk,
    )


@dataclass(frozen=True)
class LoweredStage:
    """Everything one pipeline stage means, as pure metadata."""
    index: int
    stage: StageConfig
    mesh_axes: SH.MeshAxes
    exec_cfg: ExecConfig                  # train-mode segmentation
    ep_ok: bool
    param_specs: Dict[str, P]             # bf16 weights
    grad_specs: Dict[str, P]              # f32 grad accumulator
    opt_specs: Dict[str, P]               # f32 master / mu / nu
    master_split: Dict[str, int]          # WO_i: leading host slices
    opt_split: Dict[str, int]             # OO_i: leading host slices
    has_embed: bool = True
    has_head: bool = True
    # live microbatches at this stage's memory peak (1F1B: S - i)
    inflight: int = 1


@dataclass(frozen=True)
class LoweredPlan:
    """One plan, fully interpreted against one mesh.

    ``stages`` carry the per-stage metadata; the methods materialize
    NamedShardings (execution) or walk the spec tables (analysis).
    Pipeline-specific tables (``pipeline_*``) exist when S > 1.
    """
    cfg: ArchConfig
    shape: Optional[ShapeConfig]
    plan: Plan
    mesh: Any
    params_sds: Dict[str, Any]
    axes_table: Axes
    stages: Tuple[LoweredStage, ...]
    # S > 1: stacked-layer dim 0 -> 'stage' (sharding-as-stage-assignment)
    pipeline_param_specs: Optional[Dict[str, P]] = None
    # shard_map in_specs: mention ONLY the manual 'stage' axis
    pipeline_manual_specs: Optional[Dict[str, P]] = None

    # -- exec configs ---------------------------------------------------------

    @property
    def plan_exec_cfg(self) -> ExecConfig:
        """Plan-level knobs only (no per-stage remat clamp) — the pipeline
        embed/unembed path and other stage-agnostic compute."""
        kc = self.plan.kernel
        return ExecConfig(remat_policy=self.plan.remat_policy,
                          attn_impl=self.plan.attn_impl,
                          use_pallas=self.plan.use_pallas,
                          sequence_parallel=self.plan.sequence_parallel,
                          attn_q_block=kc.attn_q_block,
                          attn_kv_block=kc.attn_kv_block,
                          rmsnorm_block=kc.rmsnorm_block,
                          ssd_chunk=kc.ssd_chunk)

    @property
    def serve_exec_cfg(self) -> ExecConfig:
        """Inference never remats/offloads activations."""
        return self.stages[0].exec_cfg.replace(
            remat_policy="none", ckpt_layers=0, offload_layers=0)

    # -- spec-table materialization (single-stage SPMD) -----------------------

    def shard_rules(self, i: int = 0) -> ShardRules:
        return SH.make_shard_rules(self.mesh, self.stages[i].mesh_axes,
                                   self.plan.sequence_parallel)

    def param_shardings(self, i: int = 0) -> Dict[str, Any]:
        from jax.sharding import NamedSharding
        return {n: NamedSharding(self.mesh, sp)
                for n, sp in self.stages[i].param_specs.items()}

    def grad_shardings(self, i: int = 0) -> Dict[str, Any]:
        from jax.sharding import NamedSharding
        return {n: NamedSharding(self.mesh, sp)
                for n, sp in self.stages[i].grad_specs.items()}

    def state_shardings(self, i: int = 0) -> Dict[str, Any]:
        """NamedShardings mirroring the optimizer-state pytree: params by
        param_specs, master/mu/nu by opt_specs, WO/OO-split leaves as
        {"host", "dev"} pairs with the host part on ``pinned_host``."""
        st = self.stages[i]
        return self._opt_tree(st.param_specs, st.opt_specs,
                              st.master_split, st.opt_split)

    def _opt_tree(self, pspecs, ospecs, master_split, opt_split):
        from jax.sharding import NamedSharding

        def entry(split):
            out = {}
            for n, spec in ospecs.items():
                if split.get(n, 0):
                    host = NamedSharding(self.mesh, spec,
                                         memory_kind=compat.host_memory_kind())
                    out[n] = {"host": host,
                              "dev": NamedSharding(self.mesh, spec)}
                else:
                    out[n] = NamedSharding(self.mesh, spec)
            return out

        return {
            "step": NamedSharding(self.mesh, P()),
            "params": {n: NamedSharding(self.mesh, sp)
                       for n, sp in pspecs.items()},
            "master": entry(master_split),
            "mu": entry(opt_split),
            "nu": entry(opt_split),
        }

    # -- batch / cache (data-entry) shardings ---------------------------------

    def batch_shardings(self, batch, i: int = 0):
        return SH.batch_specs(batch, self.mesh, self.stages[i].mesh_axes)

    def cache_shardings(self, caches_sds, batch: int, i: int = 0
                        ) -> Tuple[Any, str]:
        """(cache NamedSharding pytree, cache-update mode) for serving."""
        ma = self.stages[i].mesh_axes
        sh = SH.cache_specs(caches_sds, self.mesh, ma, batch, lead_dims=1)
        return sh, SH.cache_update_mode(sh, ma)

    # -- pipeline materialization (S > 1) -------------------------------------

    def pipeline_param_shardings(self) -> Dict[str, Any]:
        from jax.sharding import NamedSharding
        assert self.pipeline_param_specs is not None, "single-stage plan"
        return {n: NamedSharding(self.mesh, sp)
                for n, sp in self.pipeline_param_specs.items()}

    def pipeline_state_shardings(self) -> Dict[str, Any]:
        """Optimizer-state shardings for the pipeline step: every entry
        follows the stacked param sharding (the 'stage' dim partitions
        optimizer state exactly like weights), with the stage-0 WO/OO
        ratios selecting host splits."""
        st0 = self.stages[0]
        specs = self.pipeline_param_specs
        assert specs is not None, "single-stage plan"
        return self._opt_tree(specs, specs, st0.master_split, st0.opt_split)

    # -- memory ---------------------------------------------------------------

    def memory_report(self, **kw):
        from repro.lowering.memory import memory_report
        return memory_report(self, **kw)

    def state_layout_terms(self, i: int = 0) -> Dict[str, float]:
        """Per-device state bytes of stage ``i`` by term — the shared
        state-layout derivation (`repro.lowering.state_layout`) evaluated
        concretely against this lowering's actual mesh degrees; the same
        derivation the tuner's cost model evaluates symbolically."""
        from repro.lowering.memory import stage_layout_terms
        return stage_layout_terms(self, i)


def check_plan_mesh(plan: Plan, mesh) -> None:
    """Reject lowering a plan onto a mesh whose axis sizes disagree with
    the plan's parallel degrees.

    The spec tables shard over the REAL mesh axes, so a mismatched pair
    silently produces a layout for different dp/tp than the plan (and
    its cost/memory predictions) assumed — the dryrun ``--view`` /
    ``--plan-json`` hole.  The intentional tp=1 fold (``plan_mesh_axes``
    folds 'model' into DP) stays legal: the folded dp is compared.
    """
    S = plan.num_stages
    has_stage = "stage" in getattr(mesh, "shape", {})
    if S > 1:
        if not has_stage:
            raise ValueError(
                f"plan/mesh mismatch: plan has {S} pipeline stages but the "
                f"mesh {dict(mesh.shape)} has no 'stage' axis")
        if mesh.shape["stage"] != S:
            raise ValueError(
                f"plan/mesh mismatch: plan has {S} pipeline stages but the "
                f"mesh 'stage' axis has size {mesh.shape['stage']}")
    elif has_stage and mesh.shape["stage"] != 1:
        raise ValueError(
            f"plan/mesh mismatch: single-stage plan on a mesh with a "
            f"'stage' axis of size {mesh.shape['stage']}")
    for i, st in enumerate(plan.stages):
        ma = (SH.MeshAxes.from_mesh(mesh) if S > 1
              else plan_mesh_axes(mesh, st.tp))
        dp_size = SH.axis_size(mesh, ma.dp)
        tp_size = SH.axis_size(mesh, ma.tp)
        if (dp_size, tp_size) != (st.dp, st.tp):
            raise ValueError(
                f"plan/mesh mismatch at stage {i}: plan wants (dp, tp) = "
                f"({st.dp}, {st.tp}) but mesh {dict(mesh.shape)} provides "
                f"(dp, tp) = ({dp_size}, {tp_size}); reshape the mesh view "
                f"to match the plan (or retune the plan for this mesh)")


def _split_table(params_sds, axes_table: Axes, ratio: float) -> Dict[str, int]:
    # lazy: repro.training re-exports its step builders (which import this
    # package) from its __init__, so a module-level import would be circular
    from repro.training.optimizer import split_k
    out = {}
    for name, sds in params_sds.items():
        k = split_k(name, sds.shape, axes_table, ratio)
        if k:
            out[name] = k
    return out


def lower_plan(cfg: ArchConfig, shape: Optional[ShapeConfig], plan: Plan,
               mesh) -> LoweredPlan:
    """THE plan-interpretation entry point (see module docstring).

    ``shape`` is the workload the plan was tuned for; it is carried for
    ``memory_report`` and may be None for pure-execution callers that
    never ask for one.  ``mesh`` may be a concrete mesh (execution) or an
    ``jax.sharding.AbstractMesh`` shell (analysis).  Raises ValueError
    when the mesh axis sizes disagree with the plan's parallel degrees
    (``check_plan_mesh``).
    """
    from repro.models.zoo import abstract_params

    check_plan_mesh(plan, mesh)
    params_sds, axes_table = abstract_params(cfg)
    S = plan.num_stages
    pipeline = S > 1

    stages = []
    for i, st in enumerate(plan.stages):
        # pipeline stages live in one SPMD program whose 'data'/'model'
        # axes are fixed by the mesh; single-stage plans may fold a tp=1
        # 'model' axis into DP (plan_mesh_axes)
        ma = (SH.MeshAxes.from_mesh(mesh) if pipeline
              else plan_mesh_axes(mesh, st.tp))
        tp_size = SH.axis_size(mesh, ma.tp)
        ep_ok = cfg.num_experts > 0 and \
            cfg.num_experts % max(1, tp_size) == 0
        pspecs, gspecs, ospecs = {}, {}, {}
        for name, sds in params_sds.items():
            axes = axes_table[name]
            pspecs[name] = SH.param_spec(name, sds.shape, axes, mesh, ma,
                                         zero3=st.zero >= 3, ep_ok=ep_ok)
            gspecs[name] = SH.grad_spec(name, sds.shape, axes, mesh, ma,
                                        zero=st.zero, ep_ok=ep_ok)
            ospecs[name] = SH.opt_spec(name, sds.shape, axes, mesh, ma,
                                       zero=st.zero, ep_ok=ep_ok)
        stages.append(LoweredStage(
            index=i, stage=st, mesh_axes=ma,
            exec_cfg=stage_exec_config(plan, st),
            ep_ok=ep_ok, param_specs=pspecs, grad_specs=gspecs,
            opt_specs=ospecs,
            master_split=_split_table(params_sds, axes_table, st.wo),
            opt_split=_split_table(params_sds, axes_table, st.oo),
            has_embed=(i == 0), has_head=(i == S - 1),
            inflight=max(1, S - i),
        ))

    pipe_specs = manual_specs = None
    if pipeline:
        # stage-block assignment as sharding: stacked-layer dim 0 ->
        # 'stage', remaining dims via the stage-0 TP/ZeRO rules (dp/tp/
        # ZeRO must be uniform across stages inside one SPMD program)
        st0 = stages[0]
        pipe_specs, manual_specs = {}, {}
        for name, sds in params_sds.items():
            axes = axes_table[name]
            if axes and axes[0] == "layers":
                inner = SH.param_spec(name, sds.shape[1:], axes[1:], mesh,
                                      st0.mesh_axes,
                                      zero3=st0.stage.zero >= 3,
                                      ep_ok=st0.ep_ok)
                pipe_specs[name] = P("stage", *inner)
                manual_specs[name] = P("stage")
            else:
                pipe_specs[name] = st0.param_specs[name]
                manual_specs[name] = P()

    return LoweredPlan(cfg=cfg, shape=shape, plan=plan, mesh=mesh,
                       params_sds=params_sds, axes_table=axes_table,
                       stages=tuple(stages),
                       pipeline_param_specs=pipe_specs,
                       pipeline_manual_specs=manual_specs)
