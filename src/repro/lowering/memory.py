"""Per-device memory accounting of a lowered plan.

``memory_report(lowered)`` charges each train stage's model state by
evaluating the shared state-layout module
(:mod:`repro.lowering.state_layout`) **concretely** — the same per-group
shard counts, replication sets, and integer WO/OO host splits the
symbolic cost model evaluates over the tuner's knob symbols.  Activation
/ transient / logits terms reuse the cost model's analytic per-arch
coefficients (``arch_stats``) with the lowering's integer remat/offload
segmentation.  Since PR 5 the predictor and the report are two
evaluations of ONE derivation, so they agree bitwise wherever the plan
and the mesh agree (and ``MEMORY_REL_TOL`` is a tight guard, not an
apology for structural divergence).

``_state_walk`` — the exact walk over the lowered PartitionSpec tables —
is retained as the independent oracle: ``stage_state_bytes`` (dryrun)
uses it, and tests assert the layout evaluation reproduces it, which
pins the layout module to what ``param_spec``/``opt_spec`` actually
emit.

``memory_consistency`` quantifies the remaining predicted-vs-lowered gap
for one concrete (cfg, shape, plan), with a per-term breakdown (state /
act / transient / logits) so a future regression is attributable; the
golden-plan configs must agree within ``MEMORY_REL_TOL`` (asserted in
tests/test_lowering.py and, per config, by
``benchmarks/tuning_time.py --json``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, TYPE_CHECKING

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.hardware import V5E, HardwareSpec
from repro.lowering.state_layout import (concrete_state_terms, param_class
                                         as _param_class)
from repro.parallel import sharding as SH

if TYPE_CHECKING:                                    # pragma: no cover
    from repro.lowering.lower import LoweredPlan, LoweredStage

# Tolerance of the predicted-vs-lowered cross-check.  With the shared
# state-layout derivation (spec-exact shard counts incl. indivisible-dim
# replication, integer WO/OO/AO splits) the two sides agree bitwise on
# matched plan/mesh pairs — granite-3-8b's indivisible vocab at tp=8,
# formerly a 0.207 rel error, is now exact, and the serve-side cache
# layout (``lowering/cache_layout.py``) extends the bitwise contract to
# decode/prefill shapes.  The ``runtime_reserved`` constant — once the
# stated reason for 3% headroom — is read from the same ``CostParams``
# field by both sides AND cross-checked against real compiled-executable
# bytes by ``tools/calibrate_reserved.py``, so the guard is now 1%:
# pure drift detection, not an apology for any known divergence.
MEMORY_REL_TOL = 0.01


def _nshards(mesh, spec) -> int:
    """Device count a PartitionSpec divides a tensor over."""
    k = 1
    for ax in spec:
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            k *= mesh.shape[a]
    return k


@dataclass(frozen=True)
class StageMemory:
    """Per-device bytes of one lowered stage (train kind unless noted)."""
    index: int
    weight_bytes: float = 0.0        # bf16 weights
    grad_bytes: float = 0.0          # f32 grad accumulator
    master_bytes: float = 0.0        # f32 master weights (device part)
    opt_bytes: float = 0.0           # f32 mu+nu (device part)
    host_state_bytes: float = 0.0    # WO/OO slices living in host memory
    act_bytes: float = 0.0           # saved activations at peak
    host_act_bytes: float = 0.0      # AO-offloaded activation bytes
    cache_bytes: float = 0.0         # KV/state caches (serving)
    transient_bytes: float = 0.0     # working set + recompute scratch
    logits_bytes: float = 0.0
    overhead_bytes: float = 0.0      # compiler's share beyond act+trans+logits
    reserved_bytes: float = 0.0      # XLA runtime + fragmentation

    @property
    def state_bytes(self) -> float:
        return (self.weight_bytes + self.grad_bytes + self.master_bytes
                + self.opt_bytes)

    @property
    def device_bytes(self) -> float:
        return (self.state_bytes + self.act_bytes + self.cache_bytes
                + self.transient_bytes + self.logits_bytes
                + self.overhead_bytes + self.reserved_bytes)


@dataclass(frozen=True)
class MemoryReport:
    kind: str                        # train | prefill | decode
    stages: tuple
    budget_bytes: float

    @property
    def peak_bytes(self) -> float:
        return max(s.device_bytes for s in self.stages)

    @property
    def fits(self) -> bool:
        return self.peak_bytes <= self.budget_bytes

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kind": self.kind,
            "peak_bytes": self.peak_bytes,
            "budget_bytes": self.budget_bytes,
            "fits": self.fits,
            "per_stage": [{
                "stage": s.index,
                "state_bytes": s.state_bytes,
                "host_state_bytes": s.host_state_bytes,
                "act_bytes": s.act_bytes,
                "host_act_bytes": s.host_act_bytes,
                "cache_bytes": s.cache_bytes,
                "device_bytes": s.device_bytes,
            } for s in self.stages],
        }


def _state_walk(lowered: "LoweredPlan", st: "LoweredStage",
                layer_frac: float) -> Dict[str, float]:
    """Spec-exact per-device state bytes of one stage, walked from the
    ACTUAL PartitionSpec tables — the oracle the state-layout module is
    tested against (tests/test_state_layout.py).

    Stacked params contribute their ``layer_frac`` share (this stage's
    layers / total); shared-block params replicate to every stage;
    embed/head params follow the cost model's attribution (first and last
    stage).  WO/OO splits move leading stacked slices to host.
    """
    mesh = lowered.mesh
    out = dict(weight=0.0, grad=0.0, master=0.0, opt=0.0, host=0.0)
    for name, sds in lowered.params_sds.items():
        axes = lowered.axes_table[name]
        cls = _param_class(name, axes)
        if cls == "stacked":
            frac = layer_frac
        elif cls == "shared":
            frac = 1.0
        else:
            frac = 1.0 if (st.has_embed or st.has_head) else 0.0
        if frac == 0.0:
            continue
        n = math.prod(sds.shape) * frac
        lead = sds.shape[0] if sds.shape else 1
        k_m = st.master_split.get(name, 0)
        k_o = st.opt_split.get(name, 0)
        dev_m = (lead - k_m) / lead if k_m else 1.0
        dev_o = (lead - k_o) / lead if k_o else 1.0
        w = 2.0 * n / _nshards(mesh, st.param_specs[name])
        g = 4.0 * n / _nshards(mesh, st.grad_specs[name])
        o_sh = _nshards(mesh, st.opt_specs[name])
        out["weight"] += w
        out["grad"] += g
        out["master"] += 4.0 * n * dev_m / o_sh
        out["opt"] += 8.0 * n * dev_o / o_sh
        out["host"] += (4.0 * n * (1.0 - dev_m)
                        + 8.0 * n * (1.0 - dev_o)) / o_sh
    return out


def stage_state_bytes(lowered: "LoweredPlan", i: int = 0) -> float:
    """Device-resident model-state bytes (weights + grad accumulator +
    master + mu/nu) of one lowered stage — the exact spec walk, counting
    replicated indivisible dims at full size."""
    st = lowered.stages[i]
    frac = st.stage.layers / lowered.plan.total_layers
    s = _state_walk(lowered, st, frac)
    return s["weight"] + s["grad"] + s["master"] + s["opt"]


def stage_layout_terms(lowered: "LoweredPlan", i: int = 0
                       ) -> Dict[str, float]:
    """The shared state layout evaluated concretely for one lowered
    stage: tp/fsdp degrees come from the stage's ACTUAL MeshAxes (so
    folded tp=1 meshes and production views count the real mesh)."""
    st = lowered.stages[i]
    sc = st.stage
    return concrete_state_terms(
        lowered.cfg,
        tp_size=SH.axis_size(lowered.mesh, st.mesh_axes.tp),
        fsdp_size=SH.axis_size(lowered.mesh, st.mesh_axes.fsdp),
        zero=sc.zero, wo=sc.wo, oo=sc.oo, layers=sc.layers,
        total_layers=lowered.plan.total_layers,
        has_embed=st.has_embed, has_head=st.has_head)


def memory_report(lowered: "LoweredPlan", *, hw: HardwareSpec = V5E,
                  cp=None) -> MemoryReport:
    """Actual per-device bytes of the lowered plan (module docstring):
    state via the shared layout, activations/transients via the cost
    model's analytic coefficients + the ExecConfig's integer
    segmentation."""
    from repro.core.costmodel import CostParams, arch_stats
    cp = cp or CostParams()
    shape = lowered.shape
    if shape is None:
        raise ValueError("memory_report needs the workload shape; pass it "
                         "to lower_plan")
    cfg, plan = lowered.cfg, lowered.plan
    stt = arch_stats(cfg)
    budget = hw.hbm_bytes * cp.mem_headroom

    if shape.kind != "train":
        return _serve_report(lowered, stt, shape, budget, cp)

    stages: List[StageMemory] = []
    for st in lowered.stages:
        sc, ec = st.stage, st.exec_cfg
        state = stage_layout_terms(lowered, st.index)
        tok = sc.micro_batch * shape.seq_len
        sp_div = sc.tp if plan.sequence_parallel else 1
        act_full_l = 2.0 * stt.act_coef_full * stt.d_model * tok / sp_div
        act_ckpt_l = 2.0 * stt.act_coef_ckpt * stt.d_model * tok / sp_div
        ck, off = ec.ckpt_layers, ec.offload_layers
        act = st.inflight * ((ck - off) * act_ckpt_l
                             + (sc.layers - ck) * act_full_l)
        act_host = st.inflight * off * act_ckpt_l
        # transient working set, mirroring the symbolic model: one layer's
        # full intermediates during (re)compute, gathered ZeRO-3 params
        # for ~2 layers, bwd boundary grads, and the bwd recompute scratch
        trans = 2.0 * act_full_l + 2.0 * act_ckpt_l * st.inflight \
            + act_full_l
        if sc.zero >= 3:
            trans += 2.0 * (2.0 * stt.n_layer / sc.tp)
        logits = (2.0 * sc.micro_batch * min(512, shape.seq_len)
                  * stt.vocab * 4.0 / sc.tp) if st.has_head else 0.0
        stages.append(StageMemory(
            index=st.index, weight_bytes=state["weight"],
            grad_bytes=state["grad"], master_bytes=state["master"],
            opt_bytes=state["opt"], host_state_bytes=state["host"],
            act_bytes=act, host_act_bytes=act_host,
            transient_bytes=trans, logits_bytes=logits,
            overhead_bytes=cp.act_overhead * (act + trans + logits),
            reserved_bytes=cp.runtime_reserved))
    return MemoryReport(kind="train", stages=tuple(stages),
                        budget_bytes=budget)


def stage_cache_bytes(lowered: "LoweredPlan", shape: ShapeConfig) -> float:
    """Per-device decode-cache bytes, walked from the ACTUAL cache
    PartitionSpec tables — the independent oracle the shared cache
    layout (``lowering/cache_layout.py``) is tested against, exactly as
    ``_state_walk`` pins the state layout."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    st = lowered.stages[0]
    mesh = lowered.mesh
    model = build_model(lowered.cfg)
    cdt = (jnp.int8 if lowered.plan.kv_cache_dtype == "int8"
           else jnp.bfloat16)
    caches = jax.eval_shape(
        lambda: model.init_caches(shape.global_batch, shape.seq_len, cdt))
    specs = SH.cache_specs(caches, mesh, st.mesh_axes, shape.global_batch)
    cache = 0.0
    for sds, sh in zip(jax.tree.leaves(caches), jax.tree.leaves(
            specs, is_leaf=lambda x: hasattr(x, "spec"))):
        n = math.prod(sds.shape)
        cache += n * sds.dtype.itemsize / _nshards(mesh, sh.spec)
    return cache


def _serve_report(lowered: "LoweredPlan", stt, shape: ShapeConfig,
                  budget: float, cp) -> MemoryReport:
    """Serving: params-per-chip via the SHARED state-layout derivation
    and cache-per-chip via the SHARED cache layout (the same two
    evaluations the serve cost model runs over its Expr tapes — one
    derivation per term, not a private spec-table walk), plus the
    transient/reserved envelope from ``CostParams``."""
    from repro.lowering.cache_layout import (concrete_cache_bytes,
                                             concrete_paged_cache_bytes,
                                             prefill_transient_bytes)
    st = lowered.stages[0]
    sc = st.stage
    mesh = lowered.mesh
    weight = stage_layout_terms(lowered, 0)["weight"]
    cache = 0.0
    if shape.kind == "decode":
        page_size = int(getattr(lowered.plan, "page_size", 0))
        if page_size > 0:
            # paged serve plan: the continuous-batching engine's pool
            # layout (page pools + trash page + widened pos + block
            # table), same two-evaluation contract as the contiguous path
            cache = concrete_paged_cache_bytes(
                lowered.cfg, shape.global_batch, shape.seq_len, page_size,
                lowered.plan.kv_cache_dtype,
                dp_size=SH.axis_size(mesh, st.mesh_axes.dp),
                tp_size=SH.axis_size(mesh, st.mesh_axes.tp))
        else:
            cache = concrete_cache_bytes(
                lowered.cfg, shape.global_batch, shape.seq_len,
                lowered.plan.kv_cache_dtype,
                dp_size=SH.axis_size(mesh, st.mesh_axes.dp),
                tp_size=SH.axis_size(mesh, st.mesh_axes.tp))
        trans = cp.serve_decode_transient
    else:   # prefill: a couple of layers' activations + logits headroom
        trans = prefill_transient_bytes(
            stt.act_coef_full, stt.d_model, float(shape.global_batch),
            float(shape.seq_len), float(max(1, sc.dp)),
            float(max(1, sc.tp)))
    stage = StageMemory(index=0, weight_bytes=weight, cache_bytes=cache,
                        transient_bytes=trans,
                        reserved_bytes=cp.runtime_reserved)
    return MemoryReport(kind=shape.kind, stages=(stage,),
                        budget_bytes=budget)


def memory_consistency(cfg: ArchConfig, shape: ShapeConfig, plan, *,
                       hw: HardwareSpec = V5E,
                       profile=None) -> Dict[str, Any]:
    """Predicted (symbolic estimate_plan) vs lowered (memory_report)
    per-device peak bytes for one concrete plan, on an abstract mesh
    shaped exactly like the plan.  This is the tuner->runtime consistency
    check: the cost model that *selected* the plan and the lowering that
    *executes* it must agree on what the plan costs.

    ``terms`` breaks the gap down per memory term at the lowered peak
    stage.  Per-term rel errors are normalized by the predicted TOTAL
    (how much of the budget that term's disagreement is worth), so tiny
    terms cannot blow the ratio up.  ``profile`` (a CalibrationProfile)
    layers its fitted constants over both sides."""
    from jax.sharding import AbstractMesh
    from repro.core.costmodel import estimate_plan
    from repro.lowering.lower import lower_plan

    est = estimate_plan(cfg, shape, plan, hw=hw, profile=profile)
    cp = profile.cost_params() if profile is not None else None
    st0 = plan.stages[0]
    if plan.num_stages > 1:
        mesh = AbstractMesh(
            (plan.num_stages, st0.dp, st0.tp), ("stage", "data", "model"))
    else:
        mesh = AbstractMesh((st0.dp, st0.tp), ("data", "model"))
    rep = lower_plan(cfg, shape, plan, mesh).memory_report(hw=hw, cp=cp)
    predicted = float(est["mem_peak_max"])
    lowered_b = float(rep.peak_bytes)
    rel = abs(lowered_b - predicted) / max(predicted, 1.0)

    peak_i = max(range(len(rep.stages)),
                 key=lambda i: rep.stages[i].device_bytes)
    ps = rep.stages[peak_i]
    pt = est["mem_terms_per_stage"][peak_i]
    lowered_terms = {"state": ps.state_bytes, "act": ps.act_bytes,
                     "transient": ps.transient_bytes,
                     "logits": ps.logits_bytes,
                     "overhead": ps.overhead_bytes,
                     "host_state": ps.host_state_bytes,
                     "host_act": ps.host_act_bytes}
    terms = {k: {"predicted": float(pt[k]), "lowered": float(v),
                 "rel_error": abs(v - pt[k]) / max(predicted, 1.0)}
             for k, v in lowered_terms.items()}
    return {
        "predicted_bytes": predicted,
        "lowered_bytes": lowered_b,
        "rel_error": rel,
        "within_tol": rel <= MEMORY_REL_TOL,
        "terms": terms,
        "peak_stage": peak_i,
        "predicted_per_stage": [float(x) for x in est["mem_per_stage"]],
        "lowered_per_stage": [s.device_bytes for s in rep.stages],
    }
