"""Plan-lowering equivalence + the tuner->runtime memory cross-check.

The first half freezes the PRE-REFACTOR call-site derivations (what
training/step.py, parallel/pipeline.py, and launch/dryrun.py each
computed for themselves before `repro.lowering` existed) and asserts the
lowered tables are byte-identical to them, across the golden-plan configs
of every SPACES preset and both golden archs.  A drift here means the
refactor changed what a plan *means* — exactly the divergence the single
lowering layer exists to prevent.

The second half closes the loop with the symbolic layer: the cost model
that selected each feasible golden plan must agree with
``LoweredPlan.memory_report()`` within ``MEMORY_REL_TOL``.
"""
import json
import pathlib
import subprocess
import sys

import jax
import pytest

from repro import compat
from repro.configs.base import ShapeConfig, get_arch, list_archs
from repro.core import golden
from repro.core.plan import Plan, StageConfig, single_stage_plan
from repro.lowering import (MEMORY_REL_TOL, lower_plan, memory_consistency,
                            plan_mesh_axes)
from repro.models.zoo import abstract_params
from repro.parallel import sharding as SH

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

# ---------------------------------------------------------------------------
# frozen pre-refactor derivations (DO NOT "simplify" by calling the new code)
# ---------------------------------------------------------------------------


def frozen_mesh_axes_for_plan(mesh, tp_size):
    """training/step.py + launch/dryrun.py: SH.MeshAxes.for_plan."""
    ma = SH.MeshAxes.from_mesh(mesh)
    if tp_size == 1 and ma.tp is not None:
        dp = ma.dp + (ma.tp,)
        return SH.MeshAxes(dp=dp, tp=None, fsdp=dp)
    return ma


def frozen_stage_exec_config(plan, stage):
    """training/step.py: stage_exec_config."""
    from repro.models.common import ExecConfig
    lyr = stage.layers
    return ExecConfig(
        ckpt_layers=min(stage.ckpt_layers, lyr),
        offload_layers=int(round(stage.ao * min(stage.ckpt_layers, lyr))),
        remat_policy=plan.remat_policy,
        attn_impl=plan.attn_impl,
        use_pallas=plan.use_pallas,
        sequence_parallel=plan.sequence_parallel,
    )


def frozen_single_stage_tables(cfg, plan, mesh):
    """training/step.py make_train_step + training/optimizer.py
    state_shardings: the param/grad/opt PartitionSpec derivations."""
    stage = plan.stages[0]
    ma = frozen_mesh_axes_for_plan(mesh, stage.tp)
    params_sds, axes_table = abstract_params(cfg)
    ep_ok = cfg.num_experts > 0 and (
        cfg.num_experts % mesh.shape.get(ma.tp, 1) == 0 if ma.tp else False)
    pspecs = {n: SH.param_spec(n, s.shape, axes_table[n], mesh, ma,
                               zero3=stage.zero >= 3, ep_ok=ep_ok)
              for n, s in params_sds.items()}
    gspecs = {n: SH.grad_spec(n, s.shape, axes_table[n], mesh, ma,
                              zero=stage.zero, ep_ok=ep_ok)
              for n, s in params_sds.items()}
    ospecs = {n: SH.opt_spec(n, s.shape, axes_table[n], mesh, ma,
                             zero=stage.zero, ep_ok=ep_ok)
              for n, s in params_sds.items()}
    return ma, pspecs, gspecs, ospecs


def frozen_pipeline_specs(cfg, plan, mesh):
    """parallel/pipeline.py: stage_param_specs (spec level) + the
    shard_map manual specs."""
    from jax.sharding import PartitionSpec as P
    st0 = plan.stages[0]
    ma = SH.MeshAxes.from_mesh(mesh)
    params_sds, axes_table = abstract_params(cfg)
    ep_ok = cfg.num_experts > 0 and \
        cfg.num_experts % max(1, mesh.shape.get(ma.tp or "", 1)) == 0
    specs, manual = {}, {}
    for name, sds in params_sds.items():
        axes = axes_table[name]
        if axes and axes[0] == "layers":
            inner = SH.param_spec(name, sds.shape[1:], axes[1:], mesh, ma,
                                  zero3=st0.zero >= 3, ep_ok=ep_ok)
            specs[name] = P("stage", *inner)
            manual[name] = P("stage")
        else:
            specs[name] = SH.param_spec(name, sds.shape, axes, mesh, ma,
                                        zero3=st0.zero >= 3, ep_ok=ep_ok)
            manual[name] = P()
    return specs, manual


# ---------------------------------------------------------------------------
# one representative plan per SPACES preset (golden workload: seq 2048,
# global batch 16, 8 devices).  Feasible golden cells use the pinned plan
# from tests/golden/; infeasible cells get a hand-written plan drawn from
# that preset's knob grid so every preset still exercises the lowering.
# ---------------------------------------------------------------------------

_SPACE_FALLBACK = {
    "none": dict(zero=0, ckpt_layers=0),
    "megatron": dict(zero=1),                      # full ckpt
    "ckpt": dict(zero=1, ckpt_layers=16),
    "zero": dict(zero=3),                          # full ckpt
    "offload": dict(zero=1, ckpt_layers=16, oo=0.5, ao=0.25),
    "mist": dict(zero=2, ckpt_layers=8, oo=0.75, ao=0.5),
    "uniform": dict(zero=1, ckpt_layers=8, oo=0.25, ao=0.0),
    "serve": dict(zero=0, ckpt_layers=0),          # inference: no remat
}


def golden_plan_for(space, arch):
    path = golden.golden_path(space, arch)
    doc = json.loads(path.read_text())["doc"]
    if doc["plan"] is not None:
        return Plan.from_json(json.dumps(doc["plan"]))
    kw = dict(_SPACE_FALLBACK[space])
    cfg = get_arch(arch)
    ck = kw.pop("ckpt_layers", cfg.num_layers)
    return single_stage_plan(cfg.num_layers, dp=2, tp=4, micro_batch=2,
                             grad_accum=4, ckpt_layers=ck, **kw)


CASES = [(s, a) for s in golden.GOLDEN_SPACES for a in golden.GOLDEN_ARCHS]


@pytest.mark.parametrize("space,arch", CASES,
                         ids=[f"{s}-{a}" for s, a in CASES])
def test_lowering_matches_frozen_reference(space, arch):
    """Lowered mesh axes / exec configs / spec tables == the pre-refactor
    call-site derivations, byte for byte."""
    cfg = get_arch(arch)
    plan = golden_plan_for(space, arch)
    st = plan.stages[0]
    mesh = jax.sharding.AbstractMesh((st.dp, st.tp), ("data", "model"))
    low = lower_plan(cfg, None, plan, mesh)

    ma, pspecs, gspecs, ospecs = frozen_single_stage_tables(cfg, plan, mesh)
    ls = low.stages[0]
    assert ls.mesh_axes == ma
    assert plan_mesh_axes(mesh, st.tp) == ma
    assert ls.exec_cfg == frozen_stage_exec_config(plan, st)
    assert ls.param_specs == pspecs
    assert ls.grad_specs == gspecs
    assert ls.opt_specs == ospecs


def test_lowering_pipeline_tables_match_frozen_reference():
    """S=2 plan: the stacked-'stage' param specs and shard_map manual
    specs == parallel/pipeline.py's pre-refactor derivation."""
    cfg = get_arch("granite-3-8b")
    stages = tuple(StageConfig(layers=20, micro_batch=2, dp=2, tp=2,
                               zero=3, ckpt_layers=20 if i == 0 else 0,
                               wo=0.5, oo=0.25)
                   for i in range(2))
    plan = Plan(grad_accum=2, stages=stages)
    mesh = jax.sharding.AbstractMesh((2, 2, 2), ("stage", "data", "model"))
    low = lower_plan(cfg, None, plan, mesh)
    specs, manual = frozen_pipeline_specs(cfg, plan, mesh)
    assert low.pipeline_param_specs == specs
    assert low.pipeline_manual_specs == manual
    # pipeline stages never fold the model axis
    assert low.stages[0].mesh_axes == SH.MeshAxes.from_mesh(mesh)
    assert [s.inflight for s in low.stages] == [2, 1]


def test_lower_plan_rejects_mismatched_mesh():
    """The dryrun --view / --plan-json hole: a plan tuned for (dp, tp) =
    (4, 2) silently lowered onto a 2x4 view, sharding over axes the plan
    (and its memory/cost predictions) never assumed.  Now a ValueError
    naming both sides."""
    cfg = get_arch("granite-3-8b").reduced()
    plan = single_stage_plan(cfg.num_layers, dp=4, tp=2, micro_batch=2,
                             grad_accum=2, zero=1)
    with pytest.raises(ValueError, match=r"plan/mesh mismatch.*\(4, 2\)"):
        lower_plan(cfg, None, plan,
                   jax.sharding.AbstractMesh((2, 4), ("data", "model")))
    # the matching view lowers fine
    lower_plan(cfg, None, plan,
               jax.sharding.AbstractMesh((4, 2), ("data", "model")))


def test_lower_plan_tp1_fold_stays_legal():
    """A tp=1 plan on a mesh WITH a model axis is the intentional fold
    (plan_mesh_axes): dp spans data*model, not a mismatch."""
    cfg = get_arch("granite-3-8b").reduced()
    plan = single_stage_plan(cfg.num_layers, dp=8, tp=1, micro_batch=1,
                             grad_accum=2, zero=1)
    low = lower_plan(cfg, None, plan,
                     jax.sharding.AbstractMesh((4, 2), ("data", "model")))
    assert low.stages[0].mesh_axes.tp is None
    with pytest.raises(ValueError, match="plan/mesh mismatch"):
        lower_plan(cfg, None, plan,
                   jax.sharding.AbstractMesh((2, 2), ("data", "model")))


def test_lower_plan_rejects_stage_mismatch():
    """Pipeline plans need a 'stage' axis of exactly num_stages."""
    cfg = get_arch("granite-3-8b")
    stages = tuple(StageConfig(layers=20, micro_batch=2, dp=2, tp=2,
                               zero=1) for _ in range(2))
    plan = Plan(grad_accum=2, stages=stages)
    with pytest.raises(ValueError, match="no 'stage' axis"):
        lower_plan(cfg, None, plan,
                   jax.sharding.AbstractMesh((2, 2), ("data", "model")))
    with pytest.raises(ValueError, match="'stage' axis has size 4"):
        lower_plan(cfg, None, plan,
                   jax.sharding.AbstractMesh((4, 2, 2),
                                        ("stage", "data", "model")))


def test_state_shardings_tree_on_concrete_mesh():
    """Full optimizer-state NamedSharding tree (incl. WO/OO host/dev
    splits and memory kinds) == the frozen training/optimizer.py
    state_shardings construction, on a real 1-device mesh."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.training.optimizer import init_state, is_split

    cfg = get_arch("granite-3-8b")
    plan = single_stage_plan(40, dp=1, tp=1, micro_batch=2, grad_accum=2,
                             zero=1, ckpt_layers=20, wo=0.5, oo=0.25)
    stage = plan.stages[0]
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    params_sds, axes_table = abstract_params(cfg)
    ma = frozen_mesh_axes_for_plan(mesh, stage.tp)

    # frozen: training/optimizer.py state_shardings (pre-refactor)
    ep_ok = cfg.num_experts > 0 and (
        cfg.num_experts % mesh.shape.get(ma.tp, 1) == 0 if ma.tp else False)
    state = init_state(params_sds, axes_table, stage)
    want = {"step": NamedSharding(mesh, P())}
    want["params"] = {
        n: NamedSharding(mesh, SH.param_spec(
            n, s.shape, axes_table[n], mesh, ma, zero3=stage.zero >= 3,
            ep_ok=ep_ok))
        for n, s in state["params"].items()}
    hk = compat.host_memory_kind()
    for entry, ratio in (("master", stage.wo), ("mu", stage.oo),
                         ("nu", stage.oo)):
        e = {}
        for n, leaf in state[entry].items():
            spec = SH.opt_spec(n, state["params"][n].shape, axes_table[n],
                               mesh, ma, zero=stage.zero, ep_ok=ep_ok)
            if is_split(leaf):
                host = NamedSharding(mesh, spec, memory_kind=hk)
                e[n] = {"host": host, "dev": NamedSharding(mesh, spec)}
            else:
                e[n] = NamedSharding(mesh, spec)
        want[entry] = e

    got = lower_plan(cfg, None, plan, mesh).state_shardings()
    leaf = lambda x: isinstance(x, NamedSharding)          # noqa: E731
    assert jax.tree.structure(want, is_leaf=leaf) \
        == jax.tree.structure(got, is_leaf=leaf)
    for a, b in zip(jax.tree.leaves(want, is_leaf=leaf),
                    jax.tree.leaves(got, is_leaf=leaf)):
        assert a == b and a.memory_kind == b.memory_kind
    # the WO/OO ratios actually split stacked entries
    assert any(isinstance(v, dict) for v in got["master"].values())
    assert any(isinstance(v, dict) for v in got["mu"].values())


def test_serve_lowering_matches_spec_library():
    """Cache shardings + update mode == direct SH.cache_specs /
    cache_update_mode calls (the pre-refactor make_serve_step glue)."""
    import jax
    from repro.models.zoo import build_model

    cfg = get_arch("granite-3-8b").reduced()
    model = build_model(cfg)
    plan = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    low = lower_plan(cfg, None, plan, mesh)
    caches = jax.eval_shape(lambda: model.init_caches(8, 128))
    got_sh, got_mode = low.cache_shardings(caches, 8)
    ma = frozen_mesh_axes_for_plan(mesh, 1)
    want_sh = SH.cache_specs(caches, mesh, ma, 8, lead_dims=1)
    eq = jax.tree.map(lambda a, b: a == b, got_sh, want_sh,
                      is_leaf=lambda x: hasattr(x, "spec"))
    assert all(jax.tree.leaves(eq))
    assert got_mode == SH.cache_update_mode(want_sh, ma)
    ec = low.serve_exec_cfg
    assert ec.remat_policy == "none" and ec.ckpt_layers == 0 \
        and ec.offload_layers == 0


# ---------------------------------------------------------------------------
# the memory cross-check: symbolic predictions vs lowered bytes
# ---------------------------------------------------------------------------

_GOLDEN_SHAPE = ShapeConfig("golden", 2048, 16, "train")


def test_memory_rel_tol_is_tight():
    """The shared state/cache-layout derivations make predicted and
    lowered memory agree bitwise on matched plan/mesh pairs (train AND
    serve shapes), and the one estimated constant (`runtime_reserved`)
    is read from the same CostParams field by both sides and
    cross-checked against compiled-executable bytes by
    tools/calibrate_reserved.py — the tolerance is a pure 1% drift
    guard.  Loosening it again is a regression."""
    assert MEMORY_REL_TOL == 0.01


@pytest.mark.parametrize("space,arch", CASES,
                         ids=[f"{s}-{a}" for s, a in CASES])
def test_predicted_vs_lowered_memory(space, arch):
    """StageCostModel/estimate_plan memory predictions agree with
    LoweredPlan.memory_report() within MEMORY_REL_TOL for every golden
    cell (fixture plan where feasible, the preset representative
    otherwise) — including the per-term breakdown."""
    plan = golden_plan_for(space, arch)
    mc = memory_consistency(get_arch(arch), _GOLDEN_SHAPE, plan)
    assert mc["within_tol"], (
        f"predicted {mc['predicted_bytes'] / 2**30:.2f} GiB vs lowered "
        f"{mc['lowered_bytes'] / 2**30:.2f} GiB: rel error "
        f"{mc['rel_error']:.3f} > {MEMORY_REL_TOL}")
    for term in ("state", "act", "transient", "logits"):
        assert mc["terms"][term]["rel_error"] <= MEMORY_REL_TOL, \
            (term, mc["terms"][term])


# every zoo arch x every SPACES preset, on a preset-representative plan
# (the tuner only pins golden plans for 2 archs; the consistency contract
# must hold for any legal plan on any arch)
_ZOO_CASES = [(s, a) for s in golden.GOLDEN_SPACES for a in list_archs()]


@pytest.mark.parametrize("space,arch", _ZOO_CASES,
                         ids=[f"{s}-{a}" for s, a in _ZOO_CASES])
def test_predicted_vs_lowered_memory_zoo(space, arch):
    """memory_consistency holds at MEMORY_REL_TOL across the FULL arch
    zoo for each preset's representative plan — indivisible head/vocab
    dims, MoE expert grids, shared blocks, enc-dec stacks and all."""
    cfg = get_arch(arch)
    kw = dict(_SPACE_FALLBACK[space])
    ck = kw.pop("ckpt_layers", cfg.num_layers)
    try:
        plan = single_stage_plan(cfg.num_layers, dp=2, tp=4, micro_batch=2,
                                 grad_accum=4,
                                 ckpt_layers=min(ck, cfg.num_layers), **kw)
    except (ValueError, AssertionError) as e:        # pragma: no cover
        pytest.skip(f"infeasible cell for {arch}: {e}")
    mc = memory_consistency(cfg, _GOLDEN_SHAPE, plan)
    assert mc["rel_error"] <= MEMORY_REL_TOL, (
        f"rel error {mc['rel_error']:.3f} > {MEMORY_REL_TOL}: "
        f"{mc['terms']}")


def test_memory_report_offload_moves_bytes_to_host():
    """WO/OO/AO ratios move state/activation bytes off-device; device
    total shrinks accordingly."""
    cfg = get_arch("granite-3-8b")
    mesh = jax.sharding.AbstractMesh((1, 8), ("data", "model"))

    def rep(**kw):
        plan = single_stage_plan(40, dp=1, tp=8, micro_batch=4,
                                 grad_accum=4, zero=0, ckpt_layers=40, **kw)
        return lower_plan(cfg, _GOLDEN_SHAPE, plan, mesh).memory_report()

    base = rep()
    off = rep(wo=0.5, oo=0.5, ao=0.5)
    assert off.stages[0].host_state_bytes > 0
    assert off.stages[0].host_act_bytes > 0
    assert off.peak_bytes < base.peak_bytes
    d = base.to_dict()
    assert d["per_stage"][0]["device_bytes"] == base.peak_bytes


def test_dryrun_analytic_helpers_in_process():
    """The dryrun analytics are pure lowering metadata now: they run on
    abstract meshes with no devices.  (jax is touched first so dryrun's
    import-time XLA_FLAGS poke cannot affect this process's already-
    initialized backend.)"""
    import jax
    jax.devices()
    from repro.launch import dryrun as DR

    cfg = get_arch("granite-3-8b")
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    b1 = DR.state_bytes_per_device(cfg, mesh, 1)
    b2 = DR.state_bytes_per_device(cfg, mesh, 2)
    b3 = DR.state_bytes_per_device(cfg, mesh, 3)
    assert b1 > b2 > b3 > 0      # each ZeRO level shards more state
    assert DR.min_fitting_zero(cfg, mesh) in (1, 2, 3)

    # train cells report both sides of the lowering contract
    plan = single_stage_plan(cfg.num_layers, dp=16, tp=16, micro_batch=1,
                             grad_accum=16, zero=1)
    low = lower_plan(cfg, ShapeConfig("t", 4096, 256, "train"), plan, mesh)
    m = DR.analytic_memory(low)
    assert m["analytic_bytes"] > 0 and m["lowered_bytes"] > 0
    assert "predicted_vs_lowered_rel" in m

    # serving cells: the analytic number IS the lowered spec walk
    pshape = ShapeConfig("p", 1024, 16, "prefill")
    plow = lower_plan(cfg, pshape,
                      single_stage_plan(cfg.num_layers, dp=16, tp=16,
                                        micro_batch=1, grad_accum=1,
                                        zero=0, ckpt_layers=0), mesh)
    mp = DR.analytic_memory(plow)
    assert mp["analytic_bytes"] == mp["lowered_bytes"] > 0


# ---------------------------------------------------------------------------
# dryrun smoke: lower_cell through the lowering layer, 2 archs
# ---------------------------------------------------------------------------

_DRYRUN_SMOKE = r"""
from repro.launch.dryrun import lower_cell
for arch in ("whisper-small", "internvl2-1b"):
    rec = lower_cell(arch, "train_4k", multi_pod=False, view="2x1")
    m = rec["memory"]
    assert m["device_total_bytes"] > 0, rec
    assert m["analytic_bytes"] > 0 and m["lowered_bytes"] > 0
    assert m["predicted_vs_lowered_rel"] < 0.35, m
    assert rec["plan"]["stages"][0]["tp"] == 1
    print("DRYRUN_OK", arch, rec["mesh"])
"""


def test_dryrun_lower_cell_smoke():
    """launch/dryrun.py lower_cell compiles two archs end to end through
    the lowering layer (subprocess: dryrun forces a host device count via
    XLA_FLAGS, which must not leak into this process's jax)."""
    import os
    env = {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    r = subprocess.run([sys.executable, "-c", _DRYRUN_SMOKE],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert r.stdout.count("DRYRUN_OK") == 2
