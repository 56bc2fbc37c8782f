import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes (16x16 single-pod, 2x16x16 multi-pod), record
memory_analysis / cost_analysis / trip-count-aware HLO stats, and emit the
roofline terms.

Usage:
    python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod both|on|off]
    python -m repro.launch.dryrun --all --plan-json plan.json
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, get_arch, list_archs
from repro.core.plan import Plan, single_stage_plan
from repro.launch.mesh import make_production_mesh
from repro.lowering import LoweredPlan, lower_plan
from repro.lowering.memory import stage_state_bytes
from repro.models import build_model
from repro.models.zoo import abstract_params, input_specs
from repro.perf.hloanalysis import analyze
from repro.perf.roofline import model_flops_for, report_from_stats
from repro.training import optimizer as OPT
from repro.training.step import (make_prefill_step, make_serve_step,
                                 make_train_step)

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def state_bytes_per_device(cfg: ArchConfig, mesh, zero: int) -> float:
    """EXACT model-state bytes per chip for a zero level: lowers a trial
    plan and walks every param's actual PartitionSpec (indivisible dims —
    MHA head counts, small norms — really do replicate, which naive
    N/(dp*tp) accounting misses)."""
    tp = mesh.shape.get("model", 1)
    n_dev = 1
    for s in mesh.shape.values():
        n_dev *= s
    trial = single_stage_plan(cfg.num_layers, dp=max(1, n_dev // tp), tp=tp,
                              micro_batch=1, grad_accum=1, zero=zero)
    return stage_state_bytes(lower_plan(cfg, None, trial, mesh))


def min_fitting_zero(cfg: ArchConfig, mesh,
                     budget: float = 0.6 * 16 * 2**30) -> int:
    """Smallest ZeRO level whose model-state bytes fit the per-chip budget.

    Megatron-LM's --use-distributed-optimizer corresponds to ZeRO>=1; the
    paper's point is that this knob must be co-tuned, so the *baseline* picks
    the smallest feasible level (what a careful engineer would hand-pick)."""
    for zero in (1, 2, 3):
        if state_bytes_per_device(cfg, mesh, zero) < budget:
            return zero
    return 3


def analytic_memory(lowered: LoweredPlan) -> Dict[str, Any]:
    """TPU-target memory estimate (bytes/chip), independent of the host
    compile artifact.  XLA:CPU's FloatNormalization legalizes bf16 compute
    through f32 buffers (whole-cache/param f32 copies visible in the host
    HLO), so the compiled `memory_analysis` OVERESTIMATES what the TPU
    (native-bf16 MXU) target allocates; this analytic estimate is the
    TPU-side number and EXPERIMENTS.md reports both.

    Train cells report BOTH sides of the lowering contract: the symbolic
    prediction (``analytic_bytes``, what the tuner believed) and the
    spec-walked ``lowered_bytes`` from ``LoweredPlan.memory_report`` (what
    the lowered program holds), plus their relative gap — the
    tuner->runtime consistency signal (docs/plan-lowering.md)."""
    cfg, shape, plan = lowered.cfg, lowered.shape, lowered.plan
    rep = lowered.memory_report()
    if shape.kind == "train":
        from repro.core.costmodel import estimate_plan
        est = estimate_plan(cfg, shape, plan)
        pred = float(est["mem_peak_max"])
        return {"analytic_bytes": pred,
                "fits_16GiB_analytic": bool(est["fits"]),
                "lowered_bytes": rep.peak_bytes,
                "fits_16GiB_lowered": bool(rep.fits),
                "predicted_vs_lowered_rel":
                    abs(rep.peak_bytes - pred) / max(pred, 1.0)}
    # serving: exact params-per-chip (+ cache-per-chip) + transient, all
    # from the lowered spec tables
    return {"analytic_bytes": rep.peak_bytes,
            "fits_16GiB_analytic": bool(rep.peak_bytes < 16 * 2**30),
            "lowered_bytes": rep.peak_bytes}


def analytic_hbm_traffic(cfg: ArchConfig, shape: ShapeConfig,
                         plan: Plan) -> Optional[float]:
    """TPU-target HBM bytes per chip per step (the artifact's byte count
    reflects XLA:CPU fusion boundaries + f32 legalization; see DESIGN §8).
    Train cells use the cost-model traffic expression; serve cells use
    weights+cache per token."""
    from repro.core.costmodel import StageCostModel
    from repro.core.schedule import Candidate
    st = plan.stages[0]
    try:
        if shape.kind == "train":
            scm = StageCostModel(cfg, shape.seq_len,
                                 sequence_parallel=plan.sequence_parallel)
            cand = Candidate(b=st.micro_batch, dp=st.dp, tp=st.tp,
                             zero=st.zero,
                             ckpt=min(st.ckpt_layers, st.layers), wo=st.wo,
                             go=st.go, oo=st.oo, ao=st.ao)
            env = scm._env(scm.env_from_candidates(
                [cand], layers=st.layers, grad_accum=plan.grad_accum))
            import numpy as np
            return float(np.asarray(
                scm.hbm_bytes_step.evaluate(env)).reshape(-1)[0])
        # serving: weights once + cache read(+write for decode)
        n = cfg.param_count()
        w = 2.0 * n / (st.tp * (st.dp if st.zero >= 3 else 1))
        if shape.kind == "prefill":
            tokens_local = shape.global_batch * shape.seq_len / st.dp
            from repro.core.costmodel import arch_stats
            stt = arch_stats(cfg)
            act = 4.0 * stt.act_coef_full * stt.d_model * tokens_local \
                / max(1, st.tp)
            return st.layers and w * 1.0 + act
        return None   # decode: cache-spec-dependent; artifact number kept
    except Exception:
        return None


def baseline_plan(cfg: ArchConfig, shape: ShapeConfig, mesh,
                  overrides: Optional[Dict[str, Any]] = None) -> Plan:
    """Paper-faithful Megatron-style baseline: TP over the model axis, DP
    over data(+pod), minimum feasible ZeRO, full activation checkpointing,
    micro-batch 1, FlashAttention on (the paper's Fig. 11 setting)."""
    ov = dict(overrides or {})
    tp = ov.pop("tp", mesh.shape.get("model", 1))
    # a tp=1 plan folds the model axis into DP (lowering.plan_mesh_axes),
    # so dp always spans all chips divided by tp
    dp = ov.pop("dp", mesh.devices.size // tp)
    ov.setdefault("attn_impl", "blocked")
    if "zero" not in ov:
        if shape.kind == "train":
            ov["zero"] = min_fitting_zero(cfg, mesh)
        else:
            ov["zero"] = 0   # serving: replicated weights per TP group
            #                  (zero=3 override = weight-gathered serving)
    if shape.kind == "train":
        micro = ov.pop("micro_batch", 1)
        assert shape.global_batch % (dp * micro) == 0, (shape, dp, micro)
        grad_accum = ov.pop("grad_accum", shape.global_batch // (dp * micro))
    else:
        micro = max(1, shape.global_batch // dp)
        grad_accum = 1
    return single_stage_plan(cfg.num_layers, dp=dp, tp=tp, micro_batch=micro,
                             grad_accum=grad_accum, **ov)


def _attach(sds_tree, shardings):
    return jax.tree.map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        sds_tree, shardings)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               plan_overrides: Optional[Dict[str, Any]] = None,
               save_hlo: bool = False, hw_check: bool = True,
               view: Optional[str] = None) -> Dict[str, Any]:
    """view: 'DPxTP' reshapes the SAME chips into a different (data, model)
    mesh for an optimized plan (the spec mesh stays the baseline)."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if shape_name not in cfg.shapes:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "shape not applicable (see DESIGN.md §4)"}
    if view:
        dpv, tpv = (int(x) for x in view.split("x"))
        mesh = compat.make_mesh((dpv, tpv), ("data", "model"))
        plan_overrides = dict(plan_overrides or {})
        plan_overrides.setdefault("dp", dpv)
        plan_overrides.setdefault("tp", tpv)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    model = build_model(cfg)
    plan = baseline_plan(cfg, shape, mesh, plan_overrides)
    low = lower_plan(cfg, shape, plan, mesh)
    params_sds, axes_table = low.params_sds, low.axes_table

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = make_train_step(model, plan, mesh, lowered=low)
            state_abs = OPT.init_state(params_sds, axes_table, plan.stages[0])
            state_sds = _attach(state_abs, step.state_shardings)
            batch = input_specs(cfg, shape)
            batch_sds = _attach(batch, low.batch_shardings(batch))
            program = step.fn.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            step = make_prefill_step(model, plan, mesh, lowered=low)
            p_sds = _attach(params_sds, low.param_shardings())
            batch = input_specs(cfg, shape)
            batch_sds = _attach(batch, low.batch_shardings(batch))
            program = step.fn.lower(p_sds, batch_sds)
        else:  # decode
            b, s = shape.global_batch, shape.seq_len
            step = make_serve_step(model, plan, mesh, b, s, lowered=low)
            p_sds = _attach(params_sds, low.param_shardings())
            cache_dtype = jnp.int8 if plan.kv_cache_dtype == "int8" \
                else jnp.bfloat16
            spec = input_specs(cfg, shape, cache_dtype=cache_dtype)
            tok_sds = spec["tokens"]
            cache_sds = _attach(spec["caches"], step.batch_shardings)
            program = step.fn.lower(p_sds, tok_sds, cache_sds)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = program.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    stats = analyze(hlo_text)
    chips = mesh.devices.size
    mf = model_flops_for(cfg, shape)
    rep = report_from_stats(stats, arch=arch, shape=shape_name,
                            mesh=mesh_name, chips=chips,
                            model_flops_global=mf, xla_cost=cost,
                            hbm_bytes_analytic=analytic_hbm_traffic(
                                cfg, shape, plan))

    # donated state aliases its outputs: alias_size must not double count
    dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "plan": json.loads(plan.to_json()),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "host_argument_bytes": mem.host_argument_size_in_bytes,
            "host_temp_bytes": mem.host_temp_size_in_bytes,
            "device_total_bytes": dev_bytes,
            "fits_16GiB": bool(dev_bytes < 16 * 2**30),
            **analytic_memory(low),
        },
        "xla_cost": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "hlo_stats": {
            "dot_flops": stats.dot_flops,
            "hbm_bytes": stats.hbm_bytes,
            "collective_wire_bytes": stats.collective_wire_bytes,
            "collective_by_kind": stats.collective_by_kind,
            "n_collectives": stats.n_collectives,
        },
        "roofline": json.loads(rep.to_json()),
    }
    if save_hlo:
        import gzip
        RESULTS.mkdir(parents=True, exist_ok=True)
        with gzip.open(RESULTS / f"{arch}_{shape_name}_{mesh_name}.hlo.gz",
                       "wt") as f:
            f.write(hlo_text)
    return rec


def run(archs, shapes, pods, save_json=True, plan_overrides=None,
        tag="", save_hlo=False, view=None) -> list:
    out = []
    for arch in archs:
        cfg = get_arch(arch)
        for shape_name in shapes:
            if shape_name not in cfg.shapes:
                out.append({"arch": arch, "shape": shape_name,
                            "skipped": True})
                print(f"SKIP  {arch:18s} {shape_name:12s} (not applicable)")
                continue
            for mp in pods:
                try:
                    rec = lower_cell(arch, shape_name, multi_pod=mp,
                                     plan_overrides=plan_overrides,
                                     save_hlo=save_hlo, view=view)
                    rec["ok"] = True
                    r = rec["roofline"]
                    m = rec["memory"]
                    print(f"OK    {arch:18s} {shape_name:12s} "
                          f"mesh={rec['mesh']:9s} "
                          f"compile={rec['compile_s']:6.1f}s "
                          f"dev={m['device_total_bytes']/2**30:6.2f}GiB "
                          f"fit={m['fits_16GiB']} "
                          f"bottleneck={r['bottleneck']:10s} "
                          f"roofline={r['roofline_fraction']:.3f}")
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "multi_pod": mp, "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    print(f"FAIL  {arch:18s} {shape_name:12s} multi_pod={mp} "
                          f"{type(e).__name__}: {str(e)[:120]}")
                out.append(rec)
                if save_json:
                    RESULTS.mkdir(parents=True, exist_ok=True)
                    mesh_name = rec.get("mesh", f"mp{int(mp)}")
                    p = RESULTS / f"{arch}_{shape_name}_{mesh_name}{tag}.json"
                    p.write_text(json.dumps(rec, indent=2))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["both", "on", "off"],
                    default="both")
    ap.add_argument("--plan-json", default=None,
                    help="JSON dict of StageConfig/plan overrides")
    ap.add_argument("--tag", default="", help="suffix for result files")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--view", default=None,
                    help="'DPxTP' mesh view of the same 256 chips for an "
                         "optimized plan (e.g. 32x8)")
    args = ap.parse_args()

    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = list(SHAPES) if args.all or not args.shape else [args.shape]
    pods = {"both": [False, True], "on": [True], "off": [False]}[
        args.multi_pod]
    overrides = json.loads(pathlib.Path(args.plan_json).read_text()) \
        if args.plan_json else None
    recs = run(archs, shapes, pods, plan_overrides=overrides, tag=args.tag,
               save_hlo=args.save_hlo, view=args.view)
    n_ok = sum(1 for r in recs if r.get("ok"))
    n_skip = sum(1 for r in recs if r.get("skipped"))
    n_fail = sum(1 for r in recs if r.get("ok") is False)
    print(f"\n== dry-run summary: ok={n_ok} skipped={n_skip} fail={n_fail} ==")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
