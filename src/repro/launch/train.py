"""Training launcher.

    python -m repro.launch.train --arch granite-3-8b --steps 50 --smoke
    python -m repro.launch.train --arch qwen2-72b --tune --devices 256 \
        --seq 4096 --global-batch 256           # tune-only (prints the plan)

`--smoke` runs a reduced same-family config end-to-end on the host CPU
devices (the full configs are exercised via the dry-run); otherwise the
launcher tunes/loads a Plan for the production mesh and either executes
(when the mesh is available) or emits the plan + predicted throughput.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--devices", type=int, default=256)
    ap.add_argument("--space", default="mist")
    ap.add_argument("--tune", action="store_true",
                    help="run the Mist tuner and print the plan")
    ap.add_argument("--memo-dir", default=None,
                    help="persistent tuning memo store "
                         "(core/memo_store.py): warm (arch, mesh, batch) "
                         "queries answer in milliseconds, cold sweeps "
                         "persist their frontiers for future runs")
    ap.add_argument("--plan-json", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced config on host devices")
    ap.add_argument("--calibrate", action="store_true",
                    help="measure this arch's reduced golden cells on the "
                         "host devices and fit a per-platform "
                         "CalibrationProfile (docs/calibration.md)")
    ap.add_argument("--calibration-out", default=None, metavar="PATH|auto",
                    help="with --calibrate: persist the fitted profile "
                         "(auto = the platform's default cache location)")
    ap.add_argument("--ckpt-dir", default=str(
        pathlib.Path(__file__).resolve().parents[3] / "results" / "ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    from repro.configs.base import ShapeConfig, get_arch
    from repro.core.plan import Plan
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_arch(args.arch)
    shape = ShapeConfig("cli", args.seq, args.global_batch, "train")

    if args.calibrate:
        from repro.calibration.driver import format_table, run_calibration
        report = run_calibration(archs=(args.arch,),
                                 steps=min(args.steps, 6),
                                 write_profile=args.calibration_out)
        print(format_table(report))
        if report.get("error"):
            return 1
        return 0 if (report["mean_err_fitted"]
                     <= report["mean_err_uncalibrated"] + 1e-12) else 1

    # the platform's fitted constants (docs/calibration.md); on a TPU v5e
    # the shipped profile prices what the chip's compiler holds in HBM
    from repro.calibration import load_profile
    profile = load_profile()
    plan = None
    if args.plan_json:
        plan = Plan.from_json(pathlib.Path(args.plan_json).read_text())
    elif args.tune:
        from repro.core.tuner import tune
        rep = tune(cfg, shape, args.devices, space=args.space,
                   memo_dir=args.memo_dir, profile=profile)
        if rep.plan is None:
            print("INFEASIBLE for this device count / batch")
            return 1
        print(f"# tuned in {rep.tune_seconds:.1f}s over {rep.n_points} "
              f"configs; predicted step {rep.objective:.3f}s "
              f"({rep.throughput_samples:.2f} samples/s)"
              + (" [memo-store hit]" if rep.from_memo else ""))
        print(rep.plan.to_json())
        plan = rep.plan

    if plan is not None:
        # tuner->runtime consistency: what the cost model predicted vs what
        # the lowered spec tables actually hold per device
        if args.space == "serve":
            # serving plans are priced by the serve cost model; check the
            # bitwise serve contract (docs/serving.md), not the training
            # memory model
            from repro.core.costmodel import estimate_serve_plan
            from repro.lowering import lower_plan
            st0 = plan.stages[0]
            sshape = ShapeConfig("cli", args.seq, args.global_batch,
                                 "decode")
            mesh = jax.sharding.AbstractMesh((st0.dp, st0.tp),
                                        ("data", "model"))
            rep = lower_plan(cfg, sshape, plan, mesh).memory_report()
            est = estimate_serve_plan(cfg, sshape, plan)
            print(f"# serve memory: predicted "
                  f"{est['mem_decode'] / 2**30:.2f} GiB lowered "
                  f"{rep.peak_bytes / 2**30:.2f} GiB "
                  f"(bitwise={est['mem_decode'] == rep.peak_bytes})")
        else:
            from repro.lowering import memory_consistency
            mc = memory_consistency(cfg, shape, plan, profile=profile)
            print(f"# memory: predicted "
                  f"{mc['predicted_bytes'] / 2**30:.2f} GiB "
                  f"lowered {mc['lowered_bytes'] / 2**30:.2f} GiB "
                  f"(rel err {mc['rel_error']:.3f}, "
                  f"within_tol={mc['within_tol']})")
        if args.tune and not args.smoke:
            return 0

    if not args.smoke:
        print("no --smoke and no executable mesh: use --tune to produce a "
              "plan, or repro.launch.dryrun to compile for the production "
              "mesh")
        return 0

    # ---- smoke training on host devices ------------------------------------
    from repro.core.plan import single_stage_plan
    from repro.launch.mesh import make_host_mesh
    from repro.lowering import lower_plan
    from repro.models.zoo import build_model
    from repro.training.data import BatchSpec, SyntheticLM
    from repro.training.loop import LoopConfig, TrainLoop
    from repro.training.step import init_sharded_state, make_train_step

    rcfg = cfg.reduced()
    model = build_model(rcfg)
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 and rcfg.num_heads % 2 == 0 else 1
    dp = n // tp
    gbs = max(8, dp * 2)
    plan = single_stage_plan(rcfg.num_layers, dp=dp, tp=tp,
                             micro_batch=gbs // dp // 2 or 1, grad_accum=2,
                             zero=1, ckpt_layers=rcfg.num_layers // 2)
    mesh = make_host_mesh(n, tp)
    seq = 128
    smoke_shape = ShapeConfig("smoke", seq, gbs, "train")
    low = lower_plan(rcfg, smoke_shape, plan, mesh)
    rep = low.memory_report()
    print(f"# smoke plan lowered: peak {rep.peak_bytes / 2**30:.2f} GiB "
          f"per device (fits={rep.fits})")
    with jax.set_mesh(mesh):
        step = make_train_step(model, plan, mesh, lowered=low)
        state, shardings = init_sharded_state(model, plan, mesh,
                                              jax.random.PRNGKey(0),
                                              lowered=low)
        data = SyntheticLM(BatchSpec(global_batch=gbs, seq_len=seq,
                                     vocab_size=rcfg.vocab_size))

        def batches(step_idx):
            b = data.batch(step_idx)
            return {k: jnp.asarray(v) for k, v in b.items()}

        loop = TrainLoop(step.fn, state, batches, ckpt_dir=args.ckpt_dir,
                         cfg=LoopConfig(total_steps=args.steps,
                                        ckpt_every=args.ckpt_every),
                         state_shardings=shardings,
                         meta={"arch": rcfg.name})
        t0 = time.time()
        stats = loop.run()
        dt = time.time() - t0
    print(f"trained {stats.steps_done} steps in {dt:.1f}s "
          f"({dt / max(1, stats.steps_done):.2f}s/step); "
          f"loss {stats.losses[0]:.3f} -> {stats.losses[-1]:.3f}; "
          f"restarts={stats.restarts} rollbacks={stats.rollbacks}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
