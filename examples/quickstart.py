"""Quickstart: tune a Mist plan for an assigned architecture, inspect it,
and run a few training steps of the reduced config locally.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import ShapeConfig, get_arch
from repro.core.costmodel import estimate_plan
from repro.core.plan import single_stage_plan
from repro.core.tuner import tune
from repro.launch.mesh import make_host_mesh
from repro.models.zoo import build_model
from repro.training.step import init_sharded_state, make_train_step


def main():
    # ---- 1. auto-tune a training plan for the production target ----------
    arch = get_arch("granite-3-8b")
    shape = ShapeConfig("train", seq_len=4096, global_batch=64, kind="train")
    print(f"tuning {arch.name} ({arch.param_count() / 1e9:.1f}B params) "
          f"for 32 TPU-v5e chips, global batch {shape.global_batch} ...")
    report = tune(arch, shape, n_devices=32, space="mist",
                  stage_counts=(1, 2), grad_accums=(2, 4, 8))
    print(f"  evaluated {report.n_points} configurations in "
          f"{report.tune_seconds:.1f}s")
    print(f"  predicted step time {report.objective:.2f}s "
          f"({report.throughput_tokens / 1e6:.2f}M tokens/s)")
    print(report.plan.to_json())

    est = estimate_plan(arch, shape, report.plan)
    print(f"  modeled peak memory/chip: "
          f"{est['mem_peak_max'] / 2**30:.2f} GiB (fits: {est['fits']})")

    # ---- 2. train the reduced config for a few steps locally -------------
    rcfg = arch.reduced()
    model = build_model(rcfg)
    mesh = make_host_mesh(1, 1)
    tuned = report.plan.stages[0]
    plan = single_stage_plan(rcfg.num_layers, dp=1, tp=1, micro_batch=4,
                             grad_accum=2, zero=tuned.zero,
                             ckpt_layers=min(tuned.ckpt_layers,
                                             rcfg.num_layers))
    with jax.set_mesh(mesh):
        step = make_train_step(model, plan, mesh, donate=False)
        state, _ = init_sharded_state(model, plan, mesh,
                                      jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        batch = {
            "tokens": jax.random.randint(key, (8, 128), 0, rcfg.vocab_size),
            "labels": jax.random.randint(key, (8, 128), 0, rcfg.vocab_size),
        }
        print("training the reduced config (same code paths, tiny dims):")
        for i in range(5):
            state, metrics = step.fn(state, batch)
            print(f"  step {i}: loss {float(metrics['loss']):.4f} "
                  f"grad_norm {float(metrics['grad_norm']):.3f}")


if __name__ == "__main__":
    main()
