"""End-to-end training driver: a ~100M-parameter LLaMa-style model trained
for a few hundred steps on the host devices, with the full production
substrate — Mist-tuned execution knobs, packed data pipeline, async sharded
checkpoints, fault-tolerant loop, and resume.

    PYTHONPATH=src python examples/train_e2e.py [--steps 300]
"""
import argparse
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, register
from repro.core.plan import single_stage_plan
from repro.launch.mesh import make_host_mesh
from repro.models.zoo import build_model
from repro.training.data import BatchSpec, SyntheticLM
from repro.training.loop import LoopConfig, TrainLoop
from repro.training.step import init_sharded_state, make_train_step

# ~100M params: 12 x 512 with a 32k vocab
M100 = ArchConfig(
    name="llama-100m", family="dense", num_layers=12, d_model=512,
    num_heads=8, num_kv_heads=4, d_ff=1536, vocab_size=32000,
    norm_type="rmsnorm", act="silu", mlp_gated=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    model = build_model(M100)
    n_params = M100.param_count()
    print(f"model: {M100.name}, {n_params / 1e6:.1f}M params")

    mesh = make_host_mesh(1, 1)
    plan = single_stage_plan(M100.num_layers, dp=1, tp=1,
                             micro_batch=args.batch // 2, grad_accum=2,
                             zero=0, ckpt_layers=M100.num_layers // 2)
    data = SyntheticLM(BatchSpec(global_batch=args.batch, seq_len=args.seq,
                                 vocab_size=M100.vocab_size), seed=7)

    with jax.set_mesh(mesh):
        step = make_train_step(model, plan, mesh)
        state, shardings = init_sharded_state(model, plan, mesh,
                                              jax.random.PRNGKey(0))
        start = 0
        if args.resume:
            from repro.training.checkpoint import Checkpointer
            ck = Checkpointer(args.ckpt_dir)
            if ck.latest_step() is not None:
                start, state, _ = ck.restore(shardings=shardings)
                print(f"resumed from step {start}")

        def batches(i):
            b = data.batch(i)
            return {k: jnp.asarray(v) for k, v in b.items()}

        loop = TrainLoop(step.fn, state, batches, ckpt_dir=args.ckpt_dir,
                         cfg=LoopConfig(total_steps=args.steps,
                                        ckpt_every=50, log_every=25),
                         state_shardings=shardings,
                         meta={"arch": M100.name, "plan": plan.to_json()})
        loop._step = start
        t0 = time.time()
        stats = loop.run()
        dt = time.time() - t0

    tok_s = stats.steps_done * args.batch * args.seq / dt
    print(f"\ntrained {stats.steps_done} steps in {dt:.0f}s "
          f"({tok_s / 1e3:.1f}K tokens/s on host CPU)")
    k = max(1, len(stats.losses) // 10)
    print("loss curve:", " ".join(f"{np.mean(stats.losses[i:i + k]):.3f}"
                                  for i in range(0, len(stats.losses), k)))
    assert stats.losses[-1] < stats.losses[0], "loss must decrease"
    print(f"checkpoints under {args.ckpt_dir}: resume with --resume")


if __name__ == "__main__":
    main()
