"""Batched serving example: prefill a batch of prompts, then decode with a
shared KV cache — exercising the same serve_step the decode-shape dry-run
cells lower.

    PYTHONPATH=src python examples/serve_batched.py [--arch zamba2-2.7b]

``--continuous`` serves the same prompts through the continuous-batching
engine (paged KV cache, docs/continuous-batching.md) with fewer decode
slots than requests, and asserts every request's tokens equal the static
batch's rows — batching policy must not move numerics.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch
from repro.core.plan import single_stage_plan
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import generate
from repro.models.zoo import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-2.7b",
                    help="any assigned arch (reduced config is served)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--continuous", action="store_true",
                    help="also serve via the continuous-batching engine "
                         "and assert per-request token identity")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--page-size", type=int, default=8)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    model = build_model(cfg)
    mesh = make_host_mesh(1, 1)
    plan = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0)
    max_len = args.prompt_len + args.gen
    if args.continuous and max_len % args.page_size:
        raise SystemExit(f"--page-size {args.page_size} must divide "
                         f"prompt-len + gen = {max_len}")
    with jax.set_mesh(mesh):
        params, _ = model.init(jax.random.PRNGKey(0))
        rng = jax.random.PRNGKey(1)
        prompts = jax.random.randint(
            rng, (args.batch, args.prompt_len), 0,
            cfg.vocab_size).astype(jnp.int32)
        t0 = time.time()
        toks = generate(model, params, prompts, args.gen, mesh, plan)
        dt = time.time() - t0
        if args.continuous:
            from repro.serving import ContinuousBatchingEngine
            eng = ContinuousBatchingEngine(
                model, params, plan, mesh, slots=args.slots,
                max_len=max_len, page_size=args.page_size)
            for i in range(args.batch):
                eng.submit({"tokens": prompts[i:i + 1]}, args.gen, rid=i)
            t1 = time.time()
            res = eng.run()
            dt_c = time.time() - t1
    total = args.batch * args.gen
    print(f"{cfg.name}: generated {total} tokens for {args.batch} requests "
          f"in {dt:.2f}s ({total / dt:.1f} tok/s, host CPU)")
    for i in range(min(2, args.batch)):
        print(f"  request {i}: {np.asarray(toks[i])[:12]} ...")
    if args.continuous:
        ref = np.asarray(toks)
        for i in range(args.batch):
            assert np.array_equal(res[i], ref[i]), \
                f"continuous tokens diverged from static (request {i})"
        print(f"  continuous ({args.slots} slots, page_size "
              f"{args.page_size}): {total} tokens in {dt_c:.2f}s "
              f"({total / dt_c:.1f} tok/s, {eng.steps_run} decode steps); "
              f"all {args.batch} requests token-identical to the static "
              f"batch")


if __name__ == "__main__":
    main()
