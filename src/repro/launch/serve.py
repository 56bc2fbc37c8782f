"""Serving launcher: batched prefill + decode against the KV/state cache.

    python -m repro.launch.serve --arch granite-3-8b --smoke \
        --batch 4 --prompt-len 32 --gen 16

``--tune`` replaces the hand-written dp-only baseline with the winner of
the ``serve`` search space (docs/serving.md): the tuner prices (dp, tp,
zero, kv dtype) candidates on the symbolic KV-cache/decode cost model,
and the launcher cross-checks that model against the lowered plan's
``memory_report()`` — the predicted serve bytes must match bitwise.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def generate(model, params, prompts: jnp.ndarray, gen: int, mesh, plan,
             lowered=None, max_len=None):
    """Greedy decode `gen` tokens for a batch of fixed-length prompts.

    ``max_len`` pins the cache horizon (default: plen + gen).  Pass the
    engine's global horizon when comparing against the continuous-
    batching path: XLA may associate attention reductions differently at
    different cache lengths, so token-identity holds only like-for-like.
    """
    from repro.lowering import lower_plan
    from repro.models.zoo import pad_caches, quantize_caches
    from repro.training.step import make_prefill_step, make_serve_step

    b, plen = prompts.shape
    if max_len is None:
        max_len = plen + gen
    if plen + gen > max_len:
        raise ValueError(f"prompt {plen} + gen {gen} exceeds max_len "
                         f"{max_len}")
    # one lowering shared by the prefill and decode programs: both read the
    # same mesh-axis mapping / spec tables / serve exec config
    low = lowered or lower_plan(model.cfg, None, plan, mesh)
    prefill = make_prefill_step(model, return_cache=True, lowered=low)
    logits, caches = prefill.fn(params, {"tokens": prompts})
    if plan.kv_cache_dtype == "int8":
        # prefill emits bf16 caches; decode reads the int8+scales layout
        caches = quantize_caches(caches)
    caches = pad_caches(caches, max_len - plen)
    serve = make_serve_step(model, batch=b, max_len=max_len, donate=False,
                            lowered=low)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    out = [tok]
    for _ in range(gen - 1):
        logits, caches = serve.fn(params, tok, caches)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def tuned_serve_plan(cfg, *, batch: int, max_len: int, n_devices: int,
                     page_grid=None):
    """Run the ``serve`` search space and return (plan, report)."""
    from repro.core.tuner import MistTuner, TuneSpec
    spec = TuneSpec(arch=cfg, seq_len=max_len, global_batch=batch,
                    n_devices=n_devices, space="serve",
                    page_grid=page_grid)
    report = MistTuner(spec).tune()
    if report.plan is None:
        raise SystemExit("serve tuner: no feasible plan "
                         f"(swept {report.n_swept} candidates)")
    return report.plan, report


def run_continuous(model, params, prompts, gens, mesh, plan, *,
                   slots: int, page_size: int, max_len: int, lowered=None):
    """Serve one request per prompt (a (1, n_i) token array each; lengths
    may differ) with per-request output budgets ``gens`` through the
    continuous-batching engine; returns ({rid: tokens}, engine)."""
    from repro.serving import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(model, params, plan, mesh, slots=slots,
                                   max_len=max_len, page_size=page_size,
                                   lowered=lowered)
    for i, p in enumerate(prompts):
        eng.submit({"tokens": p}, gens[i], rid=i)
    return eng.run(), eng


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tune", action="store_true",
                    help="pick the plan via the 'serve' search space "
                         "instead of the dp-only baseline")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(paged KV cache, docs/continuous-batching.md) "
                         "instead of one static batch")
    ap.add_argument("--slots", type=int, default=2,
                    help="decode slots for --continuous")
    ap.add_argument("--page-size", type=int, default=8,
                    help="KV page rows for --continuous (must divide "
                         "prompt-len + gen); --tune sweeps {0, this}")
    args = ap.parse_args()

    from repro.configs.base import get_arch
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from repro.core.plan import single_stage_plan
    from repro.launch.mesh import make_host_mesh
    from repro.models.zoo import build_model

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)
    n = len(jax.devices())
    max_len = args.prompt_len + args.gen
    if args.continuous and max_len % args.page_size:
        raise SystemExit(f"--page-size {args.page_size} must divide "
                         f"prompt-len + gen = {max_len}")
    if args.tune:
        plan, report = tuned_serve_plan(
            cfg, batch=args.batch, max_len=max_len, n_devices=n,
            page_grid=(0, args.page_size) if args.continuous else None)
        st = plan.stages[0]
        mesh = make_host_mesh(st.dp, st.tp)
        print(f"# tuned serve plan: dp={st.dp} tp={st.tp} zero={st.zero} "
              f"kv={plan.kv_cache_dtype} page_size={plan.page_size} "
              f"(objective {report.objective:.4f}s, "
              f"{report.throughput_tokens:.1f} tok/s predicted)")
        print(plan.to_json())
    else:
        plan = single_stage_plan(cfg.num_layers, dp=n, tp=1, micro_batch=1,
                                 grad_accum=1, zero=0, ckpt_layers=0)
        mesh = make_host_mesh(n, 1)
    from repro.configs.base import ShapeConfig
    from repro.lowering import lower_plan
    shape = ShapeConfig("serve", max_len, args.batch, "decode")
    low = lower_plan(cfg, shape, plan, mesh)
    rep = low.memory_report()
    print(f"# lowered serve memory: {rep.peak_bytes / 2**30:.2f} GiB "
          f"per device (weights+cache+transient)")
    if args.tune:
        # the two-evaluation contract, asserted at launch: the symbolic
        # model the tuner ranked candidates with must equal the lowered
        # report on the chosen plan, bitwise
        from repro.core.costmodel import estimate_serve_plan
        est = estimate_serve_plan(cfg, shape, plan)
        assert est["mem_decode"] == rep.peak_bytes, \
            (est["mem_decode"], rep.peak_bytes)
        print("# predicted serve memory == memory_report(): bitwise OK")
    with jax.set_mesh(mesh):
        params, _ = model.init(jax.random.PRNGKey(0))
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size).astype(jnp.int32)
        if args.continuous:
            # mixed output budgets (full / half, alternating) so retire +
            # re-admit actually fires; tokens must equal the static path
            # prefix by greedy determinism
            gens = [args.gen if i % 2 == 0 else max(1, args.gen // 2)
                    for i in range(args.batch)]
            ps = plan.page_size or args.page_size
            t0 = time.time()
            res, eng = run_continuous(model, params, prompts[:, None], gens,
                                      mesh, plan, slots=args.slots,
                                      page_size=ps, max_len=max_len,
                                      lowered=low)
            dt = time.time() - t0
            ref = generate(model, params, prompts, args.gen, mesh, plan,
                           lowered=low, max_len=max_len)
            for i, g in enumerate(gens):
                assert np.array_equal(res[i], np.asarray(ref[i])[:g]), \
                    f"continuous tokens diverged from static (request {i})"
            if n == 1:
                from repro.lowering.cache_layout import \
                    concrete_paged_cache_bytes
                want = int(concrete_paged_cache_bytes(
                    cfg, args.slots, max_len, ps, plan.kv_cache_dtype,
                    dp_size=1, tp_size=1))
                assert eng.memory_bytes() == want, \
                    (eng.memory_bytes(), want)
                print("# paged cache bytes == derived layout: bitwise OK")
            total = sum(gens)
            print(f"continuous: {total} tokens / {args.batch} requests in "
                  f"{dt:.2f}s ({total / dt:.1f} tok/s, {eng.steps_run} "
                  f"decode steps, {args.slots} slots, page_size {ps}); "
                  f"tokens match the static path")
            return 0
        t0 = time.time()
        toks = generate(model, params, prompts, args.gen, mesh, plan,
                        lowered=low)
        dt = time.time() - t0
    total = args.batch * args.gen
    print(f"generated {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s); first row: {np.asarray(toks[0])[:8]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
