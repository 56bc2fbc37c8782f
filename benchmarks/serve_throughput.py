"""Serving throughput: the ``serve`` search space measured end to end
(docs/serving.md).

Three measurement groups:

  * **predicted memory bitwise** — the symbolic serve cost model the
    tuner ranks candidates with equals ``memory_report()`` on the tuned
    plan's lowering, bitwise (the two-evaluation contract on the serve
    path).  Asserted, not reported.
  * **tokens identical** — ``generate()`` under the tuned plan emits the
    same token ids as under the dp-only baseline plan (plan choice moves
    work around; it must not move numerics).  Asserted for bf16 plans.
  * **tok/s tuned vs baseline** — measured greedy-decode throughput of
    both plans.  When the tuner selects exactly the baseline plan (it
    does on a single device, where dp=1/tp=1 is the whole grid), one
    measurement serves both rows and the ratio is exactly 1 — the
    benchmark never flakes on timing noise in a degenerate cell.

Run with --smoke for a CI-sized invocation (reduced golden arch, small
batch/lengths, one rep); --json PATH additionally writes the rows as a
JSON document (uploaded as a CI artifact next to the kernel-tuning
report).

``--trace`` runs the continuous-batching headline instead: a mixed
prompt/output-length trace served by ``ContinuousBatchingEngine``
(paged KV cache, docs/continuous-batching.md) vs plen-bucketed static
batches of the same requests at the same global max_len.  Per-request
token identity and a >= 1.25x useful-tok/s ratio are asserted.
"""
from __future__ import annotations

import json
import sys
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs.base import ShapeConfig, get_arch
from repro.core.costmodel import estimate_serve_plan
from repro.core.plan import single_stage_plan
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import generate, tuned_serve_plan
from repro.lowering import lower_plan
from repro.models.zoo import build_model

SMOKE_ARCH = "granite-3-8b"


def _measure(model, params, prompts, gen, mesh, plan, low, reps: int):
    """Best-of-reps wall-clock of a full generate() call; returns
    (tok/s, tokens)."""
    b = prompts.shape[0]
    best = float("inf")
    toks = None
    for _ in range(reps):
        t0 = time.perf_counter()
        toks = generate(model, params, prompts, gen, mesh, plan,
                        lowered=low)
        jax.block_until_ready(toks)
        best = min(best, time.perf_counter() - t0)
    return b * gen / best, np.asarray(toks)


def run_cell(arch_name: str, *, smoke: bool, batch: int, prompt_len: int,
             gen: int, reps: int) -> List[str]:
    cfg = get_arch(arch_name)
    if smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)
    n = len(jax.devices())
    max_len = prompt_len + gen
    shape = ShapeConfig("serve", max_len, batch, "decode")

    base_plan = single_stage_plan(cfg.num_layers, dp=n, tp=1, micro_batch=1,
                                  grad_accum=1, zero=0, ckpt_layers=0)
    plan, report = tuned_serve_plan(cfg, batch=batch, max_len=max_len,
                                    n_devices=n)
    st = plan.stages[0]

    # group 1: predicted serve memory == lowered report, bitwise
    mesh = make_host_mesh(st.dp, st.tp)
    low = lower_plan(cfg, shape, plan, mesh)
    rep_mem = low.memory_report()
    est = estimate_serve_plan(cfg, shape, plan)
    assert est["mem_decode"] == rep_mem.peak_bytes, \
        f"serve cost model drifted from memory_report: " \
        f"{est['mem_decode']} != {rep_mem.peak_bytes}"
    rows = [emit(f"serve_throughput/predicted_mem_bitwise/{cfg.name}",
                 rep_mem.peak_bytes / 2**20,
                 f"MiB plan=dp{st.dp}_tp{st.tp}_z{st.zero}_"
                 f"{plan.kv_cache_dtype} tune_seconds="
                 f"{report.tune_seconds:.2f}")]

    base_mesh = make_host_mesh(n, 1)
    base_low = lower_plan(cfg, shape, base_plan, base_mesh)
    same_plan = plan.to_json() == base_plan.to_json()

    with jax.set_mesh(base_mesh):
        params, _ = model.init(jax.random.PRNGKey(0))
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (batch, prompt_len), 0,
            cfg.vocab_size).astype(jnp.int32)
        base_tps, base_toks = _measure(model, params, prompts, gen,
                                       base_mesh, base_plan, base_low, reps)
    if same_plan:
        tuned_tps, tuned_toks = base_tps, base_toks
    else:
        with jax.set_mesh(mesh):
            params, _ = model.init(jax.random.PRNGKey(0))
            prompts = jax.random.randint(
                jax.random.PRNGKey(1), (batch, prompt_len), 0,
                cfg.vocab_size).astype(jnp.int32)
            tuned_tps, tuned_toks = _measure(model, params, prompts, gen,
                                             mesh, plan, low, reps)

    # group 2: plan choice must not move numerics (bf16 plans; the int8
    # fallback intentionally perturbs logits and is exempt)
    tokens_match = bool((tuned_toks == base_toks).all())
    if plan.kv_cache_dtype == "bf16":
        assert tokens_match, "tuned plan changed the generated tokens"

    speedup = tuned_tps / base_tps
    rows += [
        emit(f"serve_throughput/baseline_tok_s/{cfg.name}", base_tps,
             f"plan=dp{n}_tp1_z0_bf16 reps={reps}"),
        emit(f"serve_throughput/tuned_tok_s/{cfg.name}", tuned_tps,
             f"plan=dp{st.dp}_tp{st.tp}_z{st.zero}_{plan.kv_cache_dtype} "
             f"same_plan_as_baseline={same_plan}"),
        emit(f"serve_throughput/speedup/{cfg.name}", speedup,
             f"tokens_match={tokens_match} "
             f"predicted_tok_s={report.throughput_tokens:.1f}"),
    ]
    return rows


def run(smoke: bool = False) -> List[str]:
    if smoke:
        return run_cell(SMOKE_ARCH, smoke=True, batch=4, prompt_len=16,
                        gen=8, reps=1)
    rows = []
    for arch in ("granite-3-8b", "qwen2-moe-a2.7b"):
        rows += run_cell(arch, smoke=True, batch=8, prompt_len=64,
                         gen=32, reps=3)
    return rows


# ---------------------------------------------------------------------------
# --trace: continuous batching vs static batching on a mixed-length trace
# ---------------------------------------------------------------------------

# prompt-length buckets with one long request + short tails each: static
# batching decodes every bucket until its LONGEST request finishes
# (head-of-line blocking, bucket after bucket), continuous batching
# retires the shorts immediately AND runs the four long tails in
# parallel across its slots
TRACE_MAX_LEN = 64
TRACE_SLOTS = 4
TRACE_PAGE = 8
TRACE_BUCKETS = ((4, (48, 2, 2, 2)), (8, (46, 2, 2, 2)),
                 (12, (46, 2, 2, 2)), (16, (44, 2, 2, 2)))


def _trace_requests(cfg):
    """One prompt batch per bucket (rows are the per-request prompts)."""
    out = []
    for i, (plen, gens) in enumerate(TRACE_BUCKETS):
        prompts = jax.random.randint(
            jax.random.PRNGKey(10 + i), (len(gens), plen), 0,
            cfg.vocab_size).astype(jnp.int32)
        out.append((prompts, gens))
    return out


def _make_static_steps(model, low, batch: int):
    """Compile the static path's prefill/decode programs ONCE — the
    engine amortizes its compiles across the whole trace, so the
    baseline must too or the ratio measures recompilation, not
    batching policy."""
    from repro.training.step import make_prefill_step, make_serve_step
    prefill = make_prefill_step(model, return_cache=True, lowered=low)
    serve = make_serve_step(model, batch=batch, max_len=TRACE_MAX_LEN,
                            donate=False, lowered=low)
    return prefill, serve


def _static_trace(prefill, serve, params, buckets):
    """Static baseline (generate() semantics, prebuilt steps): each
    bucket decodes until its LONGEST request finishes, at the engine's
    global max_len.  Returns (useful tok/s, per-bucket token arrays)."""
    from repro.models.zoo import pad_caches
    t0 = time.perf_counter()
    outs = []
    for prompts, gens in buckets:
        logits, caches = prefill.fn(params, {"tokens": prompts})
        caches = pad_caches(caches, TRACE_MAX_LEN - prompts.shape[1])
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out = [tok]
        for _ in range(max(gens) - 1):
            logits, caches = serve.fn(params, tok, caches)
            tok = jnp.argmax(logits[:, -1],
                             axis=-1).astype(jnp.int32)[:, None]
            out.append(tok)
        outs.append(np.asarray(jnp.concatenate(out, axis=1)))
    dt = time.perf_counter() - t0
    useful = sum(sum(gens) for _, gens in buckets)
    return useful / dt, outs


def _continuous_trace(eng, buckets):
    """Submit every request (FCFS, bucket order) and drain the engine.
    Returns (useful tok/s, {rid: tokens})."""
    rid = 0
    for prompts, gens in buckets:
        for r, g in enumerate(gens):
            eng.submit({"tokens": prompts[r:r + 1]}, g, rid=rid)
            rid += 1
    t0 = time.perf_counter()
    res = eng.run()
    dt = time.perf_counter() - t0
    useful = sum(sum(gens) for _, gens in buckets)
    return useful / dt, res


def run_trace(reps: int = 2) -> List[str]:
    """Continuous-vs-static headline on the mixed trace.  Token identity
    per request and the >= 1.25x tok/s ratio are asserted, not merely
    reported (docs/continuous-batching.md)."""
    from repro.serving import ContinuousBatchingEngine

    cfg = get_arch(SMOKE_ARCH).reduced()
    model = build_model(cfg)
    plan = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0)
    mesh = make_host_mesh(1, 1)
    low = lower_plan(cfg, None, plan, mesh)
    with jax.set_mesh(mesh):
        params, _ = model.init(jax.random.PRNGKey(0))
        buckets = _trace_requests(cfg)
        eng = ContinuousBatchingEngine(
            model, params, plan, mesh, slots=TRACE_SLOTS,
            max_len=TRACE_MAX_LEN, page_size=TRACE_PAGE, lowered=low)
        prefill, serve = _make_static_steps(
            model, low, batch=len(TRACE_BUCKETS[0][1]))
        # warmup: compile both paths' prefill/decode programs off-clock
        _static_trace(prefill, serve, params, buckets)
        _continuous_trace(eng, buckets)
        static_tps = cont_tps = 0.0
        refs, res = None, None
        for _ in range(reps):
            tps, refs = _static_trace(prefill, serve, params, buckets)
            static_tps = max(static_tps, tps)
            tps, res = _continuous_trace(eng, buckets)
            cont_tps = max(cont_tps, tps)

    # per-request token identity: the engine's tokens are the static
    # rows' prefixes (greedy decode is deterministic)
    rid = 0
    for ref, (_, gens) in zip(refs, buckets):
        for r, g in enumerate(gens):
            assert np.array_equal(res[rid], ref[r][:g]), \
                f"continuous tokens diverged from static (request {rid})"
            rid += 1
    speedup = cont_tps / static_tps
    assert speedup >= 1.25, \
        f"continuous/static tok/s ratio {speedup:.2f} below 1.25"
    n_req = sum(len(g) for _, g in TRACE_BUCKETS)
    return [
        emit(f"serve_throughput/trace_static_tok_s/{cfg.name}", static_tps,
             f"requests={n_req} buckets={len(TRACE_BUCKETS)} reps={reps}"),
        emit(f"serve_throughput/trace_continuous_tok_s/{cfg.name}",
             cont_tps, f"slots={TRACE_SLOTS} page_size={TRACE_PAGE} "
             f"max_len={TRACE_MAX_LEN}"),
        emit(f"serve_throughput/trace_speedup/{cfg.name}", speedup,
             "tokens_match=True floor=1.25"),
    ]


def rows_to_json(rows: List[str]) -> dict:
    out = []
    for r in rows:
        name, value, notes = r.split(",", 2)
        out.append({"name": name, "value": float(value), "notes": notes})
    return {"benchmark": "serve_throughput", "rows": out}


if __name__ == "__main__":
    if "--trace" in sys.argv:
        rows = run_trace()
    else:
        rows = run(smoke="--smoke" in sys.argv)
    if "--json" in sys.argv:
        path = sys.argv[sys.argv.index("--json") + 1]
        with open(path, "w") as f:
            json.dump(rows_to_json(rows), f, indent=2)
        print(f"wrote {path}")
