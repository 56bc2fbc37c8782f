"""Run the main path once on a TPU v5e and check what comes out.

    python chip_smoke.py               # one chip: tune -> lower -> train, serve
    python chip_smoke.py --four-chip   # four chips: tuned, zero and 2-stage
                                       # pipeline plans side by side

One chip: ``granite-3-8b`` at its published widths, cut in depth to 4 of
its 40 layers, is tuned for one device (space "mist"), lowered, and
trained for a few steps through ``TrainLoop``; then the ``serve`` space's
plan serves 8 requests through the continuous-batching engine.  Four
chips: the same widths at a depth one chip cannot hold, the tuned plan
and a 2-stage pipeline plan against the ``zero`` preset on one batch.

Every phase checks its outputs (finite losses, loss against a plain
forward, kernels lowered for the chip, offloaded state in host memory,
served tokens against ``generate()``).  The last line of standard output
is ``{"ok": true, "device": {...}}`` only when every phase passed; the
script exits non-zero without it, and refuses to run on anything but a
TPU v5e.  Lines before it are bring-up observations, not benchmarks.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import pathlib
import shutil
import statistics
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
OUT_DIR = REPO / "results" / "chip_smoke"

ARCH = "granite-3-8b"
SEED = 0
SEQ = 4096
GLOBAL_BATCH = 8
ONE_CHIP_LAYERS = 4
FOUR_CHIP_LAYERS = 8
TRAIN_STEPS = 5
FOUR_CHIP_STEPS = 3
# first-step loss against the plain forward: bf16 weights and activations
# carry 8 significant bits, so two summation orders of the same math agree
# to a few bf16 epsilons (2**-8) of the loss, relative
LOSS_RTOL = 2 * 2.0**-8
# second-step loss, after one AdamW update, against the plain reference
# (or, on four chips, between plans): the first update moves each weight
# by about lr * sign(g), so gradient elements near zero may take either
# sign across programs; plans on four v5e chips agreed to 1.5e-4 at step 2
STEP2_RTOL = 1e-3
SERVE_PROMPT_LENS = (256, 2048, 512, 1024, 2048, 256, 1024, 512)
SERVE_GEN = 32
SERVE_SLOTS = 4
SERVE_PAGE = 32
# where the multi-slot engine's greedy token differs from generate()'s,
# the plain prefill's logits of the two tokens may differ by at most this
# much: a near-tie that bf16 decode numerics can break either way
NEAR_TIE = 2.0**-4
# the first AdamW step, recomputed in float64 on the host from the f32
# state: f32 rounding of mu^2/nu, and of the master update (in units of lr)
ADAM_MOMENT_RTOL = 1e-4
ADAM_MASTER_TOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _gib(x) -> str:
    return "not reported" if x is None else f"{x / 2**30:.3f} GiB"


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _with_attention(plan, attn: str):
    return plan.replace(attn_impl=attn, use_pallas=attn == "pallas")


def _batch(cfg, seq: int, global_batch: int, step: int):
    import jax.numpy as jnp
    from repro.training.data import BatchSpec, SyntheticLM
    data = SyntheticLM(BatchSpec(global_batch=global_batch, seq_len=seq,
                                 vocab_size=cfg.vocab_size), seed=SEED)
    return {k: jnp.asarray(v) for k, v in data.batch(step).items()}


def _step_batch(plan, batch):
    """The pipeline step takes its microbatches as a leading (G, ...) dim."""
    if plan.num_stages == 1:
        return batch
    G = plan.grad_accum
    return {k: v.reshape((G, v.shape[0] // G) + v.shape[1:])
            for k, v in batch.items()}


def _build(model, plan, shape):
    """(mesh, lowered plan, train step) for ``plan`` on the attached
    devices: the single-stage SPMD step, or the pipeline step."""
    from repro.launch.mesh import make_plan_mesh
    from repro.lowering import lower_plan
    from repro.parallel.pipeline import make_pipeline_train_step
    from repro.training.step import make_train_step
    st = plan.stages[0]
    mesh = make_plan_mesh(st.dp, st.tp, stages=plan.num_stages)
    low = lower_plan(model.cfg, shape, plan, mesh)
    make = make_pipeline_train_step if plan.num_stages > 1 \
        else make_train_step
    return mesh, low, make(model, plan, mesh, lowered=low)


def _compile(model, plan, mesh, low, step, batch):
    """AOT-compile the train step on abstract state; (seconds, HLO)."""
    import jax
    from repro.training import optimizer as OPT
    abstract = jax.eval_shape(lambda: OPT.init_state(
        model.init(jax.random.PRNGKey(SEED))[0], low.axes_table,
        plan.stages[0]))
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        abstract, step.state_shardings)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        hlo = step.fn.lower(state, _step_batch(plan, batch)) \
            .compile().as_text()
    return time.perf_counter() - t0, hlo


def tune_and_compile(model, shape, n_devices: int, space: str, hw, batch,
                     *, attn: str = "pallas", log=print, **spec_kw):
    """Tune through ``repro.core.tuner.tune`` with the platform's
    calibration profile, as ``repro.launch.train --tune`` does, and
    compile the plan's train step.  A plan the compiler refuses fails the
    phase: the tuner's memory model is what picks a plan that fits.
    Returns (plan, predicted bytes, (mesh, lowered, step), compile
    seconds, HLO text)."""
    import jax
    from repro.calibration import load_profile
    from repro.core.tuner import tune
    from repro.lowering import memory_consistency
    cfg = model.cfg
    profile = load_profile()
    rep = tune(cfg, shape, n_devices, space=space, workers=1, hw=hw,
               profile=profile, **spec_kw)
    check(rep.plan is not None, f"{space}: the tuner found no plan")
    plan = _with_attention(rep.plan, attn)
    predicted = memory_consistency(cfg, shape, plan, hw=hw,
                                   profile=profile)["predicted_bytes"]
    log(f"# {space}{' ' + str(spec_kw) if spec_kw else ''} tuned in "
        f"{rep.tune_seconds:.2f}s ({cfg.num_layers} layers, seq "
        f"{shape.seq_len}, global batch {shape.global_batch}, {n_devices} "
        f"devices, calibration profile {profile.platform!r}): predicted "
        f"step {rep.objective:.4f}s, peak {_gib(predicted)}")
    log(rep.plan.to_json())
    built = _build(model, plan, shape)
    try:
        seconds, hlo = _compile(model, plan, *built, batch)
    except jax.errors.JaxRuntimeError as e:
        raise SmokeFailure(
            f"{space}: the compiler refused the tuner's plan (predicted "
            f"{_gib(predicted)}): {str(e).splitlines()[0]}") from e
    return plan, predicted, built, seconds, hlo


def _plain_mean_loss(model, params, batch) -> float:
    """Mean next-token loss of ``batch`` from a plain forward: no remat,
    no offload, naive attention, one sequence at a time."""
    import jax
    import numpy as np
    from repro.models.common import ExecConfig
    ec = ExecConfig(ckpt_layers=0, offload_layers=0, remat_policy="none",
                    attn_impl="naive", use_pallas=False)
    loss = jax.jit(lambda p, b: model.loss_fn(p, b, ec))
    rows = batch["tokens"].shape[0]
    return float(np.mean([
        float(loss(params, {k: v[i:i + 1] for k, v in batch.items()}))
        for i in range(rows)]))


def _plain_adamw_step(model, params, batch, adam):
    """bf16 weights after one AdamW step on ``batch`` from the f32 master
    copy of ``params``: per-sequence ``jax.grad`` of ``model.loss_fn``
    (every layer rematerialized and the jnp blocked attention, so that
    the gradient fits the device), averaged in f32, clipped by global
    norm, and the update written out in jnp."""
    import jax
    import jax.numpy as jnp
    from repro.models.common import ExecConfig
    ec = ExecConfig(ckpt_layers=model.cfg.num_layers, remat_policy="full",
                    attn_impl="blocked", use_pallas=False)

    @functools.partial(jax.jit, donate_argnums=0)
    def accumulate(acc, p, b):
        g = jax.grad(lambda p_: model.loss_fn(p_, b, ec))(p)
        return jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)

    rows = batch["tokens"].shape[0]
    acc = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    for i in range(rows):
        acc = accumulate(acc, params,
                         {k: v[i:i + 1] for k, v in batch.items()})

    @jax.jit
    def update(p, acc):
        g = jax.tree.map(lambda a: a / rows, acc)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, adam.grad_clip / (gnorm + 1e-12))

        def one(w, x):
            w32, x = w.astype(jnp.float32), x * clip
            mu, nu = (1 - adam.b1) * x, (1 - adam.b2) * x * x
            step = (mu / (1 - adam.b1)) / (jnp.sqrt(nu / (1 - adam.b2))
                                           + adam.eps)
            w32 = w32 - adam.lr * (step + adam.weight_decay * w32)
            return w32.astype(w.dtype)
        return jax.tree.map(one, p, g)

    return update(params, acc)


def reference_losses(model, batch0, batch1, adam) -> tuple:
    """The plain reference of the first two steps, on weights made from
    the seed exactly as ``init_sharded_state`` makes them: the loss of
    ``batch0``, and the loss of ``batch1`` after one plain AdamW step on
    ``batch0`` (``_plain_adamw_step``)."""
    import jax
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(SEED))[0])()
    first = _plain_mean_loss(model, params, batch0)
    params = _plain_adamw_step(model, params, batch0, adam)
    second = _plain_mean_loss(model, params, batch1)
    del params
    return first, second


def _host_value(leaf):
    """A (possibly WO/OO-split) optimizer leaf as one host array."""
    import numpy as np
    from repro.training.optimizer import is_split
    if is_split(leaf):
        return np.concatenate([np.asarray(leaf["host"]),
                               np.asarray(leaf["dev"])])
    return np.asarray(leaf)


def first_adam_step_error(master0, state, name: str, adam) -> tuple:
    """How far tensor ``name``'s first AdamW update is from the rule.

    After step 1, mu = (1-b1) g and nu = (1-b2) g^2, so mu^2/nu is the
    constant (1-b1)^2/(1-b2) wherever g != 0, and the new master weight
    follows from the old one, mu and nu alone.  Returns (relative error of
    mu^2/nu, master error in units of lr), host and device slices alike."""
    import numpy as np
    mu, nu = _host_value(state["mu"][name]), _host_value(state["nu"][name])
    master = _host_value(state["master"][name])
    c1, c2 = 1 - adam.b1, 1 - adam.b2
    live = nu > 0
    ratio = mu[live] ** 2 / nu[live]
    moment_err = float(np.max(np.abs(ratio / (c1 * c1 / c2) - 1)))
    want = master0 - adam.lr * ((mu / c1) / (np.sqrt(nu / c2) + adam.eps)
                                + adam.weight_decay * master0)
    return moment_err, float(np.max(np.abs(master - want)) / adam.lr)


def _offloaded_leaves(state):
    """Memory kinds of the host parts of WO/OO-split optimizer leaves."""
    from repro.training.optimizer import is_split
    return [leaf["host"].sharding.memory_kind
            for entry in ("master", "mu", "nu")
            for leaf in state[entry].values() if is_split(leaf)]


def train_phase(cfg, *, seq: int, global_batch: int, steps: int,
                out_dir: pathlib.Path, hw=None, log=print) -> dict:
    """Tune ``cfg`` for one device, run the plan through ``lower_plan`` ->
    ``init_sharded_state`` -> ``make_train_step`` -> ``TrainLoop`` and
    check the result.  Returns the observations."""
    import jax
    import numpy as np
    from repro.calibration import load_profile
    from repro.configs.base import ShapeConfig
    from repro.core.hardware import V5E
    from repro.kernels import ops
    from repro.models.zoo import build_model
    from repro.training.loop import LoopConfig, TrainLoop
    from repro.training.optimizer import AdamConfig, is_split
    from repro.training.step import init_sharded_state

    hw = hw or V5E
    shape = ShapeConfig("smoke", seq, global_batch, "train")
    model = build_model(cfg)
    batch0 = _batch(cfg, seq, global_batch, 0)
    # the cost model prices flash-style tiled attention, so the run takes
    # the Pallas kernel the tuner's numbers describe
    log("# attention: attn_impl=pallas use_pallas=True (the tiled attention "
        "the cost model prices)")
    plan, predicted, (mesh, low, step), compile_s, hlo = tune_and_compile(
        model, shape, 1, "mist", hw, batch0, log=log)
    st = plan.stages[0]
    lowered_peak = low.memory_report(
        hw=hw, cp=load_profile().cost_params()).peak_bytes
    if ops._on_tpu():
        check("tpu_custom_call" in hlo,
              "train step has no tpu_custom_call: a kernel ran in "
              "interpret mode")
    log(f"# train step compiled in {compile_s:.2f}s; tpu_custom_call in "
        f"HLO: {'tpu_custom_call' in hlo}")

    ref, ref2 = reference_losses(model, batch0,
                                 _batch(cfg, seq, global_batch, 1),
                                 AdamConfig())
    ckpt_dir = out_dir / "ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    with jax.set_mesh(mesh):
        state, shardings = init_sharded_state(
            model, plan, mesh, jax.random.PRNGKey(SEED), lowered=low)
        offloaded = _offloaded_leaves(state)
        if st.wo > 0 or st.oo > 0:
            check(len(offloaded) > 0, "plan offloads state, none is split")
        check(all(k == "pinned_host" for k in offloaded),
              f"offloaded leaves not in pinned_host: {set(offloaded)}")
        log(f"# offloaded optimizer leaves: {len(offloaded)}, memory kinds "
            f"{sorted(set(offloaded))}")

        # the smallest offloaded tensor (any tensor when none is) is
        # checked against the AdamW rule after the first step
        leaves = {n: l for n, l in state["mu"].items() if is_split(l)} \
            or state["mu"]
        probe = min(leaves, key=lambda n: _host_value(leaves[n]).size)
        master0 = _host_value(state["master"][probe])
        times, first = [], {}

        def timed_step(s, b):
            t = time.perf_counter()
            out = jax.block_until_ready(step.fn(s, b))
            times.append(time.perf_counter() - t)
            if not first:
                first["err"] = first_adam_step_error(master0, out[0], probe,
                                                     AdamConfig())
            return out

        loop = TrainLoop(
            timed_step, state, lambda i: _batch(cfg, seq, global_batch, i),
            ckpt_dir=str(ckpt_dir),
            cfg=LoopConfig(total_steps=steps, ckpt_every=steps, keep_ckpts=1,
                           validate_delay=False, max_restarts=0),
            state_shardings=shardings, meta={"arch": cfg.name})
        del state
        stats = loop.run()
        final_kinds = _offloaded_leaves(loop.state)
        del loop
    losses = stats.losses
    log(f"# losses: {losses}; restarts={stats.restarts} "
        f"rollbacks={stats.rollbacks}")
    check(len(losses) == steps and all(np.isfinite(losses)),
          f"losses not finite: {losses}")
    check(stats.restarts == 0 and stats.rollbacks == 0,
          f"restarts={stats.restarts} rollbacks={stats.rollbacks}")
    check(final_kinds == offloaded, "offloaded leaves moved after steps")
    moment_err, master_err = first["err"]
    log(f"# first AdamW step of {probe} (host and device slices): "
        f"mu^2/nu rel err {moment_err:.2e}, master err {master_err:.2e} lr")
    check(moment_err <= ADAM_MOMENT_RTOL and master_err <= ADAM_MASTER_TOL,
          f"{probe}: first AdamW step off the rule")
    rel = abs(losses[0] - ref) / abs(ref)
    log(f"# first-step loss {losses[0]:.6f} vs plain forward {ref:.6f}: "
        f"rel diff {rel:.2e} (tolerance {LOSS_RTOL:.2e})")
    check(rel <= LOSS_RTOL, f"first-step loss {losses[0]} vs reference {ref}")
    rel2 = abs(losses[1] - ref2) / abs(ref2)
    log(f"# second-step loss {losses[1]:.6f} vs plain AdamW step + forward "
        f"{ref2:.6f}: rel diff {rel2:.2e} (tolerance {STEP2_RTOL:.2e})")
    check(rel2 <= STEP2_RTOL,
          f"second-step loss {losses[1]} vs reference {ref2}")
    peak = _peak_bytes(jax.devices()[0])
    log(f"# device peak_bytes_in_use {_gib(peak)}; lowered memory_report "
        f"{_gib(lowered_peak)}; tuner predicted {_gib(predicted)}")
    median = statistics.median(times[1:]) if len(times) > 1 else None
    log(f"# step seconds (block_until_ready): {times}; median after the "
        f"first: {median}")
    return {"plan": json.loads(plan.to_json()), "losses": losses,
            "reference_losses": [ref, ref2], "compile_s": compile_s,
            "step_s": times, "median_step_s": median,
            "peak_bytes_in_use": peak, "lowered_bytes": lowered_peak,
            "predicted_bytes": predicted,
            "offloaded_leaves": len(offloaded)}


def _first_divergence(got, want) -> int:
    """Index of the first differing token, -1 when the two agree."""
    import numpy as np
    diff = np.nonzero(np.asarray(got) != np.asarray(want))[0]
    return int(diff[0]) if len(diff) else -1


def tie_gap(prefill, params, prompt, want, got, k: int) -> float:
    """How far apart the logits of ``want[k]`` and ``got[k]`` are in the
    prefill of ``prompt`` followed by ``want[:k]``: the reference's view
    of the first step at which two greedy decodes part."""
    import jax.numpy as jnp
    toks = jnp.concatenate(
        [prompt, jnp.asarray(want[:k], prompt.dtype)[None]], axis=1)
    logits = prefill.fn(params, {"tokens": toks})[0, -1]
    return abs(float(logits[int(want[k])] - logits[int(got[k])]))


def serve_phase(cfg, *, prompt_lens, gen: int, slots: int, page: int,
                log=print) -> dict:
    """Serve one request per prompt length through the continuous-batching
    engine on the ``serve`` space's plan, with ``slots`` decode slots and
    again with one.  With one slot every request's greedy tokens must
    equal ``generate()`` at the same horizon; the engine's cache bytes
    must equal the derived paged layout.

    With several slots the TPU's decode numerics differ from the batch-1
    program's at bf16 rounding, so a greedy near-tie may break the other
    way.  Each request must then agree with ``generate()`` up to a step
    at which the two tokens' logits lie within ``NEAR_TIE`` of each other
    (``tie_gap``); a slot decoding from wrong cache pages parts at a
    decisive step and fails."""
    import jax
    import numpy as np
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_plan_mesh
    from repro.launch.serve import generate, run_continuous, tuned_serve_plan
    from repro.lowering import lower_plan
    from repro.lowering.cache_layout import concrete_paged_cache_bytes
    from repro.models.zoo import build_model
    from repro.training.step import make_prefill_step

    max_len = max(prompt_lens) + gen
    plan, rep = tuned_serve_plan(cfg, batch=slots, max_len=max_len,
                                 n_devices=1, page_grid=(0, page))
    st = plan.stages[0]
    ps = plan.page_size or page
    log(f"# tuned serve plan: dp={st.dp} tp={st.tp} "
        f"kv={plan.kv_cache_dtype} page_size={plan.page_size} (engine pages "
        f"{ps}); predicted {rep.throughput_tokens:.1f} tok/s")
    mesh = make_plan_mesh(st.dp, st.tp)
    low = lower_plan(cfg, ShapeConfig("serve", max_len, slots, "decode"),
                     plan, mesh)
    model = build_model(cfg)
    key = jax.random.PRNGKey(SEED + 1)
    gens = [gen] * len(prompt_lens)
    with jax.set_mesh(mesh):
        params = jax.jit(lambda: model.init(jax.random.PRNGKey(SEED))[0])()
        prompts = [jax.random.randint(jax.random.fold_in(key, i), (1, n), 0,
                                      cfg.vocab_size).astype(np.int32)
                   for i, n in enumerate(prompt_lens)]
        t0 = time.perf_counter()
        res, eng = run_continuous(model, params, prompts, gens, mesh, plan,
                                  slots=slots, page_size=ps, max_len=max_len,
                                  lowered=low)
        serve_s = time.perf_counter() - t0
        res1, _ = run_continuous(model, params, prompts, gens, mesh, plan,
                                 slots=1, page_size=ps, max_len=max_len,
                                 lowered=low)
        want = [np.asarray(generate(model, params, p, gen, mesh, plan,
                                    lowered=low, max_len=max_len))[0]
                for p in prompts]
        want_bytes = int(concrete_paged_cache_bytes(
            cfg, slots, max_len, ps, plan.kv_cache_dtype, dp_size=st.dp,
            tp_size=st.tp))
        got_bytes = eng.memory_bytes()
        one = [_first_divergence(res1[i], w) for i, w in enumerate(want)]
        many = [_first_divergence(res[i], w) for i, w in enumerate(want)]
        prefill = make_prefill_step(model, lowered=low)
        gaps = [None if k <= 0 else tie_gap(prefill, params, prompts[i],
                                            want[i], res[i], k)
                for i, k in enumerate(many)]
    same = one.count(-1)
    log(f"# served {len(prompts)} requests (prompts {list(prompt_lens)}, "
        f"{gen} new tokens each, {slots} slots) in {serve_s:.2f}s including "
        f"compilation; {eng.steps_run} decode steps")
    log(f"# token-identical to generate(): {same}/{len(prompts)} with 1 "
        f"slot; with {slots} slots {many.count(-1)}/{len(prompts)} (first "
        f"differing token per request, -1 = none: {many}; prefill logit "
        f"gap there {gaps}, near-tie bound {NEAR_TIE})")
    log(f"# paged cache bytes {got_bytes} vs derived {want_bytes}")
    check(same == len(prompts),
          f"{len(prompts) - same} requests diverged from generate(): {one}")
    check(all(m != 0 for m in many),
          f"{slots}-slot engine differs at the prefill token: {many}")
    check(all(g is None or g <= NEAR_TIE for g in gaps),
          f"{slots}-slot engine parts from generate() at a decisive step: "
          f"logit gaps {gaps}")
    check(got_bytes == want_bytes, "paged cache bytes != derived layout")
    return {"requests": len(prompts), "identical": same,
            "identical_with_slots": many.count(-1), "first_divergence": many,
            "tie_gaps": gaps,
            "decode_steps": eng.steps_run, "serve_s": serve_s,
            "cache_bytes": got_bytes}


def four_chip_phase(cfg, *, seq: int, global_batch: int, steps: int,
                    n_devices: int = 4, hw=None, log=print) -> dict:
    """Tune ``cfg`` for ``n_devices``, run the tuned plan and a 2-stage
    pipeline plan, and compare their first two losses with the ``zero``
    preset's plan on the same batch and chips."""
    import jax
    import numpy as np
    from repro.configs.base import ShapeConfig
    from repro.core.hardware import V5E
    from repro.models.zoo import build_model
    from repro.training.step import init_sharded_state

    hw = hw or V5E
    shape = ShapeConfig("smoke", seq, global_batch, "train")
    model = build_model(cfg)
    batch = _batch(cfg, seq, global_batch, 0)
    # The 2-stage plan comes from the zero space (full remat): the pipeline
    # step differentiates a scan over every microbatch, so it keeps all G
    # microbatches' activations where the cost model prices S in flight,
    # and the mist space's 2-stage plan (no remat) is refused at about
    # twice its predicted bytes.  It takes the jnp flash-style attention
    # (same tiling, no kernel): Mosaic kernels are partitioned only by a
    # fully manual shard_map, and the pipeline's is manual over 'stage'.
    runs = (("mist", "mist", "pallas", {}), ("zero", "zero", "pallas", {}),
            ("pipeline", "zero", "blocked", {"stage_counts": (2,)}))
    out = {}
    for name, space, attn, kw in runs:
        log(f"# {name}: attention {attn}")
        plan, predicted, (mesh, low, step), compile_s, _ = \
            tune_and_compile(model, shape, n_devices, space, hw, batch,
                             attn=attn, log=log, **kw)
        if name == "pipeline":
            check(plan.num_stages == 2, "pipeline plan is not 2-stage")
        with jax.set_mesh(mesh):
            state, _ = init_sharded_state(model, plan, mesh,
                                          jax.random.PRNGKey(SEED),
                                          lowered=low)
            losses = []
            for _ in range(steps):
                state, m = step.fn(state, _step_batch(plan, batch))
                losses.append(float(m["loss"]))
            del state
        gc.collect()
        peaks = [_peak_bytes(d) for d in jax.devices()]
        log(f"# {name}: compiled in {compile_s:.1f}s; losses {losses}; "
            f"per-device peak_bytes_in_use (max since start) "
            f"{[_gib(p) for p in peaks]}; predicted {_gib(predicted)}")
        check(all(np.isfinite(losses)), f"{name}: losses not finite")
        out[name] = {"plan": json.loads(plan.to_json()), "losses": losses,
                     "compile_s": compile_s, "predicted_bytes": predicted,
                     "peak_bytes_in_use": peaks}
    peaks = out["mist"]["peak_bytes_in_use"]
    if all(p is not None for p in peaks):
        check(min(peaks) >= 0.5 * max(peaks),
              f"memory lands unevenly across devices: {peaks}")
    # step 1 checks the forward; step 2, after one update, the gradient
    # (the pipeline plan's comes from the jnp attention, not the kernel)
    for i, tol in ((0, LOSS_RTOL), (1, STEP2_RTOL)):
        ref = out["zero"]["losses"][i]
        for name in ("mist", "pipeline"):
            rel = abs(out[name]["losses"][i] - ref) / abs(ref)
            log(f"# {name} step-{i + 1} loss vs zero preset: rel diff "
                f"{rel:.2e} (tolerance {tol:.2e})")
            check(rel <= tol, f"{name} step-{i + 1} loss differs from zero")
    return out


def device_line():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip plan comparison")
    args = ap.parse_args(argv)

    dev = device_line()
    print(f"# device: {json.dumps(dev)}", flush=True)
    if dev["platform"] != "tpu":
        print("# refused: no TPU attached", file=sys.stderr)
        return 1
    want = 4 if args.four_chip else 1
    if dev["count"] < want:
        print(f"# refused: needs {want} chips, found {dev['count']}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(REPO / "src"))
    from repro.configs.base import get_arch
    from repro.core.hardware import V5E, spec_for_device_kind
    from repro.launch.cache import enable_compile_cache

    hw = spec_for_device_kind(dev["kind"])      # unknown chips raise
    if hw is not V5E:
        print(f"# refused: {dev['kind']} is not a TPU v5e", file=sys.stderr)
        return 1
    print(f"# compilation cache: {enable_compile_cache()}", flush=True)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    full = get_arch(ARCH)

    def log(msg):
        print(msg, flush=True)

    if args.four_chip:
        result = {"four_chip": four_chip_phase(
            full.replace(num_layers=FOUR_CHIP_LAYERS), seq=SEQ,
            global_batch=GLOBAL_BATCH, steps=FOUR_CHIP_STEPS, hw=hw,
            log=log)}
    else:
        cfg = full.replace(num_layers=ONE_CHIP_LAYERS)
        result = {"train": train_phase(cfg, seq=SEQ,
                                       global_batch=GLOBAL_BATCH,
                                       steps=TRAIN_STEPS, out_dir=OUT_DIR,
                                       hw=hw, log=log)}
        gc.collect()
        result["serve"] = serve_phase(cfg, prompt_lens=SERVE_PROMPT_LENS,
                                      gen=SERVE_GEN, slots=SERVE_SLOTS,
                                      page=SERVE_PAGE, log=log)
    (OUT_DIR / ("four_chip.json" if args.four_chip else "one_chip.json")
     ).write_text(json.dumps({"device": dev, **result}, indent=1))
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
