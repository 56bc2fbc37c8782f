"""Prefill/decode parity across every cache family the zoo serves
(docs/serving.md).

The serve search space moves *where* work runs (dp/tp/zero) and *how*
the KV cache is stored (bf16/int8); it must never move *what* the model
computes.  This suite pins the numerics the tuner is trusted not to
perturb, one test per contract:

1. **Teacher-forced decode == full-sequence prefill, per step.**  For
   each cache family — GQA self-attention (granite), MLA absorbed-decode
   latents (minicpm3), pure recurrent SSM state (xlstm), hybrid
   mamba+attention (zamba2), enc-dec cross-attention (whisper), and the
   VLM patch-prefix decoder (internvl2) — decode logits at step k match
   a fresh prefill over prompt+k tokens within bf16 tolerance, at EVERY
   step, not just the last.
2. **The int8 KV path is a bounded perturbation.**  ``quantize_caches``
   converts exactly the self-attention {k, v, pos} leaves, decode writes
   stay int8 (+f32 scales), and the quantized logits track the bf16
   decode within the per-token scale error — greedy argmax unchanged.
3. **Plan choice is invisible to generate().**  The serve tuner's plan
   and the hand-built dp-only baseline emit identical token ids on a
   reduced golden arch — the end-to-end acceptance criterion, in tier-1
   (benchmarks/serve_throughput.py asserts the same on the full smoke
   cell, with timing).

Model-level GQA parity on the *training* archs lives in
tests/test_arch_smoke.py; this suite owns the serve-specific surface
(frontend-stub families, quantized caches, the tuned-plan loop).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models.common import ExecConfig
from repro.models.zoo import build_model, pad_caches, quantize_caches

# one representative per cache family (reduced() configs)
FAMILY_ARCHS = {
    "gqa": "granite-3-8b",            # plain GQA self-attn {k, v, pos}
    "mla": "minicpm3-4b",             # MLA absorbed-decode latent cache
    "ssm": "xlstm-1.3b",              # pure recurrent state, no KV growth
    "hybrid": "zamba2-2.7b",          # interleaved mamba state + GQA KV
    "encdec": "whisper-small",        # self KV + frozen cross-attn KV
    "vlm": "internvl2-1b",            # GQA behind a patch-embed prefix
}

_EC = ExecConfig(ckpt_layers=0, remat_policy="none")
_B, _PROMPT, _STEPS = 2, 8, 3


def _prompt_batch(cfg, b, s, seed=0):
    """Tokens plus whatever frontend stub the family needs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    batch = {"tokens": jax.random.randint(ks[1], (b, s), 0,
                                          cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            ks[0], (b, cfg.num_patches, cfg.d_model),
            jnp.float32).astype(jnp.bfloat16)
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            ks[0], (b, cfg.encoder_seq, cfg.d_model),
            jnp.float32).astype(jnp.bfloat16)
    return batch


@pytest.fixture(scope="module", params=sorted(FAMILY_ARCHS),
                ids=sorted(FAMILY_ARCHS))
def family_setup(request):
    cfg = get_arch(FAMILY_ARCHS[request.param]).reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _decode_steps(model, params, caches, toks, start, steps):
    """Teacher-forced decode; returns last-position logits per step."""
    outs = []
    for k in range(steps):
        lg, caches = model.decode_fn(params, toks[:, start + k:start + k + 1],
                                     caches, _EC)
        outs.append(lg[:, -1])
    return outs, caches


def test_decode_matches_prefill_every_step(family_setup):
    """Contract 1: at every decode step k, cached decode logits equal a
    fresh full-sequence prefill over prompt+k tokens (bf16 tolerance —
    cached-vs-recomputed paths differ by accumulation order only)."""
    cfg, model, params = family_setup
    full = _prompt_batch(cfg, _B, _PROMPT + _STEPS)
    toks = full["tokens"]

    _, caches = model.prefill_fn(params, dict(full, tokens=toks[:, :_PROMPT]),
                                 _EC, True)
    caches = pad_caches(caches, _STEPS)
    got, _ = _decode_steps(model, params, caches, toks, _PROMPT, _STEPS)

    for k in range(_STEPS):
        ref, _ = model.prefill_fn(
            params, dict(full, tokens=toks[:, :_PROMPT + k + 1]), _EC, True)
        g = np.asarray(got[k], np.float32)
        w = np.asarray(ref[:, -1], np.float32)
        close = np.isclose(g, w, atol=0.3, rtol=0.3)
        assert close.mean() > 0.995, \
            f"step {k}: {(~close).sum()}/{close.size} logits diverged"
        assert np.max(np.abs(g - w)) < 1.0, f"step {k}"


def test_prefill_logits_deterministic(family_setup):
    """Same params + prompt -> bitwise-identical prefill logits; the
    parity contracts above are meaningful only if the baseline itself is
    stable run to run."""
    cfg, model, params = family_setup
    batch = _prompt_batch(cfg, _B, _PROMPT)
    a, _ = model.prefill_fn(params, batch, _EC, True)
    b, _ = model.prefill_fn(params, batch, _EC, True)
    assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# 2. the int8 KV-cache path
# ---------------------------------------------------------------------------

# families whose self-attn caches have the quantized read/write path
# (must track serve_space.int8_kv_supported)
_INT8_ARCHS = ("granite-3-8b", "zamba2-2.7b", "internvl2-1b")


@pytest.mark.parametrize("arch", _INT8_ARCHS)
def test_int8_decode_tracks_bf16(arch):
    """Quantized KV decode: writes stay int8+scales, logits track the
    bf16 decode within the quantization error, greedy tokens unchanged."""
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    full = _prompt_batch(cfg, _B, _PROMPT + _STEPS, seed=3)
    toks = full["tokens"]
    pre = dict(full, tokens=toks[:, :_PROMPT])

    _, c16 = model.prefill_fn(params, pre, _EC, True)
    g16, _ = _decode_steps(model, params, pad_caches(c16, _STEPS),
                           toks, _PROMPT, _STEPS)

    _, craw = model.prefill_fn(params, pre, _EC, True)
    c8 = pad_caches(quantize_caches(craw), _STEPS)
    g8, c8_out = _decode_steps(model, params, c8, toks, _PROMPT, _STEPS)

    # decode preserved the quantized layout end to end: every
    # self-attn {k, v, pos} dict still holds int8 values + f32 scales
    quantized = []

    def walk(node):
        if isinstance(node, dict):
            if "k" in node and "v" in node and "pos" in node:
                quantized.append(node)
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)
    walk(c8_out)
    assert quantized, "no self-attn caches found"
    for node in quantized:
        assert node["k"].dtype == jnp.int8 and node["v"].dtype == jnp.int8
        assert node["k_scale"].dtype == jnp.float32
        assert node["v_scale"].dtype == jnp.float32

    a16 = np.asarray(jnp.stack(g16, 1), np.float32)
    a8 = np.asarray(jnp.stack(g8, 1), np.float32)
    err = np.max(np.abs(a16 - a8))
    assert err < 0.5
    # greedy tokens: where the bf16 top-2 margin exceeds the measured
    # quantization error the argmax CANNOT move (at random init many
    # logits are near-uniform, so an unconditional argmax equality would
    # test tie-breaking, not the cache path)
    top2 = np.sort(a16, axis=-1)[..., -2:]
    decisive = (top2[..., 1] - top2[..., 0]) > 2.0 * err
    agree = a16.argmax(-1) == a8.argmax(-1)
    assert agree[decisive].all()
    assert decisive.any() or agree.mean() > 0.5


def test_quantize_caches_touches_only_self_attn():
    """MLA latents, SSM/mLSTM state, and pos-less cross-attn caches have
    no quantized path and must pass through quantize_caches unchanged."""
    for arch in ("minicpm3-4b", "xlstm-1.3b", "whisper-small"):
        cfg = get_arch(arch).reduced()
        model = build_model(cfg)
        caches = model.init_caches(2, 16)
        out = quantize_caches(caches)
        before = jax.tree_util.tree_leaves_with_path(caches)
        after = jax.tree_util.tree_leaves_with_path(out)
        if arch == "whisper-small":
            # self KV {k, v, pos} quantizes; the cross cache {k, v} (no
            # pos — it is written once at prefill) must not
            keys_after = {jax.tree_util.keystr(p) for p, _ in after}
            assert any("k_scale" in k and "self" in k for k in keys_after)
            assert not any("scale" in k and "cross" in k
                           for k in keys_after)
            cross_b = [(p, l) for p, l in before
                       if "cross" in jax.tree_util.keystr(p)]
            cross_a = [(p, l) for p, l in after
                       if "cross" in jax.tree_util.keystr(p)]
            for (pb, lb), (pa, la) in zip(cross_b, cross_a):
                assert lb.dtype == la.dtype and lb.shape == la.shape
        else:
            assert len(before) == len(after)
            for (pb, lb), (pa, la) in zip(before, after):
                assert jax.tree_util.keystr(pb) == jax.tree_util.keystr(pa)
                assert lb.dtype == la.dtype


def test_int8_support_table_matches_cache_shape():
    """serve_space.int8_kv_supported says yes exactly when the arch's
    cache tree has the {k, v, pos} self-attn dicts quantize_caches (and
    the decode read path) handle."""
    from repro.configs.base import list_archs
    from repro.core.serve_space import int8_kv_supported

    def has_quantizable(caches):
        found = []

        def walk(node):
            if isinstance(node, dict):
                if "k" in node and "v" in node and "pos" in node:
                    found.append(True)
                    return
                for v in node.values():
                    walk(v)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v)
        walk(caches)
        return bool(found)

    for arch in list_archs():
        cfg = get_arch(arch).reduced()
        model = build_model(cfg)
        caches = jax.eval_shape(lambda m=model: m.init_caches(2, 16))
        if int8_kv_supported(cfg):
            assert has_quantizable(caches), arch
        # (the converse is intentionally weaker: whisper HAS a
        # quantizable self cache but is excluded because its cross cache
        # shares the decode path without a quantized read)


# ---------------------------------------------------------------------------
# 3. tuned plan == baseline plan, token for token
# ---------------------------------------------------------------------------


def test_tuned_serve_plan_generates_identical_tokens():
    """The acceptance criterion, end to end on a reduced golden arch:
    generate() under the serve tuner's winning plan emits exactly the
    token ids the hand-built dp-only baseline emits."""
    from repro.core.plan import single_stage_plan
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import generate, tuned_serve_plan

    cfg = get_arch("granite-3-8b").reduced()
    model = build_model(cfg)
    n = len(jax.devices())
    batch, plen, gen = 2, 8, 4

    plan, report = tuned_serve_plan(cfg, batch=batch, max_len=plen + gen,
                                    n_devices=n)
    assert report.plan is plan and not report.infeasible
    base = single_stage_plan(cfg.num_layers, dp=n, tp=1, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0)

    toks = {}
    for name, p in (("base", base), ("tuned", plan)):
        st = p.stages[0]
        mesh = make_host_mesh(st.dp, st.tp)
        with jax.set_mesh(mesh):
            params, _ = model.init(jax.random.PRNGKey(0))
            prompts = jax.random.randint(jax.random.PRNGKey(1),
                                         (batch, plen), 0,
                                         cfg.vocab_size).astype(jnp.int32)
            toks[name] = np.asarray(generate(model, params, prompts, gen,
                                             mesh, p))
    assert toks["base"].shape == (batch, gen)
    assert (toks["base"] == toks["tuned"]).all(), \
        "tuned serve plan changed generated tokens"
