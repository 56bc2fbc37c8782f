"""The shared KV/state-cache layout: one derivation, two evaluation modes.

The serve-side twin of tests/test_state_layout.py.  Contracts pinned:

1. **Symbolic == concrete == oracle, bitwise.**  ``cache_bytes`` runs
   the same formula over Exprs (``SYMBOLIC_OPS``) and floats
   (``CONCRETE_OPS``); both must agree bit for bit with each other —
   and with ``stage_cache_bytes``, the independent walk over the
   PartitionSpec tables ``cache_specs`` actually emits — on randomized
   serve shapes (arch x batch x max_len x dp x tp x kv dtype).

2. **The key table mirrors the sharder.**  ``SEQ_CACHE_KEYS`` is a
   jax-free literal copy of ``sharding._SEQ_LEAF_SEQ_DIM``; drift in
   either is a silent cost-model/runtime split.

3. **The serve cost model == the lowered report, bitwise.**
   ``estimate_serve_plan``'s mem_decode/mem_prefill equal
   ``memory_report().peak_bytes`` of the matching lowering — the PR-5
   two-evaluation contract, extended to serve shapes — including the
   int8 KV path and the compiled-tape evaluation the tuner sweeps with.
"""
import numpy as np
import pytest
from jax.sharding import AbstractMesh

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.configs.base import ShapeConfig, get_arch, list_archs
from repro.core import symbolic as S
from repro.core.costmodel import ServeCostModel, estimate_serve_plan
from repro.core.plan import single_stage_plan
from repro.lowering.cache_layout import (SEQ_CACHE_KEYS, cache_bytes,
                                         concrete_cache_bytes,
                                         derive_cache_layout,
                                         symbolic_cache_bytes)
from repro.lowering.lower import lower_plan
from repro.lowering.memory import stage_cache_bytes
from repro.lowering.state_layout import CONCRETE_OPS

# every cache family in the zoo: GQA dense/moe, MLA latent, SSM state,
# hybrid mamba+attn, enc-dec cross-attn, vlm
_ARCHS = ("granite-3-8b", "qwen2-moe-a2.7b", "minicpm3-4b",
          "xlstm-1.3b", "zamba2-2.7b", "whisper-small", "internvl2-1b")


def _concrete_via_specs(arch, batch, max_len, dp, tp, kv):
    """The oracle: lower a real plan and walk the actual spec tables."""
    cfg = get_arch(arch).reduced()
    plan = single_stage_plan(cfg.num_layers, dp=dp, tp=tp, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0,
                             kv_cache_dtype=kv)
    mesh = AbstractMesh((dp, tp), ("data", "model"))
    low = lower_plan(cfg, None, plan, mesh)
    shape = ShapeConfig("serve", max_len, batch, "decode")
    return stage_cache_bytes(low, shape)


# ---------------------------------------------------------------------------
# 1. symbolic == concrete == oracle, bitwise
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        arch=st.sampled_from(_ARCHS),
        batch=st.sampled_from((1, 2, 3, 4, 8)),
        max_len=st.sampled_from((17, 32, 48, 64, 96)),
        dp=st.sampled_from((1, 2, 3, 4, 8)),
        tp=st.sampled_from((1, 2, 3, 4, 8)),
        kv=st.sampled_from(("bf16", "int8")),
    )
    def test_symbolic_matches_concrete_and_specs_bitwise(
            arch, batch, max_len, dp, tp, kv):
        """Random serve shapes: Expr evaluation, concrete-ops evaluation,
        and the raw spec-table walk agree bit for bit."""
        cfg = get_arch(arch).reduced()
        sym = symbolic_cache_bytes(cfg, batch, max_len, kv)
        got_sym = float(np.asarray(sym.evaluate(
            {"dp": float(dp), "tp": float(tp)}, {})))
        got_conc = concrete_cache_bytes(cfg, batch, max_len, kv,
                                        dp_size=dp, tp_size=tp)
        assert got_sym == got_conc, (arch, batch, max_len, dp, tp, kv)
        want = _concrete_via_specs(arch, batch, max_len, dp, tp, kv)
        assert got_conc == want, (arch, batch, max_len, dp, tp, kv)

else:                                                # pragma: no cover

    def test_property_tests_need_hypothesis():
        pytest.importorskip("hypothesis")


def test_seeded_sweep_bitwise():
    """Hypothesis-free randomized sweep (seeded) so the three-way
    bitwise contract is exercised even where hypothesis is absent."""
    import random
    rng = random.Random(0xcac4e)
    for _ in range(24):
        arch = rng.choice(_ARCHS)
        batch = rng.choice((1, 2, 3, 4, 8))
        max_len = rng.choice((17, 32, 48, 96))
        dp, tp = rng.choice((1, 2, 3, 4, 8)), rng.choice((1, 2, 3, 4, 8))
        kv = rng.choice(("bf16", "int8"))
        cfg = get_arch(arch).reduced()
        sym = symbolic_cache_bytes(cfg, batch, max_len, kv)
        got_sym = float(np.asarray(sym.evaluate(
            {"dp": float(dp), "tp": float(tp)}, {})))
        got_conc = concrete_cache_bytes(cfg, batch, max_len, kv,
                                        dp_size=dp, tp_size=tp)
        want = _concrete_via_specs(arch, batch, max_len, dp, tp, kv)
        assert got_sym == got_conc == want, \
            (arch, batch, max_len, dp, tp, kv)


def test_indivisible_batch_shards_kv_sequence():
    """batch=3 on dp=2: the batch dim cannot shard, so eligible KV
    leaves shard their sequence dim over dp instead (and state-cache
    leaves replicate) — both evaluations must track the cascade."""
    cfg = get_arch("granite-3-8b").reduced()
    got = concrete_cache_bytes(cfg, 3, 64, "bf16", dp_size=2, tp_size=1)
    want = _concrete_via_specs("granite-3-8b", 3, 64, 2, 1, "bf16")
    assert got == want
    # k/v DID shard on seq: strictly less than fully-replicated bytes
    repl = concrete_cache_bytes(cfg, 3, 64, "bf16", dp_size=1, tp_size=1)
    assert got < repl


def test_int8_halves_kv_and_adds_scales():
    """int8 caches: k/v at 1 byte plus f32 per-(token, head) scales —
    the layout records exactly what init_caches allocates."""
    cfg = get_arch("granite-3-8b").reduced()
    lay16 = derive_cache_layout(cfg, 2, 32, "bf16")
    lay8 = derive_cache_layout(cfg, 2, 32, "int8")
    keys8 = {l.key for l in lay8.leaves}
    assert {"k_scale", "v_scale"} <= keys8
    assert {l.key for l in lay16.leaves} | {"k_scale", "v_scale"} == keys8
    b16 = concrete_cache_bytes(cfg, 2, 32, "bf16", dp_size=1, tp_size=1)
    b8 = concrete_cache_bytes(cfg, 2, 32, "int8", dp_size=1, tp_size=1)
    assert b8 < b16    # scales cost less than the halved k/v saves


# ---------------------------------------------------------------------------
# 2. the key table mirrors the sharder
# ---------------------------------------------------------------------------


def test_seq_cache_keys_mirror_sharding_table():
    from repro.parallel.sharding import _SEQ_LEAF_SEQ_DIM
    assert set(SEQ_CACHE_KEYS) == set(_SEQ_LEAF_SEQ_DIM)


# ---------------------------------------------------------------------------
# 3. serve cost model == lowered memory report, bitwise
# ---------------------------------------------------------------------------

_SERVE_PLANS = [
    # (arch, dp, tp, zero, kv)
    ("granite-3-8b", 1, 1, 0, "bf16"),
    ("granite-3-8b", 4, 2, 0, "bf16"),
    ("granite-3-8b", 2, 4, 3, "int8"),
    ("qwen2-moe-a2.7b", 2, 2, 0, "bf16"),
    ("minicpm3-4b", 2, 1, 0, "bf16"),      # MLA latent cache
    ("zamba2-2.7b", 2, 2, 3, "bf16"),      # hybrid mamba+attn caches
    ("whisper-small", 2, 1, 0, "bf16"),    # enc-dec cross-attn caches
    ("xlstm-1.3b", 1, 2, 0, "bf16"),       # pure recurrent state
]


@pytest.mark.parametrize("arch,dp,tp,zero,kv", _SERVE_PLANS)
def test_estimate_serve_plan_matches_report_bitwise(arch, dp, tp, zero, kv):
    cfg = get_arch(arch).reduced()
    plan = single_stage_plan(cfg.num_layers, dp=dp, tp=tp, micro_batch=1,
                             grad_accum=1, zero=zero, ckpt_layers=0,
                             kv_cache_dtype=kv)
    mesh = AbstractMesh((dp, tp), ("data", "model"))
    for kind, field in (("decode", "mem_decode"), ("prefill", "mem_prefill")):
        shape = ShapeConfig("serve", 48, 4, kind)
        est = estimate_serve_plan(cfg, shape, plan)
        rep = lower_plan(cfg, shape, plan, mesh).memory_report()
        assert est[field] == rep.peak_bytes, \
            (arch, kind, est[field], rep.peak_bytes)


def test_tape_matches_expr_evaluation():
    """The compiled tape the tuner sweeps with is bitwise-identical to
    recursive Expr evaluation, scalar and vectorized."""
    cfg = get_arch("granite-3-8b").reduced()
    scm = ServeCostModel(cfg, batch=4, max_len=48)
    envs = [dict(dp=1.0, tp=1.0, z1=0.0, z2=0.0, z3=0.0, kv8=0.0),
            dict(dp=2.0, tp=4.0, z1=1.0, z2=1.0, z3=1.0, kv8=1.0),
            dict(dp=8.0, tp=1.0, z1=0.0, z2=0.0, z3=0.0, kv8=1.0)]
    vec = {k: np.asarray([e[k] for e in envs]) for k in envs[0]}
    got = scm.evaluate(vec)
    for i, e in enumerate(envs):
        memo = {}
        full = dict(e, wo=0.0, oo=0.0, L=float(cfg.num_layers))
        for name, expr in scm.exprs.items():
            want = float(np.asarray(expr.evaluate(full, memo)))
            assert float(got[name][i]) == want, (name, e)


def test_estimate_serve_plan_rejects_pipeline():
    from repro.core.plan import Plan, StageConfig
    cfg = get_arch("granite-3-8b").reduced()
    st0 = StageConfig(layers=cfg.num_layers // 2, micro_batch=1, dp=1,
                      tp=1, zero=0, ckpt_layers=0)
    plan = Plan(grad_accum=1, stages=(st0, st0))
    with pytest.raises(ValueError, match="single-stage"):
        estimate_serve_plan(cfg, ShapeConfig("serve", 48, 4, "decode"),
                            plan)


@pytest.mark.parametrize("arch", list_archs())
def test_every_zoo_arch_derives_a_layout(arch):
    """Every family abstract-allocates; every leaf records a real shape
    and the batch dim the sharder would find."""
    cfg = get_arch(arch).reduced()
    lay = derive_cache_layout(cfg, 2, 32, "bf16")
    assert lay.leaves
    for leaf in lay.leaves:
        assert leaf.itemsize > 0
        if leaf.bdim is not None:
            assert leaf.shape[leaf.bdim] == 2
    # the derivation is cached: same key, same object
    assert derive_cache_layout(cfg, 2, 32, "bf16") is lay
