"""The shared state-layout module: one derivation, two evaluation modes.

Three contracts are pinned here:

1. **Symbolic == concrete, bitwise.**  ``state_terms`` runs the same
   formula code over floats (``ConcreteOps``) and Exprs
   (``SymbolicOps``); every intermediate is exact in float64 (0/1
   indicators, small-integer shard counts, ``rint`` split points), so
   the two must agree bit for bit on any legal knob binding — property-
   tested over random plans.

2. **The layout == the lowered PartitionSpec tables.**  The concrete
   evaluation must reproduce ``_state_walk`` — the oracle walk over the
   specs ``lower_plan`` actually emits — so the symbolic cost model is
   transitively pinned to what the runtime shards.

3. **The selection cascades == the choosers.**  The where-chains inside
   ``_group_shards`` replicate ``choose_tp_dim`` / ``choose_fsdp_dim``
   (priority order, divisibility, ep_ok, largest-free-dim) for every
   tensor group of every zoo arch over a (tp, dp, zero) sweep.
"""
import math

import numpy as np
import pytest
from jax.sharding import AbstractMesh

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.configs.base import get_arch, list_archs
from repro.core import symbolic as S
from repro.lowering.state_layout import (CONCRETE_OPS, LAYER_AXES,
                                         _group_shards, choose_fsdp_dim,
                                         choose_tp_dim,
                                         concrete_state_terms,
                                         derive_state_layout, state_terms)

TERMS = ("weight", "grad", "master", "opt", "host")


def _symbolic_terms(cfg, *, total_layers=None, has_embed=True,
                    has_head=True):
    return state_terms(
        derive_state_layout(cfg),
        tp=S.Sym("tp"), dp=S.Sym("dp"), z1=S.Sym("z1"), z2=S.Sym("z2"),
        z3=S.Sym("z3"), wo=S.Sym("wo"), oo=S.Sym("oo"), L=S.Sym("L"),
        total_layers=total_layers, has_embed=has_embed, has_head=has_head)


def _concrete(cfg, env, *, total_layers=None, has_embed=True,
              has_head=True):
    return concrete_state_terms(
        cfg, tp_size=int(env["tp"]), fsdp_size=int(env["dp"]),
        zero=int(env["zero"]), wo=env["wo"], oo=env["oo"],
        layers=int(env["L"]),
        total_layers=(total_layers if total_layers is not None
                      else cfg.num_layers),
        has_embed=has_embed, has_head=has_head)


def _sym_env(env):
    z = env["zero"]
    return {"tp": float(env["tp"]), "dp": float(env["dp"]),
            "z1": float(z >= 1), "z2": float(z >= 2), "z3": float(z >= 3),
            "wo": float(env["wo"]), "oo": float(env["oo"]),
            "L": float(env["L"])}


# ---------------------------------------------------------------------------
# 1. symbolic == concrete, bitwise
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(
        arch=st.sampled_from(("granite-3-8b", "qwen2-moe-a2.7b",
                              "zamba2-2.7b")),
        tp=st.sampled_from((1, 2, 3, 4, 5, 8, 16)),
        dp=st.sampled_from((1, 2, 3, 4, 8, 32)),
        zero=st.integers(0, 3),
        wo=st.floats(0.0, 1.0, allow_nan=False),
        oo=st.floats(0.0, 1.0, allow_nan=False),
        layers_frac=st.floats(0.1, 1.0),
        role=st.sampled_from(((True, True), (True, False), (False, True),
                              (False, False))),
    )
    def test_symbolic_matches_concrete_bitwise(arch, tp, dp, zero, wo, oo,
                                               layers_frac, role):
        """Random legal knob bindings: the two evaluation modes of the
        SAME layout agree bit for bit, term for term."""
        cfg = get_arch(arch)
        L = max(1, int(round(layers_frac * cfg.num_layers)))
        env = dict(tp=tp, dp=dp, zero=zero, wo=wo, oo=oo, L=L)
        has_embed, has_head = role
        conc = _concrete(cfg, env, has_embed=has_embed, has_head=has_head)
        sym = _symbolic_terms(cfg, has_embed=has_embed, has_head=has_head)
        memo = {}
        se = _sym_env(env)
        for k in TERMS:
            got = float(np.asarray(S.wrap(sym[k]).evaluate(se, memo)))
            assert got == conc[k], (k, got, conc[k], env)

else:                                                # pragma: no cover

    def test_property_tests_need_hypothesis():
        pytest.importorskip("hypothesis")


def test_symbolic_matches_concrete_on_indivisible_vocab():
    """The motivating case: granite's vocab 49155 at tp=8 replicates the
    embedding; both modes must charge it at full size."""
    cfg = get_arch("granite-3-8b")
    env = dict(tp=8, dp=1, zero=0, wo=0.0, oo=1.0, L=40)
    conc = _concrete(cfg, env)
    sym = _symbolic_terms(cfg)
    memo = {}
    for k in TERMS:
        got = float(np.asarray(S.wrap(sym[k]).evaluate(_sym_env(env),
                                                       memo)))
        assert got == conc[k]
    # the embedding (201M params) replicates: >= full bf16 embed bytes
    # survive in the weight term even at tp=8
    n_embed = 49155 * 4096
    assert conc["weight"] > 2.0 * n_embed
    # its master+mu/nu are non-stacked, hence non-offloadable at oo=1
    assert conc["opt"] > 8.0 * n_embed


# ---------------------------------------------------------------------------
# 2. the layout reproduces the lowered spec tables (the oracle walk)
# ---------------------------------------------------------------------------

_PLANS = [
    # (arch, dp, tp, zero, wo, oo)
    ("granite-3-8b", 1, 8, 0, 0.0, 1.0),
    ("granite-3-8b", 4, 2, 3, 0.5, 0.25),
    ("granite-3-8b", 8, 1, 1, 0.33, 0.77),   # folded model axis
    ("qwen2-moe-a2.7b", 2, 4, 2, 0.0, 0.5),
    ("qwen2-moe-a2.7b", 1, 8, 3, 1.0, 0.0),
    ("zamba2-2.7b", 2, 4, 1, 0.25, 0.75),    # shared attention block
    ("whisper-small", 2, 2, 2, 0.5, 0.5),    # enc-dec stacks
]


@pytest.mark.parametrize("arch,dp,tp,zero,wo,oo", _PLANS)
def test_layout_matches_spec_walk(arch, dp, tp, zero, wo, oo):
    from repro.core.plan import single_stage_plan
    from repro.lowering.lower import lower_plan
    from repro.lowering.memory import _state_walk, stage_layout_terms

    cfg = get_arch(arch)
    plan = single_stage_plan(cfg.num_layers, dp=dp, tp=tp, micro_batch=1,
                             grad_accum=1, zero=zero, wo=wo, oo=oo)
    mesh = AbstractMesh((dp, tp), ("data", "model"))
    low = lower_plan(cfg, None, plan, mesh)
    want = _state_walk(low, low.stages[0], 1.0)
    got = stage_layout_terms(low, 0)
    for k in TERMS:
        assert math.isclose(got[k], want[k], rel_tol=1e-12, abs_tol=1e-6), \
            (k, got[k], want[k])


def test_layout_matches_spec_walk_pipeline():
    """S=2: per-stage fractions, unfolded mesh axes, embed/head roles."""
    from repro.core.plan import Plan, StageConfig
    from repro.lowering.lower import lower_plan
    from repro.lowering.memory import _state_walk, stage_layout_terms

    cfg = get_arch("granite-3-8b")
    stages = tuple(StageConfig(layers=20, micro_batch=2, dp=2, tp=2,
                               zero=2, ckpt_layers=20, wo=0.5, oo=0.25)
                   for _ in range(2))
    plan = Plan(grad_accum=2, stages=stages)
    mesh = AbstractMesh((2, 2, 2), ("stage", "data", "model"))
    low = lower_plan(cfg, None, plan, mesh)
    for i, ls in enumerate(low.stages):
        want = _state_walk(low, ls, ls.stage.layers / plan.total_layers)
        got = stage_layout_terms(low, i)
        for k in TERMS:
            assert math.isclose(got[k], want[k], rel_tol=1e-12,
                                abs_tol=1e-6), (i, k, got[k], want[k])


# ---------------------------------------------------------------------------
# 3. the selection cascades replicate the choosers, arch by arch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", list_archs())
def test_shard_cascades_match_choosers(arch):
    """For every tensor group of every zoo arch, over a (tp, dp, zero)
    sweep: the 0/1-indicator cascades pick exactly the shard counts the
    runtime choosers imply."""
    cfg = get_arch(arch)
    lay = derive_state_layout(cfg)
    for g in lay.groups:
        for tp in (1, 2, 3, 4, 8, 16):
            ep_ok = cfg.num_experts > 0 and cfg.num_experts % tp == 0
            ti = choose_tp_dim(g.axes, g.shape, tp, ep_ok)
            for dp in (1, 2, 3, 8):
                fi = choose_fsdp_dim(g.axes, g.shape, dp, ti)
                for zero in (0, 1, 2, 3):
                    z1, z2, z3 = (float(zero >= z) for z in (1, 2, 3))
                    w, gr, o = _group_shards(g, lay.num_experts,
                                             float(tp), float(dp),
                                             z1, z2, z3, CONCRETE_OPS)
                    t_sh = tp if ti is not None else 1
                    f_sh = dp if fi is not None else 1
                    assert w == t_sh * (f_sh if zero >= 3 else 1)
                    assert gr == t_sh * (f_sh if zero >= 2 else 1)
                    assert o == t_sh * (f_sh if zero >= 1 else 1)


def test_split_points_match_runtime_split_k():
    """The layout's integer host-split count is the optimizer's
    ``split_k`` — same rounding, same stacked-only rule."""
    from repro.models.zoo import abstract_params
    from repro.training.optimizer import split_k

    for arch in ("granite-3-8b", "zamba2-2.7b"):
        cfg = get_arch(arch)
        params, axes = abstract_params(cfg)
        for ratio in (0.0, 0.25, 1.0 / 3.0, 0.5, 0.9375, 1.0):
            for name, sds in params.items():
                k = split_k(name, sds.shape, axes, ratio)
                stacked = bool(axes[name]) and axes[name][0] in LAYER_AXES
                if stacked and sds.shape:
                    assert k == int(CONCRETE_OPS.rint(ratio
                                                      * sds.shape[0]))
                else:
                    assert k == 0
