"""Sharding rules (pure metadata — no multi-device needed) + Plan + schedule
legality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # property tests skip; example tests still run
    HAVE_HYPOTHESIS = False

from repro import compat
from repro.configs.base import get_arch
from repro.core.plan import Plan, StageConfig, megatron_baseline_plan, \
    single_stage_plan
from repro.core.schedule import (Candidate, ckpt_choices, divisors,
                                 enumerate_candidates, grad_accum_choices,
                                 legal_dp_tp, microbatch_choices,
                                 validate_plan)
from repro.models.zoo import abstract_params
from repro.parallel import sharding as SH


def _mesh(dp=1, tp=1):
    if dp * tp <= len(jax.devices()):
        return compat.make_mesh((dp, tp), ("data", "model"))
    # spec-only tests: abstract meshes carry shapes without devices
    return jax.sharding.AbstractMesh((dp, tp), ("data", "model"))


# -- choose_tp_dim / param_spec ------------------------------------------------


def test_choose_tp_priority():
    # heads beats vocab
    i = SH.choose_tp_dim(("vocab", "heads"), (100, 16), 4, False)
    assert i == 1
    # indivisible dims skipped
    i = SH.choose_tp_dim(("heads",), (6,), 4, False)
    assert i is None
    # layer axes never sharded
    i = SH.choose_tp_dim(("layers", "mlp"), (8, 64), 4, False)
    assert i == 1


def test_param_specs_divisible():
    """Every emitted spec must divide the dim it shards, and the layer dim
    is never sharded."""
    cfg = get_arch("granite-3-8b")
    ma = SH.MeshAxes(dp=("data",), tp="model", fsdp=("data",))
    params, axes = abstract_params(cfg)
    for dp, tp in ((4, 4), (2, 8)):
        mesh = _mesh(dp, tp)
        for name, sds in params.items():
            spec = SH.param_spec(name, sds.shape, axes[name], mesh, ma,
                                 zero3=True, ep_ok=False)
            for i, (dim, sp) in enumerate(zip(sds.shape, tuple(spec))):
                if sp is None:
                    continue
                size = tp if sp == "model" else dp
                assert dim % size == 0, (name, i, dim, sp)
                assert axes[name][i] not in SH.LAYER_AXES
    # at tp=8, attention q weights shard on the heads dim
    mesh = _mesh(2, 8)
    spec = SH.param_spec("layers/attn/wq", params["layers/attn/wq"].shape,
                         axes["layers/attn/wq"], mesh, ma, zero3=False,
                         ep_ok=False)
    assert "model" in tuple(spec)


def test_zero_levels_monotone_sharding():
    """grad_spec shards over dp iff zero >= 2; opt_spec iff zero >= 1."""
    cfg = get_arch("granite-3-8b")
    mesh = _mesh(4, 2)
    ma = SH.MeshAxes(dp=("data",), tp="model", fsdp=("data",))
    params, axes = abstract_params(cfg)
    name = "layers/mlp/wu"
    sds = params[name]
    g1 = SH.grad_spec(name, sds.shape, axes[name], mesh, ma, zero=1,
                      ep_ok=False)
    g2 = SH.grad_spec(name, sds.shape, axes[name], mesh, ma, zero=2,
                      ep_ok=False)
    o1 = SH.opt_spec(name, sds.shape, axes[name], mesh, ma, zero=1,
                     ep_ok=False)
    def has_data(spec):
        return any("data" in str(a) for a in tuple(spec) if a is not None)
    assert not has_data(g1)
    assert has_data(g2)
    assert has_data(o1)


# -- schedule enumeration -------------------------------------------------------


def test_divisors():
    assert divisors(12) == [1, 2, 3, 4, 6, 12]


def test_legal_dp_tp_respects_heads():
    cfg = get_arch("granite-3-8b")       # 32 heads
    pairs = legal_dp_tp(16, cfg)
    assert (16, 1) in pairs and (1, 16) in pairs
    cfg9 = cfg.replace(num_heads=9, num_kv_heads=3)
    pairs9 = legal_dp_tp(16, cfg9)
    assert all(tp in (1,) for _, tp in pairs9)  # 9 !% 2,4,8,16


def test_microbatch_choices_consistency():
    assert microbatch_choices(256, dp=8, grad_accum=4) == [8]
    assert microbatch_choices(256, dp=8, grad_accum=3) == []


def test_ckpt_choices_cover_extremes():
    cs = ckpt_choices(40, granularity=8)
    assert 0 in cs and 40 in cs


def test_enumerate_candidates_all_legal():
    cfg = get_arch("granite-3-8b")
    for c in enumerate_candidates(cfg, n_devices=8, layers=40,
                                  global_batch=32, grad_accum=4,
                                  ckpt_granularity=10):
        assert c.dp * c.tp == 8
        assert 4 * c.b * c.dp == 32
        assert cfg.num_heads % c.tp == 0


# -- Plan -------------------------------------------------------------------------


def test_plan_json_roundtrip():
    p = single_stage_plan(40, dp=4, tp=4, micro_batch=2, grad_accum=8,
                          zero=2, ckpt_layers=10, oo=0.5, ao=0.25)
    q = Plan.from_json(p.to_json())
    assert q == p


if HAVE_HYPOTHESIS:
    _ratio = st.floats(0.0, 1.0, allow_nan=False)
    _stage = st.builds(
        StageConfig,
        layers=st.integers(1, 128),
        micro_batch=st.integers(1, 64),
        dp=st.integers(1, 256),
        tp=st.integers(1, 64),
        zero=st.integers(0, 3),
        ckpt_layers=st.integers(0, 10**9),
        wo=_ratio, go=_ratio, oo=_ratio, ao=_ratio,
    )
    _plan = st.builds(
        Plan,
        grad_accum=st.integers(1, 512),
        stages=st.lists(_stage, min_size=1, max_size=4).map(tuple),
        sequence_parallel=st.booleans(),
        remat_policy=st.sampled_from(["full", "dots"]),
        attn_impl=st.sampled_from(["naive", "blocked", "pallas"]),
        use_pallas=st.booleans(),
        grad_compression=st.booleans(),
        kv_cache_dtype=st.sampled_from(["bf16", "int8"]),
    )

    @settings(max_examples=200, deadline=None)
    @given(_plan)
    def test_plan_json_roundtrip_property(plan):
        """LoweredPlan trusts serialized plans: to_json/from_json is the
        identity for every representable plan (floats ride through
        repr-exact JSON), and == means field-level equality."""
        assert Plan.from_json(plan.to_json()) == plan
else:
    def test_plan_roundtrip_needs_hypothesis():
        pytest.importorskip("hypothesis")


def test_validate_plan_catches_violations():
    cfg = get_arch("granite-3-8b")
    good = single_stage_plan(cfg.num_layers, dp=4, tp=4, micro_batch=2,
                             grad_accum=4, zero=1)
    assert validate_plan(good, cfg, 16, 32) == []
    bad_layers = single_stage_plan(39, dp=4, tp=4, micro_batch=2,
                                   grad_accum=4)
    assert validate_plan(bad_layers, cfg, 16, 32)
    bad_batch = single_stage_plan(cfg.num_layers, dp=4, tp=4, micro_batch=2,
                                  grad_accum=8)
    assert validate_plan(bad_batch, cfg, 16, 32)
    bad_ratio = single_stage_plan(cfg.num_layers, dp=4, tp=4, micro_batch=2,
                                  grad_accum=4, oo=1.5)
    assert validate_plan(bad_ratio, cfg, 16, 32)


def test_megatron_baseline_plan_shape():
    p = megatron_baseline_plan(40, 256, 256, tp=16)
    assert p.devices == 256
    assert p.stages[0].ckpt_layers >= 40
    assert p.global_batch() == 256


# -- cache specs -------------------------------------------------------------------


def test_cache_specs_batch_vs_seq_sharding():
    cfg = get_arch("granite-3-8b").reduced()
    from repro.models.zoo import build_model
    model = build_model(cfg)
    mesh = _mesh(1, 1)
    ma = SH.MeshAxes(dp=("data",), tp="model", fsdp=("data",))
    # batch divisible by dp -> batch sharded
    caches = jax.eval_shape(lambda: model.init_caches(8, 128))
    specs = SH.cache_specs(caches, mesh, ma, 8)
    leaves = jax.tree.leaves(specs,
                             is_leaf=lambda x: hasattr(x, "spec"))
    assert leaves
