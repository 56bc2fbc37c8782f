"""End-to-end system behaviour: tune -> plan -> (reduced) execution, plus the
roofline/report plumbing and gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_arch
from repro.core.plan import single_stage_plan
from repro.core.schedule import validate_plan
from repro.core.tuner import tune


def test_tune_then_execute_reduced():
    """The tuner's plan (topology scaled down) must actually run: tune for
    16 devices, execute the same knobs (zero/ckpt semantics) on 1 device
    with the reduced config."""
    cfg = get_arch("granite-3-8b")
    shape = ShapeConfig("t", 4096, 32, "train")
    rep = tune(cfg, shape, 16, space="mist", stage_counts=(1,),
               grad_accums=(4,))
    assert rep.plan is not None
    assert validate_plan(rep.plan, cfg, 16, 32) == []

    rcfg = cfg.reduced()
    from repro.models.zoo import build_model
    from repro.training.step import make_train_step, init_sharded_state
    from repro.launch.mesh import make_host_mesh
    model = build_model(rcfg)
    tuned = rep.plan.stages[0]
    plan = single_stage_plan(
        rcfg.num_layers, dp=1, tp=1, micro_batch=2, grad_accum=2,
        zero=tuned.zero,
        ckpt_layers=min(tuned.ckpt_layers, rcfg.num_layers),
        oo=tuned.oo, ao=tuned.ao)
    mesh = make_host_mesh(1, 1)
    with jax.set_mesh(mesh):
        step = make_train_step(model, plan, mesh, donate=False)
        state, _ = init_sharded_state(model, plan, mesh,
                                      jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(key, (4, 64), 0,
                                              rcfg.vocab_size),
                 "labels": jax.random.randint(key, (4, 64), 0,
                                              rcfg.vocab_size)}
        state, m = step.fn(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_roofline_report_terms():
    from repro.core.hardware import V5E
    from repro.perf.hloanalysis import HLOStats
    from repro.perf.roofline import report_from_stats
    st = HLOStats(dot_flops=1e15, hbm_bytes=1e12,
                  collective_wire_bytes=1e11,
                  collective_by_kind={"all-reduce": 1e11})
    rep = report_from_stats(st, arch="a", shape="s", mesh="16x16",
                            chips=256, model_flops_global=2e17)
    assert rep.t_compute == pytest.approx(1e15 / V5E.peak_flops_bf16)
    assert rep.t_memory == pytest.approx(1e12 / V5E.hbm_bw)
    assert rep.t_collective == pytest.approx(1e11 / V5E.ici_bw_total)
    assert rep.bottleneck == "compute"
    assert 0 < rep.roofline_fraction <= 1.0
    assert rep.useful_ratio == pytest.approx(2e17 / (256 * 1e15))


def test_model_flops_for_kinds():
    from repro.perf.roofline import model_flops_for
    cfg = get_arch("granite-3-8b")
    n = cfg.param_count(active_only=True)
    tr = model_flops_for(cfg, ShapeConfig("t", 4096, 256, "train"))
    pf = model_flops_for(cfg, ShapeConfig("p", 4096, 256, "prefill"))
    dc = model_flops_for(cfg, ShapeConfig("d", 4096, 256, "decode"))
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert pf == pytest.approx(tr / 3)
    assert dc == pytest.approx(2 * n * 256)


def test_moe_uses_active_params():
    from repro.perf.roofline import model_flops_for
    cfg = get_arch("dbrx-132b")
    t = model_flops_for(cfg, ShapeConfig("t", 4096, 8, "train"))
    n_act = cfg.param_count(active_only=True)
    n_tot = cfg.param_count()
    assert t == pytest.approx(6 * n_act * 8 * 4096)
    assert n_act < 0.5 * n_tot


def test_gradient_compression_roundtrip():
    from repro.parallel.compression import (compress_with_feedback,
                                            dequantize_int8, quantize_int8)
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
    q, s = quantize_int8(g["w"])
    assert q.dtype == jnp.int8
    deq = dequantize_int8(q, s)
    err = float(jnp.max(jnp.abs(deq - g["w"])))
    assert err <= float(s) * 0.51 + 1e-6            # half-ulp bound

    res = {"w": jnp.zeros_like(g["w"])}
    out1, res1 = compress_with_feedback(g, res)
    # error feedback: residual carries the quantization error
    np.testing.assert_allclose(np.asarray(out1["w"] + res1["w"]),
                               np.asarray(g["w"]), atol=1e-6)


def test_interference_channels_in_schedule():
    """Every cost item referenced by the overlap schedule exists in the
    cost model."""
    from repro.core.costmodel import StageCostModel
    from repro.core.schedule import OVERLAP_SCHEDULE
    scm = StageCostModel(get_arch("granite-3-8b"), 1024)
    for ph in OVERLAP_SCHEDULE:
        for item in ph.compute + ph.g2g + ph.d2h + ph.h2d:
            assert item in scm.items, item
