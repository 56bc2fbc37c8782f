"""Kernel-config plan dimension: byte-identity at the frozen default,
bitwise symbolic/concrete roofline agreement, legal-grid invariants,
and the tuned path end to end (docs/kernel-tuning.md)."""
import json

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import golden
from repro.core import symbolic as S
from repro.core.costmodel_params import (KERNEL_CONCRETE_OPS,
                                         KERNEL_SYMBOLIC_OPS, KernelCoeffs,
                                         kernel_time_terms,
                                         kernel_vmem_terms)
from repro.core.plan import DEFAULT_KERNEL_CONFIG, KernelConfig, Plan
from repro.core.schedule import DEFAULT_KERNEL_GRID
from repro.core.tuner import MistTuner, TuneSpec

CONFIGS = [
    DEFAULT_KERNEL_CONFIG,
    KernelConfig(1024, 1024, 512, 256),
    KernelConfig(128, 256, 128, 64),
    KernelConfig(256, 512, 512, 512),
]


def _spec(arch, **kw):
    return TuneSpec(arch=arch, seq_len=2048, global_batch=16, n_devices=8,
                    stage_counts=(1,), grad_accums=(2,), **kw)


# -- (a) frozen-default byte-identity ----------------------------------------


def test_frozen_default_matches_golden_fixture():
    """With the kernel dimension frozen to the default tuple (the default
    TuneSpec), a golden cell reproduces its committed fixture — the
    kernel machinery is byte-invisible until actually swept."""
    space, arch = "megatron", "granite-3-8b"
    path = golden.golden_path(space, arch)
    if not path.exists():
        pytest.skip("golden fixtures not generated")
    want = json.loads(path.read_text())
    doc = golden.compute_doc(space, arch)
    assert golden.fingerprint(doc) == want["fingerprint"], \
        golden.diff_docs(want["doc"], doc)


def test_explicit_default_grid_is_identical():
    """Passing kernel_grid=DEFAULT_KERNEL_GRID explicitly is the same
    sweep as not mentioning kernels at all."""
    arch = get_arch("granite-3-8b").reduced()
    r0 = MistTuner(_spec(arch)).tune()
    r1 = MistTuner(_spec(arch, kernel_grid=DEFAULT_KERNEL_GRID)).tune()
    assert r0.objective == r1.objective
    assert r0.plan.to_json() == r1.plan.to_json()


def test_default_kernel_omitted_from_plan_json():
    arch = get_arch("granite-3-8b").reduced()
    rep = MistTuner(_spec(arch)).tune()
    assert rep.plan.kernel == DEFAULT_KERNEL_CONFIG
    assert '"kernel"' not in rep.plan.to_json()
    assert Plan.from_json(rep.plan.to_json()) == rep.plan


def test_nondefault_kernel_roundtrips():
    arch = get_arch("granite-3-8b").reduced()
    rep = MistTuner(_spec(arch)).tune()
    tuned = rep.plan.replace(kernel=KernelConfig(1024, 512, 128, 256))
    assert '"kernel"' in tuned.to_json()
    assert Plan.from_json(tuned.to_json()) == tuned


# -- (b) symbolic == concrete roofline, bitwise ------------------------------


@pytest.mark.parametrize("config", CONFIGS, ids=[str(c.astuple())
                                                 for c in CONFIGS])
def test_time_terms_symbolic_matches_concrete(config):
    """The ONE shared formula evaluated over Exprs (what the tapes
    compile) and over floats (what the bench predictor uses) agrees
    BITWISE — same arithmetic in the same order (the state_layout
    idiom)."""
    kc = KernelCoeffs()
    kw = dict(seq=2048, b=4.0, tp=2.0, sp_div=2.0, num_heads=32,
              head_dim=128, d_model=4096, ssd_heads=64, ssd_head_dim=64,
              ssd_state=128, hbm_bw=819e9, peak_flops=197e12, kc=kc)
    qb, kvb, rnb, sch = (float(v) for v in config.astuple())
    sym = kernel_time_terms(qb=S.Sym("qb"), kvb=S.Sym("kvb"),
                            rnb=S.Sym("rnb"), sch=S.Sym("sch"),
                            ops=KERNEL_SYMBOLIC_OPS, **kw)
    con = kernel_time_terms(qb=qb, kvb=kvb, rnb=rnb, sch=sch,
                            ops=KERNEL_CONCRETE_OPS, **kw)
    env = {"qb": qb, "kvb": kvb, "rnb": rnb, "sch": sch}
    for op in ("attn", "rms", "ssd"):
        got = float(S.wrap(sym[op]).evaluate(env, {}))
        assert got == con[op], (op, got, con[op])


@pytest.mark.parametrize("config", CONFIGS, ids=[str(c.astuple())
                                                 for c in CONFIGS])
def test_vmem_terms_symbolic_matches_concrete(config):
    kw = dict(head_dim=128, d_model=4096, ssd_head_dim=64, ssd_state=128)
    qb, kvb, rnb, sch = (float(v) for v in config.astuple())
    sym = kernel_vmem_terms(qb=S.Sym("qb"), kvb=S.Sym("kvb"),
                            rnb=S.Sym("rnb"), sch=S.Sym("sch"),
                            ops=KERNEL_SYMBOLIC_OPS, **kw)
    con = kernel_vmem_terms(qb=qb, kvb=kvb, rnb=rnb, sch=sch,
                            ops=KERNEL_CONCRETE_OPS, **kw)
    env = {"qb": qb, "kvb": kvb, "rnb": rnb, "sch": sch}
    for op in ("attn", "rms", "ssd"):
        got = float(S.wrap(sym[op]).evaluate(env, {}))
        assert got == con[op], (op, got, con[op])


def test_delta_term_is_exactly_zero_at_default():
    """The cost model prices kernels as roofline(config) -
    roofline(default); at the default binding the delta is EXACTLY 0.0
    (not just small), which is what keeps every golden plan bitwise
    stable."""
    from repro.core.costmodel import StageCostModel
    arch = get_arch("granite-3-8b").reduced()
    scm = StageCostModel(arch, 2048)
    env = {k: float(v) for k, v in
           zip(("qb", "kvb", "rnb", "sch"), DEFAULT_KERNEL_CONFIG.astuple())}
    env.update(b=2.0, dp=2.0, tp=2.0, zero=1.0, ckpt=float(arch.num_layers),
               wo=0.0, go=0.0, oo=0.0, ao=0.0, L=float(arch.num_layers),
               inflight=1.0, G=2.0)
    val = scm.kernel_time_delta.evaluate(scm._env(env), {})
    assert float(np.asarray(val)) == 0.0


# -- legal grid --------------------------------------------------------------


def test_legal_grid_invariants():
    from repro.kernels.autotune import legal_kernel_grid, predict_vmem
    arch = get_arch("granite-3-8b")
    seq = 2048
    grid = legal_kernel_grid(arch, seq_len=seq, max_tuples=8)
    assert grid[0] == DEFAULT_KERNEL_CONFIG.astuple()
    assert len(grid) <= 8 and len(set(grid)) == len(grid)
    from repro.core.hardware import V5E
    vdef = predict_vmem(arch, DEFAULT_KERNEL_CONFIG)
    for qb, kvb, rnb, sch in grid:
        for v in (qb, kvb, rnb, sch):
            assert v >= 8 and (v & (v - 1)) == 0, grid
        assert seq % qb == 0 and seq % kvb == 0
        v = predict_vmem(arch, KernelConfig(qb, kvb, rnb, sch))
        for op in ("attn", "rms", "ssd"):
            assert v[op] <= max(V5E.vmem_bytes, vdef[op])


def test_plan_validation_rejects_bad_kernel_blocks():
    from repro.core.schedule import validate_plan
    arch = get_arch("granite-3-8b").reduced()
    plan = MistTuner(_spec(arch)).tune().plan
    bad = plan.replace(kernel=KernelConfig(attn_q_block=96))
    assert any("attn_q_block" in p for p in validate_plan(bad, arch, 8, 16))
    assert not any("kernel" in p or "block" in p
                   for p in validate_plan(plan, arch, 8, 16))


# -- tuned path end to end ---------------------------------------------------


def test_kernel_sweep_improves_and_verifies():
    """Sweeping the kernel dimension can only improve the objective (the
    default tuple rides in the grid), and whatever the tuner selects
    must instantiate through the real Pallas kernels."""
    from repro.kernels.autotune import verify_config
    arch = get_arch("granite-3-8b").reduced()
    base = MistTuner(_spec(arch)).tune()
    tuned = MistTuner(_spec(arch, kernel_tune=True)).tune()
    assert tuned.objective <= base.objective
    assert verify_config(arch, seq_len=512, config=tuned.plan.kernel)


def test_kernel_sweep_worker_identity():
    """The kernel grid rides inside TuneSpec, so forked sweep workers
    recompute the identical grid and the merged memo selects the same
    plan as the serial engine."""
    arch = get_arch("granite-3-8b").reduced()
    grid = ((512, 512, 256, 256), (1024, 1024, 512, 256))
    r1 = MistTuner(_spec(arch, kernel_grid=grid)).tune()
    r2 = MistTuner(_spec(arch, kernel_grid=grid, workers=2)).tune()
    assert r1.objective == r2.objective
    assert r1.plan.to_json() == r2.plan.to_json()


def test_tuned_plan_lowers_with_kernel_exec_config():
    """plan.kernel threads through lower_plan into every stage's
    ExecConfig (and the serve config)."""
    from jax.sharding import AbstractMesh
    from repro.lowering.lower import lower_plan
    arch = get_arch("granite-3-8b").reduced()
    plan = MistTuner(_spec(arch)).tune().plan.replace(
        kernel=KernelConfig(1024, 512, 128, 256), attn_impl="pallas",
        use_pallas=True)
    st = plan.stages[0]
    mesh = AbstractMesh((st.dp, st.tp), ("data", "model"))
    low = lower_plan(arch, None, plan, mesh)
    ec = low.stages[0].exec_cfg
    assert (ec.attn_q_block, ec.attn_kv_block, ec.rmsnorm_block,
            ec.ssd_chunk) == (1024, 512, 128, 256)
    assert low.serve_exec_cfg.attn_q_block == 1024
    assert low.plan_exec_cfg.rmsnorm_block == 128


def test_calibration_keeps_frozen_default_plan():
    """Calibrated roofline scales reshape the sweep but cancel in the
    delta at the default config — frozen-default plans are invariant."""
    from repro.core.costmodel import CostParams
    arch = get_arch("granite-3-8b").reduced()
    base = MistTuner(_spec(arch)).tune()
    cp = CostParams(kernels=KernelCoeffs(attn_scale=3.7, rms_scale=0.2,
                                         ssd_scale=11.0))
    scaled = MistTuner(_spec(arch), cp=cp).tune()
    assert base.objective == scaled.objective
    assert base.plan.to_json() == scaled.plan.to_json()
