"""Calibration subsystem (docs/calibration.md): profile round-trip, the
frozen-default guarantee, attribution, and the exact-scaling fit."""
import dataclasses
import pickle

import numpy as np
import pytest

from repro.calibration import (DEFAULT_PROFILE, CalibrationProfile,
                               load_profile)
from repro.calibration.fit import (ITEM_GROUP, attribute_cell, fit_profile,
                                   predict_step_scaled, scales_to_overrides)
from repro.calibration.measure import MeasuredCell, _cell_plans
from repro.configs.base import get_arch
from repro.core.costmodel import CostParams, StageCostModel, estimate_plan
from repro.core.interference import _DEFAULT, InterferenceModel


def _mk_cells(arch="granite-3-8b", n_dev=4):
    """Synthetic MeasuredCells (plans only, no jax execution)."""
    cfg = get_arch(arch).reduced()
    cells = []
    for label, plan in _cell_plans(cfg, n_dev):
        st0 = plan.stages[0]
        gbs = st0.dp * st0.micro_batch * plan.grad_accum
        cells.append(MeasuredCell(
            label=f"{arch}/{label}", arch=arch, reduced=True, seq_len=128,
            global_batch=gbs, plan=plan, steps=0, step_seconds=(),
            t_measured=0.0))
    return cells


# ---------------------------------------------------------------------------
# CalibrationProfile
# ---------------------------------------------------------------------------


class TestProfile:
    def test_frozen_default_cost_params_identity(self):
        base = CostParams()
        assert DEFAULT_PROFILE.cost_params(base) is base
        assert DEFAULT_PROFILE.cost_params() == CostParams()

    def test_frozen_default_interference_is_default(self):
        assert DEFAULT_PROFILE.interference_model().factors == _DEFAULT

    def test_frozen_default_model_outputs_identical(self):
        """StageCostModel with the default profile is bitwise-identical to
        no profile at all — the golden-fixture guarantee."""
        cfg = get_arch("granite-3-8b")
        a = StageCostModel(cfg, 4096)
        b = StageCostModel(cfg, 4096, profile=DEFAULT_PROFILE)
        env = dict(b=2.0, dp=8.0, tp=2.0, zero=1.0, ckpt=4.0, wo=0.0,
                   go=0.0, oo=0.0, ao=0.0, L=40.0, G=4.0, inflight=1.0)
        ra, rb = a.evaluate(dict(env)), b.evaluate(dict(env))
        for k in ("t_step", "t_stable", "d_delta", "mem_peak"):
            np.testing.assert_array_equal(ra[k], rb[k])
        assert b.jax_auto_threshold == a.jax_auto_threshold

    def test_with_cost_merges_over_existing_overrides(self):
        """tools/calibrate_reserved.py folds runtime_reserved into a
        profile tools/calibrate.py already fitted: other overrides are
        preserved, the new one lands, nothing else changes."""
        base = CalibrationProfile.make(platform="cpu",
                                       cost={"mxu_eff_peak": 0.41})
        merged = base.with_cost(runtime_reserved=2.0 * 2**30)
        assert dict(merged.cost) == {"mxu_eff_peak": 0.41,
                                     "runtime_reserved": 2.0 * 2**30}
        cp = merged.cost_params(CostParams())
        assert cp.runtime_reserved == 2.0 * 2**30
        assert cp.mxu_eff_peak == 0.41
        # updating an existing override replaces, not duplicates
        again = merged.with_cost(runtime_reserved=1.0 * 2**30)
        assert dict(again.cost)["runtime_reserved"] == 1.0 * 2**30

    def test_with_cost_rejects_unknown_field(self):
        with pytest.raises(ValueError, match="unknown CostParams"):
            DEFAULT_PROFILE.with_cost(runtime_reservd=1.0)

    def test_reserved_override_frozen_default_bitwise_guard(self):
        """The runtime_reserved fold keeps the frozen-default guarantee:
        a no-override profile returns the base CostParams ITSELF, and an
        override touches runtime_reserved alone — every other field stays
        bit-identical."""
        base = CostParams()
        assert DEFAULT_PROFILE.with_cost().cost_params(base) is base
        cp = DEFAULT_PROFILE.with_cost(
            runtime_reserved=base.runtime_reserved + 64 * 2**20
        ).cost_params(base)
        assert cp.runtime_reserved == base.runtime_reserved + 64 * 2**20
        for f in dataclasses.fields(CostParams):
            if f.name in ("runtime_reserved",):
                continue
            assert getattr(cp, f.name) == getattr(base, f.name), f.name

    def test_round_trip(self):
        p = CalibrationProfile.make(
            platform="cpu", source="test",
            cost={"mxu_eff_peak": 0.5, "ici_eff": 0.3,
                  "coll_latency_us": 90.0},
            kernels={"attn_scale": 1.5},
            interference={(0, 1): (1.1, 1.2), (0, 1, 2): (1.2, 1.3, 1.4)},
            jax_auto_threshold=1024)
        q = CalibrationProfile.from_json(p.to_json())
        assert q == p
        cp = q.cost_params()
        assert cp.mxu_eff_peak == 0.5
        assert cp.coll_latency_us == 90.0
        assert cp.kernels.attn_scale == 1.5
        assert cp.vpu_tax == CostParams().vpu_tax   # untouched field
        assert q.interference_model().factors[(0, 1)] == (1.1, 1.2)
        assert q.jax_auto_threshold == 1024

    def test_unknown_override_rejected(self):
        with pytest.raises(ValueError, match="CostParams"):
            CalibrationProfile.make(cost={"not_a_field": 1.0})
        with pytest.raises(ValueError, match="KernelCoeffs"):
            CalibrationProfile.make(kernels={"nope": 1.0})

    def test_newer_version_rejected(self):
        with pytest.raises(ValueError, match="version"):
            CalibrationProfile.from_json('{"version": 999}')

    def test_save_load(self, tmp_path):
        p = CalibrationProfile.make(platform="cpu",
                                    cost={"host_eff": 0.4})
        path = p.save(tmp_path / "sub" / "cpu.json")
        assert CalibrationProfile.load(path) == p

    def test_load_profile_resolution(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_CALIBRATION_DIR", str(tmp_path))
        monkeypatch.delenv("REPRO_CALIBRATION_PROFILE", raising=False)
        # missing file -> frozen default
        assert load_profile("cpu") is DEFAULT_PROFILE
        p = CalibrationProfile.make(platform="cpu",
                                    cost={"ici_eff": 0.2})
        p.save(tmp_path / "cpu.json")
        assert load_profile("cpu") == p
        # explicit env path wins
        q = CalibrationProfile.make(platform="other",
                                    cost={"ici_eff": 0.3})
        q.save(tmp_path / "explicit.json")
        monkeypatch.setenv("REPRO_CALIBRATION_PROFILE",
                           str(tmp_path / "explicit.json"))
        assert load_profile("cpu") == q

    def test_hashable_and_picklable_in_tunespec(self):
        from repro.core.tuner import TuneSpec
        p = CalibrationProfile.make(platform="cpu",
                                    cost={"mxu_eff_peak": 0.4},
                                    interference={(0, 1): (1.2, 1.3)})
        hash(p)
        spec = TuneSpec(arch=get_arch("granite-3-8b").reduced(),
                        seq_len=128, global_batch=8, n_devices=4,
                        profile=p)
        spec2 = pickle.loads(pickle.dumps(spec))
        assert spec2.profile == p
        from repro.core.tuner import MistTuner
        tuner = MistTuner(spec2)
        assert tuner.cp.mxu_eff_peak == 0.4


# ---------------------------------------------------------------------------
# Attribution + fitting
# ---------------------------------------------------------------------------


class TestFit:
    def test_item_groups_cover_all_items(self):
        scm = StageCostModel(get_arch("granite-3-8b").reduced(), 128)
        assert set(ITEM_GROUP) == set(scm.items)

    def test_attribution_matches_estimate_plan(self):
        cell = _mk_cells()[0]
        attr = attribute_cell(cell)
        est = estimate_plan(cell.config(), cell.shape(), cell.plan)
        assert attr.t_step_pred == pytest.approx(est["t_step"], rel=1e-12)

    def test_scaled_surrogate_equals_rebuilt_model(self):
        """The exact-scaling claim: dividing channel totals by the group
        scales == rebuilding the model with the equivalent CostParams."""
        for cell in _mk_cells():
            scales = (1e-3, 1e-2, 1.0)
            attr = attribute_cell(cell)
            prof = CalibrationProfile.make(
                platform="cpu",
                cost=scales_to_overrides(scales, CostParams()))
            real = estimate_plan(cell.config(), cell.shape(), cell.plan,
                                 profile=prof)["t_step"]
            sur = predict_step_scaled(attr, scales, InterferenceModel())
            assert sur == pytest.approx(real, rel=1e-9)

    def test_fit_recovers_synthetic_scales(self):
        """Measurements fabricated by a known scaled profile are recovered:
        fitted error collapses, uncalibrated error is huge."""
        cells = _mk_cells()
        true = CalibrationProfile.make(
            platform="cpu",
            cost=scales_to_overrides((3e-4, 2e-3, 1.0), CostParams()))
        for c in cells:
            c.t_measured = estimate_plan(c.config(), c.shape(), c.plan,
                                         profile=true)["t_step"]
        prof, report = fit_profile(cells, platform="cpu",
                                   fit_interference=False)
        assert report["improved"]
        assert report["mean_err_fitted"] < 0.02
        assert report["mean_err_uncalibrated"] > 0.9
        # the fitted profile predicts through the real model too
        for c in cells:
            pred = estimate_plan(c.config(), c.shape(), c.plan,
                                 profile=prof)["t_step"]
            assert pred == pytest.approx(c.t_measured, rel=0.05)

    def test_fit_keep_if_better_guard(self):
        """When measurements equal the uncalibrated predictions, fitting
        must not make things worse (and should return ~the base)."""
        cells = _mk_cells(n_dev=2)
        for c in cells:
            c.t_measured = estimate_plan(c.config(), c.shape(),
                                         c.plan)["t_step"]
        _prof, report = fit_profile(cells, platform="cpu",
                                    fit_interference=False)
        assert (report["mean_err_fitted"]
                <= report["mean_err_uncalibrated"] + 1e-12)

    def test_non_default_kernel_cell_refused(self):
        from repro.core.plan import KernelConfig
        import dataclasses
        cell = _mk_cells()[0]
        cell.plan = dataclasses.replace(
            cell.plan, kernel=KernelConfig(attn_q_block=256))
        with pytest.raises(ValueError, match="kernel"):
            attribute_cell(cell)

    def test_flops_helper_inverts_time_at_default_kernels(self):
        """evaluate_flops + the public mxu_efficiency helper reproduce the
        tape's t_fwd exactly at default kernel configs — the benchmark's
        inversion path cannot drift from the model."""
        cfg = get_arch("granite-3-8b").reduced()
        scm = StageCostModel(cfg, 128)
        env = dict(b=2.0, dp=2.0, tp=1.0, zero=1.0, ckpt=0.0, wo=0.0,
                   go=0.0, oo=0.0, ao=0.0, L=float(cfg.num_layers), G=2.0)
        out = scm.evaluate(dict(env))
        fl = scm.evaluate_flops(dict(env))
        tok = env["b"] * scm.seq
        eff = float(scm.mxu_efficiency(tok))
        t_fwd_from_flops = (float(fl["fwd"]) * (1.0 + scm.cp.vpu_tax)
                            / (scm.hw.peak_flops_bf16 * eff))
        assert t_fwd_from_flops == pytest.approx(
            float(out["items"]["fwd"]), rel=1e-12)
        assert float(fl["bwd"]) == pytest.approx(2 * float(fl["fwd"]))


# ---------------------------------------------------------------------------
# Measurement (one real end-to-end cell — also covers the driver)
# ---------------------------------------------------------------------------


def test_measure_and_fit_end_to_end():
    """One real measured cell through measure_plan -> fit_profile: the
    tune->execute->measure loop on the host backend."""
    jax = pytest.importorskip("jax")
    from repro.calibration.measure import measure_cells

    cells, skipped = measure_cells(("granite-3-8b",), steps=2, warmup=1,
                                   seq_len=64, max_cells_per_arch=1)
    assert cells, f"no cells measured; skipped={skipped}"
    cell = cells[0]
    assert cell.t_measured > 0
    assert len(cell.step_seconds) == 2
    assert cell.memory["modeled_peak_bytes"] > 0
    prof, report = fit_profile(cells, platform="cpu")
    assert (report["mean_err_fitted"]
            <= report["mean_err_uncalibrated"] + 1e-12)
    assert report["n_cells"] == 1


class TestShippedTpuProfile:
    """The v5e profile shipped in calibration/profiles/: its memory terms
    keep the tuner off the plans the chip's compiler refused."""

    @pytest.fixture
    def tpu(self, tmp_path, monkeypatch):
        monkeypatch.setenv("REPRO_CALIBRATION_DIR", str(tmp_path))
        monkeypatch.delenv("REPRO_CALIBRATION_PROFILE", raising=False)
        return load_profile("tpu")

    def test_resolution(self, tpu, tmp_path):
        assert tpu.platform == "tpu"
        assert dict(tpu.cost)["act_overhead"] > 0
        # a user's fitted file for the platform wins over the shipped one
        mine = CalibrationProfile.make(platform="tpu",
                                       cost={"act_overhead": 0.5})
        mine.save(tmp_path / "tpu.json")
        assert load_profile("tpu") == mine

    def test_overhead_term_predicted_equals_lowered(self, tpu):
        from repro.configs.base import ShapeConfig
        from repro.core.plan import single_stage_plan
        from repro.lowering import memory_consistency
        cfg = get_arch("granite-3-8b").replace(num_layers=4)
        plan = single_stage_plan(4, dp=1, tp=1, micro_batch=1, grad_accum=8,
                                 zero=0, oo=0.875, ckpt_layers=1)
        shape = ShapeConfig("t", 4096, 8, "train")
        mc = memory_consistency(cfg, shape, plan, profile=tpu)
        base = memory_consistency(cfg, shape, plan)
        assert mc["within_tol"]
        over = mc["terms"]["overhead"]
        assert over["predicted"] == pytest.approx(over["lowered"], rel=1e-9)
        assert base["terms"]["overhead"]["lowered"] == 0.0
        f = dict(tpu.cost)["act_overhead"]
        temps = sum(base["terms"][k]["lowered"]
                    for k in ("act", "transient", "logits"))
        assert over["lowered"] == pytest.approx(f * temps)

    def test_one_chip_plan_is_the_one_that_compiles(self, tpu):
        """granite-3-8b at 4 layers, seq 4096, batch 8, one v5e: the
        default constants pick ckpt_layers=1, which the v5e compiler
        refused (17.78 GiB of 15.75); the profile picks the fully
        rematerialized plan it accepted."""
        from repro.configs.base import ShapeConfig
        from repro.core.tuner import tune
        cfg = get_arch("granite-3-8b").replace(num_layers=4)
        shape = ShapeConfig("t", 4096, 8, "train")
        plain = tune(cfg, shape, 1, workers=1).plan
        tuned = tune(cfg, shape, 1, workers=1, profile=tpu).plan
        assert plain.stages[0].ckpt_layers == 1
        assert tuned.stages[0].ckpt_layers == 4
