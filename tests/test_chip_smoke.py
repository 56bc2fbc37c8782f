"""The chip smoke's phases, rehearsed on the CPU at ``reduced()`` sizes,
its refusal to run anywhere but on a TPU, and the compile-cache rule the
entry points share."""
import dataclasses
import importlib.util
import pathlib

import jax
import pytest

from repro.configs.base import get_arch
from repro.core.hardware import V5E, spec_for_device_kind
from repro.launch import cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase_rehearsal(smoke, tmp_path):
    """tune -> lower -> init -> step -> TrainLoop, checked as on the chip.
    The HBM budget is squeezed so the tuned plan offloads optimizer state
    and activations to pinned_host, as the one-chip plan does."""
    cfg = get_arch("granite-3-8b").reduced()
    hw = dataclasses.replace(V5E, hbm_bytes=0.825 * 2**30)
    out = smoke.train_phase(cfg, seq=64, global_batch=4, steps=3,
                            out_dir=tmp_path, hw=hw, log=lambda _: None)
    assert out["plan"]["stages"][0]["oo"] > 0
    assert out["offloaded_leaves"] > 0
    assert len(out["losses"]) == 3
    assert out["lowered_bytes"] == out["predicted_bytes"]
    assert (tmp_path / "ckpt").is_dir()


def test_serve_phase_rehearsal(smoke):
    cfg = get_arch("granite-3-8b").reduced()
    out = smoke.serve_phase(cfg, prompt_lens=(16, 32, 8, 32), gen=4,
                            slots=2, page=4, log=lambda _: None)
    assert out["identical"] == out["requests"] == 4
    # the CPU's decode numerics do not depend on the batch's composition
    assert out["identical_with_slots"] == 4


def test_tie_gap_flags_a_decisive_step(smoke):
    """The multi-slot serving check: a token the reference would not pick
    by a wide margin is no near-tie."""
    import numpy as np
    from repro.core.plan import single_stage_plan
    from repro.launch.mesh import make_plan_mesh
    from repro.launch.serve import generate
    from repro.lowering import lower_plan
    from repro.models.zoo import build_model
    from repro.training.step import make_prefill_step
    cfg = get_arch("granite-3-8b").reduced()
    model = build_model(cfg)
    plan = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=1,
                             grad_accum=1, zero=0, ckpt_layers=0)
    mesh = make_plan_mesh(1, 1)
    low = lower_plan(cfg, None, plan, mesh)
    with jax.set_mesh(mesh):
        params, _ = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                                    cfg.vocab_size).astype(np.int32)
        want = np.asarray(generate(model, params, prompt, 4, mesh, plan,
                                   lowered=low))[0]
        prefill = make_prefill_step(model, lowered=low)
        k = 2
        toks = np.concatenate([np.asarray(prompt), want[None, :k]], axis=1)
        logits = np.asarray(prefill.fn(params, {"tokens": toks}))[0, -1]
        got = want.copy()
        got[k] = int(np.argmin(logits))
        assert smoke.tie_gap(prefill, params, prompt, want, want, k) == 0
        assert smoke.tie_gap(prefill, params, prompt, want, got,
                             k) > smoke.NEAR_TIE


def test_main_refuses_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    assert smoke.main(["--four-chip"]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_unknown_device_kind_refused():
    assert spec_for_device_kind("TPU v5 lite") is V5E
    with pytest.raises(ValueError, match="no hardware spec"):
        spec_for_device_kind("TPU v4")


def test_missing_pinned_host_raises(monkeypatch):
    """Host offload on a backend without pinned_host is an error, never a
    quiet fall-back to device-resident state."""
    from types import SimpleNamespace
    from repro import compat
    dev = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                          addressable_memories=lambda: [
                              SimpleNamespace(kind="device")])
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    with pytest.raises(RuntimeError, match="no pinned_host"):
        compat.host_memory_kind.__wrapped__()


def test_compile_cache_dir(monkeypatch, tmp_path):
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(REPO / ".jax_cache")
        assert cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
