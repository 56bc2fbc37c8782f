"""The comparison that decides ``correct``, at a size a CPU test can hold.

A whole run (all but the look for a chip) of a tiny cell is correct; the
same run with the timed path broken underneath is not, for each fault a
training cell can have, and neither is the control: the plain reference
in float8 put in the program's place."""
import json
import math
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchlib import compare, program, spec, train  # noqa: E402
from benchlib.peaks import V5E  # noqa: E402
from benchlib.reference import Reference  # noqa: E402

PROGRAM = program.Program
SEED = 2**33 + 7
# limits of the tiny cell, between what its sound run and the control
# read on the CPU at this size and seed: loss1 2.7e-5 and 5.2e-4, loss2
# 4.2e-5 and 5.5e-4, grad 9.8e-4 and 6.5e-3, change 2.0e-4 and 4.3e-3
TINY_LIMITS = {"loss1": {"limit": 1e-4}, "loss2": {"limit": 2e-4},
               "grad": {"limit": 3e-3}, "change": {"limit": 1e-3}}


@pytest.fixture(scope="module", autouse=True)
def built_once():
    """The runs of this module tune, compile and build the same programs:
    each is built by the first run that needs it and reused after."""
    mp = pytest.MonkeyPatch()
    for mod, name in ((train, "Reference"), (program, "Program")):
        real, made = getattr(mod, name), {}

        def once(*args, _real=real, _made=made, **kw):
            key = repr((args, sorted(kw.items())))
            if key not in _made:
                _made[key] = _real(*args, **kw)
            return _made[key]
        mp.setattr(mod, name, once)
    real_tune, tuned = program.tune, {}

    def tune_once(*args):
        if repr(args) not in tuned:
            tuned[repr(args)] = real_tune(*args)
        return tuned[repr(args)]
    mp.setattr(program, "tune", tune_once)
    yield
    mp.undo()


def tiny_cell():
    cfg = json.loads((BENCH / "configs" / "granite-3-8b-l4.json")
                     .read_text())
    cfg.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
               vocab_size=512, attention_multiplier=1 / math.sqrt(32))
    mix = json.loads((BENCH / "traffic" / "train-s4k.json").read_text())
    mix.update(seq_len=128, global_batch=4)
    bench = spec.load_benchmark(ROOT)
    return spec.Cell(name="tiny.train", chips=1, config_name="tiny",
                     config=cfg, traffic_name="tiny", traffic=mix,
                     limits=TINY_LIMITS, end_to_end=bench["end_to_end"],
                     per_layer=[])


def run_tiny(capsys):
    import run
    assert run.execute(tiny_cell(), seed=SEED, seconds=0.05, trace=False,
                       devices=jax.devices(), device_kind="TPU v5 lite",
                       peaks=V5E) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checked"
    return out


def frozen_state(monkeypatch):
    """The step returns its state unchanged."""
    real = PROGRAM.run_step

    def step(self, batch):
        keep = self.state
        self.state = jax.tree.map(jnp.copy, keep)
        loss = real(self, batch)
        self.state = keep
        return loss
    monkeypatch.setattr(PROGRAM, "run_step", step)


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    real = PROGRAM.run_step

    def step(self, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return real(self, {k: jnp.concatenate([v, v]) for k, v in
                           half.items()})
    monkeypatch.setattr(PROGRAM, "run_step", step)


def reference_in_place(**variant):
    def plant(monkeypatch):
        def checked(self):
            batches = [self.feed.batch(0), self.feed.batch(1)]
            self.step_index = train.WARM_STEPS
            return Reference(self.m, train.adam(self.cell.traffic),
                             **variant).run(self.seed, batches)
        monkeypatch.setattr(train.Trainer, "checked_steps", checked)
    return plant


def test_sound_run_is_correct(capsys):
    out = run_tiny(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(out["checked"]) == set(compare.NAMES)


@pytest.mark.parametrize("fault", [
    frozen_state, half_batch,
    reference_in_place(cut=2),          # the exchange between chips left out
    reference_in_place(quant="fp8"),    # the control
], ids=["state_unchanged", "half_batch", "no_exchange", "control_fp8"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch, capsys):
    fault(monkeypatch)
    out = run_tiny(capsys)
    assert out["correct"] is False, out["checked"]


def test_judge_refuses_missing_and_nan():
    nums = {"loss1": 0.0, "loss2": 0.0, "grad": 0.0, "change": math.nan}
    assert compare.judge(nums, TINY_LIMITS)[0] is False
    nums["change"] = 0.0
    assert compare.judge(nums, TINY_LIMITS)[0] is True
    assert compare.judge(nums, {})[0] is False
