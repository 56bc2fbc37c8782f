"""Sweep-executor guarantees: the parallel (S, G) executor, the
G-collapsed multi-G sweeps, the knob-tuple tape cache, and `Tape.run`
scratch buffers must all be *bitwise invisible* — identical frontiers,
objectives, and plans to the plain serial compiled engine."""
import json
import os
import pathlib
import signal
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_arch
from repro.core.costmodel import StageCostModel
from repro.core.intra_stage import tune_stage, tune_stage_multi_g
from repro.core.schedule import candidate_grid
from repro.core.sweep import plan_units, prefetch_frontiers, _shard_units
from repro.core.symbolic import Sym, ceil, compile_tape, smax, smin, where
from repro.core.tuner import MistTuner, TuneSpec, _space_knobs, tune

ARCH = "granite-3-8b"
SHAPE = ShapeConfig("t", 4096, 32, "train")
SMALL = dict(stage_counts=(1, 2), grad_accums=(2, 4))
SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _spec(space="mist", workers=1, **kw):
    cfg = get_arch(ARCH)
    return TuneSpec(arch=cfg, seq_len=SHAPE.seq_len,
                    global_batch=SHAPE.global_batch, n_devices=16,
                    space=space, workers=workers, **{**SMALL, **kw})


def _report_key(rep):
    return (rep.objective, rep.plan, rep.best_S, rep.best_G,
            tuple(rep.per_sg), rep.n_milp)


# -- parallel vs serial plan equivalence --------------------------------------


@pytest.mark.parametrize("space", ["megatron", "zero", "mist", "uniform"])
def test_executor_plan_identical_to_serial(space):
    cfg = get_arch(ARCH)
    reps = [tune(cfg, SHAPE, 16, space=space, workers=w, **SMALL)
            for w in (0, 1, 4)]
    assert _report_key(reps[0]) == _report_key(reps[1]) \
        == _report_key(reps[2])


def test_workers4_deterministic_across_runs():
    cfg = get_arch(ARCH)
    a = tune(cfg, SHAPE, 16, space="mist", workers=4, **SMALL)
    b = tune(cfg, SHAPE, 16, space="mist", workers=4, **SMALL)
    assert _report_key(a) == _report_key(b)


# -- frontier-memo merge ------------------------------------------------------


def _memo_snapshot(tuner):
    return {k: [(p.t, p.d, p.mem, p.cand) for p in r.frontier]
            for k, r in tuner._frontier_memo.items()}


def test_memo_merge_matches_serial_memo():
    """Sharded workers must reassemble exactly the serial executor's memo:
    same keys, same frontiers."""
    knobs = _space_knobs("mist", get_arch(ARCH).num_layers)
    t1 = MistTuner(_spec())
    st1 = prefetch_frontiers(t1, t1._cells(), knobs, workers=1)
    t4 = MistTuner(_spec(workers=4))
    st4 = prefetch_frontiers(t4, t4._cells(), knobs, workers=4)
    assert st4.workers_used > 1
    assert st1.n_swept == st4.n_swept
    assert _memo_snapshot(t1) == _memo_snapshot(t4)


def test_memo_entries_match_standalone_tune_stage():
    """Executor-produced frontiers == direct tune_stage calls (the
    across-unit batched refinement must be invisible)."""
    cfg = get_arch(ARCH)
    spec = _spec()
    knobs = _space_knobs("mist", cfg.num_layers)
    tuner = MistTuner(spec)
    cells = tuner._cells()
    prefetch_frontiers(tuner, cells, knobs, workers=1)
    plan = plan_units(MistTuner(spec), cells, knobs)  # fresh: nothing memoized
    assert len(plan)
    for (layers, n_dev, role, inflight), gs in zip(plan.units,
                                                   plan.gs_per_unit):
        for G in gs:
            key = tuner._memo_key(layers=layers, n_dev=n_dev, G=G,
                                  role=role, inflight=inflight, knobs=knobs)
            got = tuner._frontier_memo[key]
            ref = tune_stage(
                cfg, seq_len=spec.seq_len, layers=layers, n_devices=n_dev,
                global_batch_per_stage=spec.global_batch, grad_accum=G,
                has_embed=role[0], has_head=role[1], inflight=inflight,
                zeros=knobs["zeros"], ratios=knobs["ratios"],
                ratio_dims=knobs["ratio_dims"],
                ckpt_values=None, max_tp=spec.max_tp,
                max_front=spec.max_front,
                scm=tuner.scm(*role), refine=bool(knobs["ratio_dims"]))
            assert got.n_evaluated == ref.n_evaluated
            assert got.n_feasible == ref.n_feasible
            assert [(p.t, p.d, p.mem, p.cand) for p in got.frontier] \
                == [(p.t, p.d, p.mem, p.cand) for p in ref.frontier]


def test_plan_units_skips_memoized_hypotheses():
    knobs = _space_knobs("mist", get_arch(ARCH).num_layers)
    tuner = MistTuner(_spec())
    cells = tuner._cells()
    prefetch_frontiers(tuner, cells, knobs, workers=1)
    again = plan_units(tuner, cells, knobs)
    assert len(again) == 0
    stats = prefetch_frontiers(tuner, cells, knobs, workers=1)
    assert stats.n_swept == 0


def test_shard_units_partitions_all_units():
    knobs = _space_knobs("mist", get_arch(ARCH).num_layers)
    tuner = MistTuner(_spec())
    plan = plan_units(tuner, tuner._cells(), knobs)
    shards = _shard_units(plan, 3)
    flat = sorted(i for s in shards for i in s)
    assert flat == list(range(len(plan)))


# -- G-collapsed sweeps -------------------------------------------------------


def test_tune_stage_multi_g_bitwise_equivalent():
    cfg = get_arch(ARCH)
    kw = dict(seq_len=4096, layers=20, n_devices=16,
              global_batch_per_stage=32, has_embed=False, has_head=True,
              inflight=2.0)
    gs = (1, 2, 4, 8)
    multi = tune_stage_multi_g(cfg, grad_accums=gs, **kw)
    for G in gs:
        single = tune_stage(cfg, grad_accum=G, **kw)
        assert multi[G].n_evaluated == single.n_evaluated
        assert multi[G].n_feasible == single.n_feasible
        assert [(p.t, p.d, p.mem, p.cand) for p in multi[G].frontier] \
            == [(p.t, p.d, p.mem, p.cand) for p in single.frontier]


def test_tune_stage_multi_g_handles_indivisible_g():
    cfg = get_arch(ARCH)
    res = tune_stage_multi_g(cfg, seq_len=2048, layers=8, n_devices=4,
                             global_batch_per_stage=8, grad_accums=(3, 16))
    # G=3 leaves no legal (b, dp); G=16 > batch/dp for dp>... both empty-ish
    assert res[3].n_evaluated == 0
    assert res[3].frontier == []


# -- knob-tuple tape cache ----------------------------------------------------


def test_time_cache_hit_returns_identical_results():
    cfg = get_arch(ARCH)
    scm = StageCostModel(cfg, 2048)
    grid = candidate_grid(cfg, n_devices=8, layers=16, global_batch=16,
                          grad_accum=2)
    env = grid.env(layers=16, grad_accum=2, inflight=1.0)
    fresh = scm.evaluate_times(env)
    key = ("k", 1)
    first = scm.evaluate_times(env, cache_key=key)
    assert scm.cache_misses >= 1
    hit = scm.evaluate_times(env, cache_key=key)
    assert scm.cache_hits >= 1
    for k in ("t_stable", "d_delta", "t_step", "t_first", "t_last"):
        np.testing.assert_array_equal(fresh[k], first[k])
        np.testing.assert_array_equal(fresh[k], hit[k])


def test_time_cache_recomputes_t_step_per_g():
    """The cache stores only G-independent outputs; t_step must follow the
    caller's G even on a hit."""
    cfg = get_arch(ARCH)
    scm = StageCostModel(cfg, 2048)
    grid = candidate_grid(cfg, n_devices=8, layers=16, global_batch=16,
                          grad_accum=2)
    env = grid.env(layers=16, grad_accum=2, inflight=1.0)
    key = ("g-indep",)
    a = scm.evaluate_times(env, cache_key=key)
    env8 = dict(env, G=8.0)
    b = scm.evaluate_times(env8, cache_key=key)
    np.testing.assert_array_equal(a["t_stable"], b["t_stable"])
    np.testing.assert_array_equal(8.0 * a["t_stable"] + a["d_delta"],
                                  b["t_step"])


def test_time_tape_is_g_and_inflight_independent():
    """The structural guarantee the whole G-collapse rests on: the time
    tape loads neither G nor inflight, the memory tape never loads G."""
    scm = StageCostModel(get_arch(ARCH), 2048)
    time_syms = {n for n, _ in scm.tape_time.sym_loads}
    mem_syms = {n for n, _ in scm.tape_mem.sym_loads}
    assert "G" not in time_syms and "inflight" not in time_syms
    assert "G" not in mem_syms


# -- MILPs after a fork --------------------------------------------------------


MILP_AFTER_FORK = r"""
import json
from repro.configs.base import ShapeConfig, get_arch
from repro.core.tuner import tune

cfg = get_arch("granite-3-8b")
shape = ShapeConfig("t", 4096, 32, "train")
small = dict(stage_counts=(1, 2), grad_accums=(2, 4))

def key(rep):
    return repr((rep.objective, rep.plan, rep.best_S, rep.best_G,
                 tuple(rep.per_sg), rep.n_milp))

same = {}
for space in ("megatron", "zero"):
    serial = tune(cfg, shape, 16, space=space, workers=0, **small)
    pooled = tune(cfg, shape, 16, space=space, workers=4, **small)
    same[space] = key(serial) == key(pooled)
print(json.dumps(same))
"""


def test_pool_tune_after_in_process_milp_returns():
    """A process that has already solved a MILP in-process (HiGHS set up
    its thread scheduler) then tunes with a forked pool, as any session
    that tunes twice does.  The tune must return, with the serial plans.
    Run in a fresh interpreter so the HiGHS and pool state are this
    test's own, and under a timeout so a hang fails instead of stalling.
    The child leads its own process group, so a timeout also kills the
    pool workers it forked."""
    env = {**os.environ, "PYTHONPATH": SRC}
    p = subprocess.Popen([sys.executable, "-c", MILP_AFTER_FORK],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True, env=env, start_new_session=True)
    try:
        out, err = p.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        os.killpg(p.pid, signal.SIGKILL)
        p.communicate()
        pytest.fail("tune(workers=4) did not return within 120 s of an "
                    "in-process MILP solve")
    assert p.returncode == 0, f"stderr:\n{err[-3000:]}"
    assert json.loads(out.strip().splitlines()[-1]) == \
        {"megatron": True, "zero": True}


# -- Tape scratch buffers -----------------------------------------------------


def test_tape_scratch_bitwise_and_output_freshness():
    x, y = Sym("x"), Sym("y")
    e1 = smin(x / y, ceil(x) * 2.0) + where(x > y, x - y, y - x)
    e2 = (x / y) * (x / y) + e1 + smax(x, 3.0)
    tape = compile_tape({"e1": e1, "e2": e2})
    sc = tape.make_scratch()
    env = {"x": np.linspace(0.1, 9.0, 997), "y": 2.0}
    base = tape.run(env)
    tape.run(env, sc)
    out = tape.run(env, sc)          # buffers active
    for k in base:
        np.testing.assert_array_equal(base[k], out[k])
    # outputs are fresh arrays, never aliases of scratch buffers
    assert not any(out[k] is b for k in out for b in sc.bufs
                   if b is not None)
    # self-resizes across batch-shape changes, and scalar envs do not
    # broadcast into stale buffers
    env2 = {"x": np.linspace(0.1, 9.0, 13), "y": 2.0}
    np.testing.assert_array_equal(tape.run(env2, sc)["e2"],
                                  tape.run(env2)["e2"])
    env3 = {"x": 4.0, "y": 2.0}
    a, b = tape.run(env3, sc), tape.run(env3)
    assert np.shape(a["e1"]) == np.shape(b["e1"])
    np.testing.assert_array_equal(a["e1"], b["e1"])
