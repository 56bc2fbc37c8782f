"""Paper Fig. 16: tuning time as optimizations are enabled one by one
(GPT-22B on 32 chips), plus three engine-level measurements:

  * batched symbolic substitution vs a per-config evaluation loop (the
    paper's >1e5x-vs-simulators claim, isolated to the batching win),
  * the compiled tuning engine (expression tapes + struct-of-arrays grids +
    frontier memoization) vs the legacy interpreted engine kept in-tree as
    the pre-refactor baseline — `tune(..., engine=...)` selects the path
    and both return identical frontiers/objectives/plans, and
  * the parallel (S, G) sweep executor (`core/sweep.py`,
    `tune(..., workers=N)`) vs the serial compiled engine (`workers=0`):
    G-collapsed hypothesis sweeps on a persistent forked worker pool +
    across-unit batched refinement, with the frontier memo sharded across
    workers and merged at the join (the per-cell MILPs are solved in the
    parent).  Selected plans are asserted byte-identical.  Reported cold
    (worker caches cleared between runs) and warm (the persistent
    workers' knob-tuple caches left alone — what repeated `tune()` calls
    in one session observe).

A fourth measurement compares the tape *evaluation backends* on one large
candidate grid (`run_backend_speedup`): the numpy instruction loop vs the
jax lowering (`Tape.lower_jax`) in both exact mode (per-op device
execution, bitwise identical under x64 — what `backend="jax"` runs) and
fused mode (one `jax.jit` program; FMA-contracted on CPU, so only close,
not bitwise).  On accelerators the fused path is the headline; on a
small CPU host expect parity-or-overhead below the `auto` threshold —
which is exactly why `auto` thresholds on grid size.

A fifth measurement (`run_memory_agreement`) closes the tuner->runtime
loop: for every feasible golden-plan config, the symbolic memory
prediction that selected the plan vs the layout-evaluated bytes of its
lowering (`repro.lowering`), asserted within `MEMORY_REL_TOL` — both in
total AND per term (state / act / transient / logits, each normalized
by the predicted total so a future accuracy regression is attributable
to a specific term).  The --json document carries the full per-config
comparison, including the per-term breakdown, as the
`predicted_vs_lowered_memory` table (uploaded as a CI artifact).

A sixth, opt-in measurement (`run_distributed_speedup`, flags
--distributed / --distributed-only) covers the distributed executor
(docs/distributed-sweep.md): one golden cell tuned cold-serial, fanned
out to two real `tools/tune_worker.py` daemon processes over the socket
RPC (byte-identical plan asserted), and answered warm from a persistent
`MemoStore` — the warm path is asserted >= 100x faster than the cold
sweep.

Run with --smoke for a CI-sized invocation; --json PATH additionally
writes the emitted rows as a JSON document (uploaded as a CI artifact).
"""
from __future__ import annotations

import json
import sys
import time
from typing import List

import numpy as np

from benchmarks.common import FAST_TUNE, emit, gpt_config, train_shape
from repro.core.costmodel import StageCostModel
from repro.core.schedule import candidate_grid, enumerate_candidates
from repro.core.tuner import tune

STEPS = ("megatron", "ckpt", "zero", "offload", "mist")


def run_tuning_time(size: str = "22b", n_dev: int = 32, gbs: int = 64
                    ) -> List[str]:
    rows = []
    for space in STEPS:
        t0 = time.perf_counter()
        rep = tune(gpt_config(size), train_shape(gbs, 2048), n_dev,
                   space=space, **FAST_TUNE)
        dt = time.perf_counter() - t0
        rows.append(emit(
            f"tuning_time/{space}", dt * 1e6,
            f"seconds={dt:.2f} points={rep.n_points} milps={rep.n_milp} "
            f"feasible={rep.plan is not None}"))
    return rows


def run_engine_speedup(size: str = "6.7b", n_dev: int = 32, gbs: int = 64,
                       space: str = "mist", repeats: int = 3) -> List[str]:
    """Compiled engine vs the legacy pre-refactor path, same machine, same
    (identical, asserted) results.  A warm-up tune first so one-time module
    imports (scipy HiGHS, etc.) don't pollute either side; each engine is
    timed min-of-N to suppress scheduler noise (min vs min is the standard
    noise-free microbenchmark estimate).  `workers=0` pins the compiled
    engine to its serial (PR-1) path so this row keeps measuring the
    compilation win in isolation."""
    cfg, shape = gpt_config(size), train_shape(gbs, 2048)
    tune(cfg, shape, n_dev, space="megatron", **FAST_TUNE)   # warm-up

    def best_of(n, **kw):
        rep, best = None, float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            rep = tune(cfg, shape, n_dev, space=space, **FAST_TUNE, **kw)
            best = min(best, time.perf_counter() - t0)
        return rep, best

    new, t_new = best_of(repeats, workers=0)
    old, t_old = best_of(repeats, engine="legacy")
    assert new.objective == old.objective and new.plan == old.plan, \
        "engine equivalence violated"
    return [
        emit("tuning_time/engine_compiled", t_new * 1e6,
             f"seconds={t_new:.2f} points={new.n_points} space={space}"),
        emit("tuning_time/engine_legacy", t_old * 1e6,
             f"seconds={t_old:.2f} points={old.n_points} space={space}"),
        emit("tuning_time/engine_speedup", 0.0,
             f"{t_old / t_new:.1f}x identical_results=True"),
    ]


def run_parallel_speedup(size: str = "6.7b", n_dev: int = 32, gbs: int = 64,
                         space: str = "mist", workers: int = 4,
                         repeats: int = 5) -> List[str]:
    """Parallel sweep executor vs the serial compiled engine.

    Cold rows clear the persistent workers' knob-tuple caches between
    runs, so they measure the per-tune executor speedup (parallel sweeps
    + batched refinement).  The warm row leaves the worker caches alone,
    which is what a session issuing many `tune()` calls actually
    experiences.  Byte-identical plans are asserted
    between every serial and parallel invocation."""
    from repro.core.sweep import clear_worker_caches, warm_pool
    cfg, shape = gpt_config(size), train_shape(gbs, 2048)
    tune(cfg, shape, n_dev, space=space, workers=workers,
         **FAST_TUNE)                                        # warm pool

    def best_of(n, *, clear=False, **kw):
        rep, best = None, float("inf")
        for _ in range(n):
            if clear:
                # fresh worker processes (deterministically cold caches),
                # but the one-time pool fork is paid before the timer —
                # it is session setup, not per-tune cost
                clear_worker_caches()
                warm_pool(workers)
            t0 = time.perf_counter()
            rep = tune(cfg, shape, n_dev, space=space, **FAST_TUNE, **kw)
            best = min(best, time.perf_counter() - t0)
        return rep, best

    ser, t_ser = best_of(repeats, workers=0)
    cold, t_cold = best_of(repeats, clear=True, workers=workers)
    warm, t_warm = best_of(repeats, workers=workers)
    for rep in (cold, warm):
        assert rep.objective == ser.objective and rep.plan == ser.plan \
            and rep.per_sg == ser.per_sg, "executor equivalence violated"
    hitrate = cold.n_cache_hits / max(1, cold.n_cache_hits
                                      + cold.n_cache_misses)
    warm_hitrate = warm.n_cache_hits / max(1, warm.n_cache_hits
                                           + warm.n_cache_misses)
    return [
        emit("tuning_time/parallel_serial", t_ser * 1e6,
             f"seconds={t_ser:.2f} workers=0 space={space}"),
        emit(f"tuning_time/parallel_workers{workers}_cold", t_cold * 1e6,
             f"seconds={t_cold:.2f} cache_hitrate={hitrate:.2f} "
             f"memo_swept={cold.n_swept}"),
        emit(f"tuning_time/parallel_workers{workers}_warm", t_warm * 1e6,
             f"seconds={t_warm:.2f} cache_hitrate={warm_hitrate:.2f}"),
        emit("tuning_time/parallel_speedup", 0.0,
             f"{t_ser / t_warm:.1f}x warm {t_ser / t_cold:.1f}x cold "
             f"identical_plans=True"),
    ]


def run_backend_speedup(size: str = "6.7b", rows: int = 1_000_000,
                        repeats: int = 3) -> List[str]:
    """Tape backends on one large synthetic candidate grid: numpy vs jax
    exact (bitwise-asserted) vs jax fused (`jax.jit`, closeness-asserted;
    its one-time compile is reported separately from the steady state).
    Emits a skip row — instead of failing — when jax is unavailable, so
    numpy-only containers still run the benchmark file end to end."""
    from repro import compat
    cfg = gpt_config(size)
    scm = StageCostModel(cfg, 2048)
    tape = scm.tape_time
    rng = np.random.default_rng(0)
    env = {name: rng.uniform(1.0, 8.0, rows)
           for name, _slot in tape.sym_loads}

    def best_of(fn):
        b = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            b = min(b, time.perf_counter() - t0)
        return b

    scratch = tape.make_scratch()
    ref = tape.run(env, scratch)
    t_np = best_of(lambda: tape.run(env, scratch))
    out = [emit("tuning_time/backend_numpy", t_np * 1e6,
                f"seconds={t_np:.3f} rows={rows} instrs={len(tape)}")]
    if not compat.has_jax():
        out.append(emit("tuning_time/backend_jax", 0.0,
                        "skipped=jax_unavailable"))
        return out
    jax, _jnp = compat.require_jax()
    with compat.enable_x64():
        exact = tape.lower_jax()
        run_exact = lambda: jax.block_until_ready(  # noqa: E731
            list(exact(env).values()))
        got = exact(env)
        for k in ref:
            g = np.asarray(got[k])
            r = np.broadcast_to(ref[k], g.shape)
            if tape.jax_bitexact:       # same guard the dispatcher uses
                assert np.array_equal(r, g), \
                    f"jax exact backend not bitwise identical on {k}"
            else:                       # pow/log2 tape: closeness only
                assert np.allclose(g, r, rtol=1e-12, atol=0), \
                    f"jax exact backend drifted on non-bitexact op: {k}"
        t_ex = best_of(run_exact)
        fused = tape.lower_jax(fused=True)
        t0 = time.perf_counter()
        fgot = fused(env)
        jax.block_until_ready(list(fgot.values()))
        t_compile = time.perf_counter() - t0
        rel = 0.0
        for k in ref:
            f = np.asarray(fgot[k])
            r = np.broadcast_to(ref[k], f.shape)
            denom = np.maximum(np.abs(r), 1e-300)
            rel = max(rel, float(np.max(np.abs(f - r) / denom)))
        # FMA contraction drift is ~1-2 ulp per op, but cancellation in
        # the d_delta-style subtractions amplifies it; ~1e-10 observed
        assert rel < 1e-8, \
            f"jax fused backend drifted beyond expectations: {rel:.2e}"
        t_fu = best_of(lambda: jax.block_until_ready(
            list(fused(env).values())))
    out += [
        emit("tuning_time/backend_jax_exact", t_ex * 1e6,
             f"seconds={t_ex:.3f} bitwise_identical={tape.jax_bitexact}"),
        emit("tuning_time/backend_jax_fused", t_fu * 1e6,
             f"seconds={t_fu:.3f} compile_s={t_compile:.2f} "
             f"max_rel_err={rel:.1e}"),
        emit("tuning_time/backend_speedup", 0.0,
             f"{t_np / t_ex:.2f}x exact {t_np / t_fu:.2f}x fused "
             f"(numpy/jax; >1 means jax wins)"),
    ]
    return out


def memory_agreement_table() -> List[dict]:
    """Predicted-vs-lowered memory agreement per golden-plan config: the
    symbolic estimate that selected each plan vs the spec-walked bytes of
    its lowering (`repro.lowering.memory_consistency`).  Infeasible golden
    cells (no plan pinned) emit a skip entry; numpy-only containers (no
    jax → no PartitionSpec tables) skip the whole table."""
    from repro import compat
    if not compat.has_jax():
        return [{"skipped": "jax_unavailable"}]
    from repro.configs.base import ShapeConfig, get_arch
    from repro.core import golden
    from repro.core.plan import Plan
    from repro.lowering import MEMORY_REL_TOL, memory_consistency

    w = golden._WORKLOAD
    shape = ShapeConfig("golden", w["seq_len"], w["global_batch"], "train")
    table = []
    for space in golden.GOLDEN_SPACES:
        for arch in golden.GOLDEN_ARCHS:
            path = golden.golden_path(space, arch)
            if not path.exists():
                continue
            doc = json.loads(path.read_text())["doc"]
            row = {"space": space, "arch": arch}
            if doc["plan"] is None:
                table.append({**row, "skipped": "infeasible"})
                continue
            plan = Plan.from_json(json.dumps(doc["plan"]))
            mc = memory_consistency(get_arch(arch), shape, plan)
            table.append({
                **row,
                "predicted_bytes": mc["predicted_bytes"],
                "lowered_bytes": mc["lowered_bytes"],
                "rel_error": mc["rel_error"],
                "within_tol": mc["within_tol"],
                "tol": MEMORY_REL_TOL,
                # per-term breakdown at the lowered peak stage; rel
                # errors are normalized by the predicted TOTAL bytes
                # (what the disagreement is worth against the budget)
                "terms": mc["terms"],
            })
    return table


def run_memory_agreement(table: List[dict] = None) -> List[str]:
    rows = []
    for r in (memory_agreement_table() if table is None else table):
        if "space" not in r:
            rows.append(emit("tuning_time/memory_agreement", 0.0,
                             f"skipped={r['skipped']}"))
            continue
        name = f"tuning_time/memory_agreement/{r['space']}_{r['arch']}"
        if "skipped" in r:
            rows.append(emit(name, 0.0, f"skipped={r['skipped']}"))
        else:
            assert r["within_tol"], r   # the lowering contract, enforced
            per_term = {k: v["rel_error"] for k, v in r["terms"].items()
                        if k in ("state", "act", "transient", "logits")}
            for k, rel in per_term.items():     # ... term by term, too
                assert rel <= r["tol"], (name, k, rel, r)
            rows.append(emit(
                name, 0.0,
                f"predicted_GiB={r['predicted_bytes'] / 2**30:.3f} "
                f"lowered_GiB={r['lowered_bytes'] / 2**30:.3f} "
                f"rel_error={r['rel_error']:.4f} "
                + " ".join(f"rel_{k}={v:.4f}"
                           for k, v in per_term.items())))
    return rows


def _spawn_tune_worker(repo_root, timeout: float = 60.0):
    """Launch `tools/tune_worker.py --port 0` as a subprocess and return
    (Popen, "host:port") once it prints its bound address."""
    import os
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    src = str(repo_root / "src")
    env["PYTHONPATH"] = src + os.pathsep * bool(env.get("PYTHONPATH")) \
        + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [_sys.executable, str(repo_root / "tools" / "tune_worker.py"),
         "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        text=True, bufsize=1)
    t0 = time.perf_counter()
    line = proc.stdout.readline()
    if "listening on" not in line or time.perf_counter() - t0 > timeout:
        proc.kill()
        raise RuntimeError(f"tune_worker failed to start: {line!r}")
    return proc, line.rsplit(" ", 1)[-1].strip()


def run_distributed_speedup(repeats: int = 3) -> List[str]:
    """The distributed table (docs/distributed-sweep.md): one golden cell
    tuned cold-serial, fanned out to two real `tools/tune_worker.py`
    daemon processes over the socket RPC, and served warm from a
    persistent memo store — with every variant's plan asserted identical
    to serial, and the warm-memo path asserted >= 100x faster than the
    cold sweep (the ROADMAP "milliseconds when warm" target)."""
    import pathlib
    import tempfile

    from repro.configs.base import ShapeConfig, get_arch
    from repro.core import golden, remote
    from repro.core.tuner import MistTuner, TuneSpec

    w = golden._WORKLOAD
    arch = get_arch(golden.GOLDEN_ARCHS[0])
    base = dict(arch=arch, seq_len=w["seq_len"],
                global_batch=w["global_batch"], n_devices=w["n_devices"],
                space="mist", stage_counts=w["stage_counts"],
                grad_accums=w["grad_accums"])

    def best_of(n, **kw):
        rep, best = None, float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            rep = MistTuner(TuneSpec(**base, **kw)).tune()
            best = min(best, time.perf_counter() - t0)
        return rep, best

    ser, t_ser = best_of(repeats, workers=0)

    repo_root = pathlib.Path(__file__).resolve().parent.parent
    procs_addrs = [_spawn_tune_worker(repo_root) for _ in range(2)]
    try:
        hosts = tuple(a for _p, a in procs_addrs)
        dist, t_dist = best_of(repeats, workers=2, hosts=hosts)
        assert dist.objective == ser.objective and dist.plan == ser.plan \
            and dist.per_sg == ser.per_sg, "multi-host plan diverged"
        assert dist.hosts_used == 2 and dist.n_host_failures == 0, \
            (dist.hosts_used, dist.n_host_failures)
    finally:
        for proc, addr in procs_addrs:
            try:
                remote.request(addr, "shutdown", timeout=5, retries=0)
            except Exception:
                proc.kill()
            proc.wait(timeout=10)

    with tempfile.TemporaryDirectory() as memo_dir:
        cold, t_cold = best_of(1, memo_dir=memo_dir)
        assert not cold.from_memo
        warm, t_warm = best_of(repeats, memo_dir=memo_dir)
        assert warm.from_memo, "second tune() missed the report cache"
        assert warm.plan == ser.plan and warm.objective == ser.objective, \
            "memo-store plan diverged"
        speedup = t_cold / t_warm
        assert speedup >= 100, \
            f"warm memo path only {speedup:.0f}x faster than cold"

    return [
        emit("tuning_time/distributed_serial", t_ser * 1e6,
             f"seconds={t_ser:.2f} workers=0"),
        emit("tuning_time/distributed_hosts2", t_dist * 1e6,
             f"seconds={t_dist:.2f} hosts=2 workers=2 "
             f"host_failures={dist.n_host_failures} identical_plan=True"),
        emit("tuning_time/distributed_memo_cold", t_cold * 1e6,
             f"seconds={t_cold:.2f} memo_store=cold"),
        emit("tuning_time/distributed_memo_warm", t_warm * 1e6,
             f"seconds={t_warm:.5f} from_memo=True"),
        emit("tuning_time/distributed_speedup", 0.0,
             f"{speedup:.0f}x warm-memo {t_ser / t_dist:.2f}x hosts2 "
             f"identical_plans=True"),
    ]


def run_batch_speedup(size: str = "6.7b") -> List[str]:
    """Batched symbolic substitution vs per-config evaluation loop."""
    cfg = gpt_config(size)
    scm = StageCostModel(cfg, 2048)
    grid = candidate_grid(cfg, n_devices=32, layers=32, global_batch=64,
                          grad_accum=8)
    env = grid.env(layers=32, grad_accum=8)
    # batched (compiled tape over the whole struct-of-arrays grid)
    t0 = time.perf_counter()
    scm.evaluate(env)
    t_batched = time.perf_counter() - t0
    # per-config loop (sample to keep runtime sane, scale up)
    cands = list(enumerate_candidates(cfg, n_devices=32, layers=32,
                                      global_batch=64, grad_accum=8))
    sample = cands[:: max(1, len(cands) // 200)][:200]
    t0 = time.perf_counter()
    for c in sample:
        e1 = scm.env_from_candidates([c], layers=32, grad_accum=8)
        scm.evaluate(e1)
    t_loop = (time.perf_counter() - t0) / len(sample) * len(cands)
    ratio = t_loop / t_batched
    rows = [
        emit("tuning_time/batched_eval", t_batched / len(grid) * 1e6,
             f"n={len(grid)} total_s={t_batched:.4f}"),
        emit("tuning_time/per_config_eval", t_loop / len(cands) * 1e6,
             f"extrapolated_total_s={t_loop:.2f}"),
        emit("tuning_time/batching_speedup", 0.0, f"{ratio:.0f}x"),
    ]
    return rows


def run(smoke: bool = False, mem_table: List[dict] = None) -> List[str]:
    if smoke:
        return (run_tuning_time(size="1.3b", n_dev=8, gbs=16)
                + run_engine_speedup(size="1.3b", n_dev=8, gbs=16)
                + run_parallel_speedup(size="1.3b", n_dev=8, gbs=16,
                                       repeats=3)
                + run_batch_speedup(size="1.3b")
                + run_backend_speedup(size="1.3b", rows=120_000, repeats=2)
                + run_memory_agreement(mem_table))
    return (run_tuning_time() + run_engine_speedup()
            + run_parallel_speedup() + run_batch_speedup()
            + run_backend_speedup() + run_memory_agreement(mem_table))


def rows_to_json(rows: List[str], mem_table: List[dict] = None) -> dict:
    out = []
    for r in rows:
        name, value, notes = r.split(",", 2)
        out.append({"name": name, "us_per_call": float(value),
                    "notes": notes})
    return {"benchmark": "tuning_time", "rows": out,
            "predicted_vs_lowered_memory":
                memory_agreement_table() if mem_table is None else mem_table}


if __name__ == "__main__":
    # --distributed appends the multi-host + memo-store table
    # (docs/distributed-sweep.md) to the standard run; --distributed-only
    # runs just that table (the CI fan-out smoke job), skipping the
    # memory-agreement recomputation.  Both ride the --json artifact.
    if "--distributed-only" in sys.argv:
        mem_table: List[dict] = []
        rows = run_distributed_speedup()
    else:
        mem_table = memory_agreement_table()   # computed once, used twice
        rows = run(smoke="--smoke" in sys.argv, mem_table=mem_table)
        if "--distributed" in sys.argv:
            rows += run_distributed_speedup()
    if "--json" in sys.argv:
        path = sys.argv[sys.argv.index("--json") + 1]
        with open(path, "w") as f:
            json.dump(rows_to_json(rows, mem_table), f, indent=2)
        print(f"wrote {path}")
