"""Measurement half of the calibration loop (docs/calibration.md).

Runs golden cells END-TO-END through the real execution path —
``lower_plan`` → ``make_train_step`` → compiled XLA steps on the live
mesh — and records, per cell:

* warmed median step wall time (``warmup`` discarded steps, then
  ``steps`` timed steps with ``block_until_ready``; the median resists
  host-side jitter),
* the compiled executable's memory analysis (argument + temp + output −
  alias, per device — the ``tools/calibrate_reserved.py`` protocol) and
  the live allocator's peak where the backend keeps one (TPU/GPU).

Cells are REDUCED same-family configs of the golden-fixture archs (the
``launch/train.py --smoke`` convention) in several plan variants chosen
to exercise distinct time-tape item mixes: pure-DP ZeRO-0 (compute +
one grad all-reduce), ZeRO-2 + full recompute (per-microbatch
reduce-scatter + recompute time), and TP=2 (per-layer collectives) when
the head counts divide.  Cells that fail to lower/execute are returned
as a skip list with reasons, never silently dropped.

CPU caveat: XLA:CPU legalizes bf16 compute to f32 and overlaps nothing,
so measured times are *host* ground truth — exactly what a cpu-platform
profile should fit, and far from the V5E defaults (which is what the
uncalibrated-vs-fitted error spread in ``benchmarks/accuracy.py
--measured`` demonstrates).  Re-run on a real accelerator host to fit a
tpu/gpu profile.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from repro.configs.base import ArchConfig, ShapeConfig, get_arch
from repro.core.plan import Plan, single_stage_plan

GOLDEN_ARCHS = ("granite-3-8b", "qwen2-moe-a2.7b")
DEFAULT_SEQ = 128


@dataclass
class MeasuredCell:
    """One executed cell: the plan that ran plus what the hardware said."""
    label: str
    arch: str                 # full arch name; config() re-derives reduced
    reduced: bool
    seq_len: int
    global_batch: int
    plan: Plan
    steps: int
    step_seconds: Tuple[float, ...]
    t_measured: float         # warmed median step seconds
    memory: Dict[str, Optional[float]] = field(default_factory=dict)

    def config(self) -> ArchConfig:
        cfg = get_arch(self.arch)
        return cfg.reduced() if self.reduced else cfg

    def shape(self) -> ShapeConfig:
        return ShapeConfig(self.label, self.seq_len, self.global_batch,
                           "train")

    def to_doc(self) -> Dict:
        return {
            "label": self.label, "arch": self.arch, "reduced": self.reduced,
            "seq_len": self.seq_len, "global_batch": self.global_batch,
            "plan": json.loads(self.plan.to_json()),
            "steps": self.steps, "step_seconds": list(self.step_seconds),
            "t_measured": self.t_measured, "memory": dict(self.memory),
        }


def _cell_plans(cfg: ArchConfig, n_dev: int) -> List[Tuple[str, Plan]]:
    """Plan variants for one arch on ``n_dev`` host devices, each lighting
    up a different subset of time-tape items."""
    L = cfg.num_layers
    G = 2
    out = [
        (f"dp{n_dev}_z0", single_stage_plan(
            L, dp=n_dev, tp=1, micro_batch=1, grad_accum=G,
            zero=0, ckpt_layers=0)),
        (f"dp{n_dev}_z2_ckpt", single_stage_plan(
            L, dp=n_dev, tp=1, micro_batch=1, grad_accum=G,
            zero=2, ckpt_layers=L)),
    ]
    if n_dev % 2 == 0 and n_dev >= 2 and cfg.num_heads % 2 == 0:
        out.append((f"dp{n_dev // 2}_tp2_z1", single_stage_plan(
            L, dp=n_dev // 2, tp=2, micro_batch=1, grad_accum=G,
            zero=1, ckpt_layers=L // 2)))
    return out


def measure_plan(cfg: ArchConfig, shape: ShapeConfig, plan: Plan, *,
                 steps: int = 4, warmup: int = 2
                 ) -> Tuple[float, Tuple[float, ...],
                            Dict[str, Optional[float]]]:
    """Execute one cell and return (median step seconds, all step times,
    memory stats).  Same execution path as ``launch/train.py --smoke``."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_host_mesh
    from repro.lowering import lower_plan
    from repro.models.zoo import build_model
    from repro.training.data import BatchSpec, SyntheticLM
    from repro.training.step import init_sharded_state, make_train_step

    st0 = plan.stages[0]
    mesh = make_host_mesh(st0.dp * st0.tp, st0.tp)
    model = build_model(cfg)
    low = lower_plan(cfg, shape, plan, mesh)
    mem: Dict[str, Optional[float]] = {
        "modeled_peak_bytes": float(low.memory_report().peak_bytes)}
    with jax.set_mesh(mesh):
        step = make_train_step(model, plan, mesh, lowered=low)
        state, _shardings = init_sharded_state(
            model, plan, mesh, jax.random.PRNGKey(0), lowered=low)
        data = SyntheticLM(BatchSpec(global_batch=shape.global_batch,
                                     seq_len=shape.seq_len,
                                     vocab_size=cfg.vocab_size))
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        try:
            ma = step.fn.lower(state, batch).compile().memory_analysis()
            mem["executable_bytes"] = float(
                ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        except Exception:           # backend exposes no analysis: optional
            mem["executable_bytes"] = None
        for _ in range(max(1, warmup)):
            state, _metrics = step.fn(state, batch)
        jax.block_until_ready(state)
        times: List[float] = []
        for _ in range(max(1, steps)):
            t0 = time.perf_counter()
            state, _metrics = step.fn(state, batch)
            jax.block_until_ready(state)
            times.append(time.perf_counter() - t0)
        dev = jax.devices()[0]
        stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
        mem["allocator_peak_bytes"] = (stats or {}).get("peak_bytes_in_use")
    return sorted(times)[len(times) // 2], tuple(times), mem


def measure_cells(archs: Sequence[str] = GOLDEN_ARCHS, *,
                  steps: int = 4, warmup: int = 2,
                  seq_len: int = DEFAULT_SEQ, reduced: bool = True,
                  max_cells_per_arch: Optional[int] = None
                  ) -> Tuple[List[MeasuredCell], List[Dict]]:
    """Measure every cell variant of every arch on the current devices.

    Returns ``(cells, skipped)`` — skipped entries carry the failure
    reason so callers can report them (no-silent-caps)."""
    import jax

    n_dev = len(jax.devices())
    cells: List[MeasuredCell] = []
    skipped: List[Dict] = []
    for arch in archs:
        cfg = get_arch(arch)
        cfg_run = cfg.reduced() if reduced else cfg
        plans = _cell_plans(cfg_run, n_dev)
        if max_cells_per_arch is not None:
            dropped = plans[max_cells_per_arch:]
            skipped += [{"arch": arch, "label": lbl,
                         "error": "capped by max_cells_per_arch"}
                        for lbl, _ in dropped]
            plans = plans[:max_cells_per_arch]
        for label, plan in plans:
            st0 = plan.stages[0]
            gbs = st0.dp * st0.micro_batch * plan.grad_accum
            shape = ShapeConfig(label, seq_len, gbs, "train")
            try:
                t_med, ts, mem = measure_plan(cfg_run, shape, plan,
                                              steps=steps, warmup=warmup)
            except Exception as exc:
                skipped.append({"arch": arch, "label": label,
                                "error": f"{type(exc).__name__}: {exc}"})
                continue
            cells.append(MeasuredCell(
                label=f"{arch}/{label}", arch=arch, reduced=reduced,
                seq_len=seq_len, global_batch=gbs, plan=plan,
                steps=steps, step_seconds=ts, t_measured=t_med,
                memory=mem))
    return cells, skipped
