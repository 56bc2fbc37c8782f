"""A training cell: the train step of the plan the tuner picks, fed step
after step from the seed, and checked against the plain reference.

Set-up tunes, lowers, compiles and places the state, then drives the
step through the same call and feed as the window (``one_step``) for
``WARM_STEPS`` steps: the first two are the steps the reference follows,
read back between steps; the third only warms up.  The window then runs
whole steps until ``seconds`` have passed and blocks on the state.  Once
it has closed and the device's peak memory is read, the program's state
is freed and the reference runs the same two steps from the same seed."""
from __future__ import annotations

import gc
import math
import shutil
import time
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import numpy as np

from benchlib import compare, program
from benchlib.model import Dense, from_doc
from benchlib.peaks import Peaks
from benchlib.reference import Adam, Readings, Reference
from benchlib.spec import Cell, metric_reader
from benchlib.trace import Trace, read_trace
from benchlib.traffic import ZipfTokens

WARM_STEPS = 3


@dataclass
class Run:
    """What a training run measured; the per-layer readers take it."""
    cell: str
    model: Dense
    seq: int
    chips: int
    peaks: Peaks
    plan: dict
    tune_s: float
    predicted_step_s: float
    steps: int
    window_s: float
    tokens_per_step: int
    memory_peak_bytes: int
    trace: Optional[Trace] = None

    @property
    def tokens_per_s(self) -> float:
        return self.steps * self.tokens_per_step / self.window_s

    @property
    def step_s(self) -> float:
        return self.window_s / self.steps


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def reference_mesh(chips: int):
    if chips == 1:
        return None
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:chips]), ("x",))


def adam(mix: dict) -> Adam:
    return Adam(**mix["optimizer"])


class Trainer:
    """The program's step with its feed, as the window drives it."""

    def __init__(self, cell: Cell, seed: int, device_kind: str,
                 log: Callable[[str], None]):
        self.cell, self.log = cell, log
        self.m = from_doc(cell.config)
        mix = cell.traffic
        seq, rows = int(mix["seq_len"]), int(mix["global_batch"])
        self.tuned = program.tune(self.m, cell.config_name, seq, rows,
                                  cell.chips, device_kind)
        log(f"plan {program.plan_summary(self.tuned.plan)}, tuned in "
            f"{self.tuned.tune_s:.3f} s; predicted step "
            f"{self.tuned.predicted_step_s:.4f} s, "
            f"{self.tuned.predicted_bytes / 2**30:.3f} GiB")
        self.prog = program.Program(self.m, cell.config_name,
                                    self.tuned.plan, mix["optimizer"],
                                    seq, rows)
        self.reset(seed)

    def reset(self, seed: int) -> None:
        """The state and the feed of ``seed``, before its first step."""
        self.seed, self.step_index = seed, 0
        self.feed = ZipfTokens(self.cell.traffic, self.m.vocab, seed)
        self.prog.init_state(seed)

    def one_step(self) -> float:
        """Prepare the next batch, run the step, wait for its loss."""
        with jax.profiler.TraceAnnotation("bench.batch"):
            batch = jax.device_put(self.feed.batch(self.step_index))
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            loss = self.prog.run_step(batch)
        with jax.profiler.TraceAnnotation("bench.wait"):
            loss = float(loss)
        self.step_index += 1
        return loss

    def checked_steps(self) -> Readings:
        """Steps 1 and 2, read back for the comparison, then the warm-up
        steps that remain."""
        b1 = self.cell.traffic["optimizer"]["b1"]
        l1 = self.one_step()
        grads = self.prog.grad_norms(b1)
        l2 = self.one_step()
        change = self.prog.change_norms()
        while self.step_index < WARM_STEPS:
            self.one_step()
        self.prog.block()
        return Readings([l1, l2], grads, change)

    def window(self, seconds: float):
        """(steps, seconds, failed): whole steps until ``seconds`` have
        passed, timed to the last step's completion."""
        losses, ends = [], []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                losses.append(self.one_step())
                ends.append(time.perf_counter())
                if ends[-1] - t0 >= seconds:
                    break
            self.prog.block()
        t1 = time.perf_counter()
        self.log(f"window losses {losses}; step ends at "
                 f"{[round(e - t0, 4) for e in ends]} s")
        failed = sum(not math.isfinite(x) for x in losses)
        return len(losses), t1 - t0, failed, t0

    def free(self) -> None:
        self.prog.free()
        self.prog = None
        gc.collect()


def _start_trace(trace_dir):
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, device_kind: str, peaks: Peaks, trace_dir,
             log: Callable[[str], None]) -> dict:
    devices = jax.devices()
    tr = Trainer(cell, seed, device_kind, log)
    prog_readings = tr.checked_steps()
    log(f"set-up done: losses {prog_readings.losses}")
    if trace:
        _start_trace(trace_dir)
    steps, window_s, failed, t0 = tr.window(seconds)
    setup_s = t0 - t_start
    peak = memory_peak_bytes(devices)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = read_trace(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {steps} steps in {window_s:.4f} s; peak "
        f"{peak / 2**30:.3f} GiB")
    run = Run(cell=cell.name, model=tr.m, seq=tr.feed.seq_len,
              chips=cell.chips, peaks=peaks,
              plan=program.plan_summary(tr.tuned.plan),
              tune_s=tr.tuned.tune_s,
              predicted_step_s=tr.tuned.predicted_step_s, steps=steps,
              window_s=window_s, tokens_per_step=tr.feed.tokens_per_step,
              memory_peak_bytes=peak, trace=reduced)
    batches = [tr.feed.batch(0), tr.feed.batch(1)]
    tr.free()
    t_ref = time.perf_counter()
    ref = Reference(tr.m, adam(cell.traffic),
                    mesh=reference_mesh(cell.chips)).run(seed, batches)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s, losses "
        f"{ref.losses}")
    nums = compare.numbers(prog_readings, ref)
    correct, checked = compare.judge(nums, cell.limits)
    correct = correct and failed == 0
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"train_tokens_per_s": run.tokens_per_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    return {"correct": correct, "attempted": steps, "failed": failed,
            "metrics": metrics, "run": run, "checked": checked}
