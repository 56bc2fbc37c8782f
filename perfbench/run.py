"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix, limits and per-layer metrics
are found by name from ``BENCHMARK.json`` (see ``benchlib/spec.py``).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown`` of device time, and last ``checked``: each
number the correctness comparison compared, beside its limit.  The same
numbers are the last lines of standard error.

The run refuses (exit code 1, no result) when JAX finds no TPU, fewer
chips than the cell asks for, or a chip that is not in the peaks table.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRACE_DIR = ROOT / ".perfbench_trace"
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def refuse(msg: str) -> int:
    print(f"# refused: {msg}", file=sys.stderr, flush=True)
    return 1


def device_line(devices, peak_bytes=None) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices)}
    if peak_bytes is not None:
        out["memory_peak_bytes"] = peak_bytes
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    from benchlib import spec
    from benchlib.peaks import UnknownDevice, peaks_for
    cell = spec.cell(spec.load_benchmark(ROOT), args.workload, ROOT)

    # every program in the persistent cache, so that only the first run of
    # a cell in a checkout compiles; the program keeps its cache where
    # JAX_COMPILATION_CACHE_DIR says and the benchmark gives it that
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return refuse(f"no TPU attached ({devices[0].platform})")
    if len(devices) < cell.chips:
        return refuse(f"{cell.name} needs {cell.chips} chips, found "
                      f"{len(devices)}")
    try:
        peaks = peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        return refuse(str(e))
    return execute(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices,
                   device_kind=devices[0].device_kind, peaks=peaks)


def execute(cell, *, seed: int, seconds: float, trace: bool, devices,
            device_kind: str, peaks) -> int:
    """Everything of a run after the look for the chips: the cell's
    set-up, window and check, and the result line."""
    from benchlib import train
    log(f"{cell.name}: seed {seed}, {seconds} s, trace {int(trace)}, "
        f"device {device_line(devices)}")
    out = train.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                         t_start=T_START, device_kind=device_kind,
                         peaks=peaks, trace_dir=TRACE_DIR, log=log)
    run = out["run"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": out["metrics"],
              "device": device_line(devices, run.memory_peak_bytes)}
    if run.trace is not None:
        from benchlib import trace as T
        result["device"]["busy_s"] = (sum(T.busy_s(run.trace).values())
                                      / len(run.trace.devices))
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": T.top_ops(run.trace),
                               "idle_gaps": T.idle_gaps(run.trace)}
    result["checked"] = out["checked"]
    for name, (value, limit) in out["checked"].items():
        print(f"{name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
