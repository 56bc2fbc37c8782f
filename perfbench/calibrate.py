"""Readings that the limits of a cell's correctness check are set from.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 7,8,9] [--variants fp8,half,cut] [--out <file>]

In one process on the cell's chips: the program's train step is built
once; for each of ``--seeds`` its state is drawn, the two checked steps
run through the window's call and feed (``benchlib.train``), the state is
freed and the plain reference follows the same two steps.  For each of
``--control-seeds`` the reference is then put in the program's place in
the precision below the configuration's (``quant="fp8"``), with half of
each batch left out (``half``), and on a cell of several chips with the
exchange between them left out (``cut``).  Each reading is one JSON line:
the numbers ``correct`` compares, and every leaf's gaps.  No benchmark run
calls this."""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _record(out, kind, seed, prog, ref, t0):
    from benchlib import compare
    line = {"kind": kind, "seed": seed,
            "numbers": compare.numbers(prog, ref),
            "losses": prog.losses, "ref_losses": ref.losses,
            "leaf_gaps": compare.leaf_gaps(prog, ref),
            "seconds": time.perf_counter() - t0}
    print(json.dumps(line), file=out, flush=True)
    print(f"# {kind} seed {seed}: {line['numbers']}", file=sys.stderr,
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--variants", default="fp8,half,cut")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]

    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchlib import spec, train
    from benchlib.reference import Reference
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    bench = spec.load_benchmark(ROOT)
    cell = spec.cell(bench, args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"# refused: {cell.name} needs {cell.chips} TPU chips",
              file=sys.stderr)
        return 1
    out = (open(args.out, "a") if args.out else sys.stdout)

    def log(msg):
        print(f"# {msg}", file=sys.stderr, flush=True)

    from benchlib import program
    from benchlib.model import from_doc
    from benchlib.traffic import ZipfTokens
    m = from_doc(cell.config)
    mesh = train.reference_mesh(cell.chips)
    adam = train.adam(cell.traffic)
    ref = Reference(m, adam, mesh=mesh)
    tr, ref_readings = None, {}
    try:
        for seed in seeds:
            t0 = time.perf_counter()
            if tr is None:
                tr = train.Trainer(cell, seed, devices[0].device_kind, log)
            else:
                tr.reset(seed)
            prog = tr.checked_steps()
            batches = [tr.feed.batch(0), tr.feed.batch(1)]
            tr.prog.free()
            ref_readings[seed] = ref.run(seed, batches)
            _record(out, "program", seed, prog, ref_readings[seed], t0)
        variants = {"fp8": {"quant": "fp8"}, "half": {"half": True}}
        if cell.chips > 1:
            mix = cell.traffic
            plan = program.tune(m, cell.config_name, mix["seq_len"],
                                mix["global_batch"], cell.chips,
                                devices[0].device_kind).plan
            variants["cut"] = {"cut": plan.stages[0].tp}
        for kind in args.variants.split(","):
            if kind not in variants:
                continue
            other = Reference(m, adam, mesh=mesh, **variants[kind])
            for seed in controls:
                t0 = time.perf_counter()
                feed = ZipfTokens(cell.traffic, m.vocab, seed)
                batches = [feed.batch(0), feed.batch(1)]
                if seed not in ref_readings:
                    ref_readings[seed] = ref.run(seed, batches)
                _record(out, kind, seed, other.run(seed, batches),
                        ref_readings[seed], t0)
            del other
    finally:
        if out is not sys.stdout:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
