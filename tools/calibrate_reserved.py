#!/usr/bin/env python
"""Calibrate ``CostParams.runtime_reserved`` against real allocator stats.

The cost model charges every plan a constant ``runtime_reserved`` term for
what the analytic terms cannot see: the XLA runtime's own allocations,
allocator fragmentation, and compiler-inserted scratch that is not
attributable to any modeled tensor.  Everything else in the memory model
is spec-exact (PR 5) and shared between the predictor and
``memory_report()`` — so this constant is the ONLY term whose value is an
estimate rather than a derivation, and the only reason
``MEMORY_REL_TOL`` is not literally zero against *measured* memory.

This tool pins the constant to evidence instead of folklore:

1. It compiles the REAL program for one or more golden cells (reduced
   configs by default, so a CPU container can run it): the train step
   and the decode step, exactly as ``launch/dryrun.py`` lowers them.
2. It reads the compiled executable's memory analysis
   (``argument + temp + output - alias``, per device) and — where the
   backend exposes one (TPU/GPU) — the live allocator's
   ``device.memory_stats()`` peak.
3. ``implied_reserved = measured - (modeled_peak - runtime_reserved)``:
   what the constant WOULD have to be for the model to match the
   measurement exactly on that cell.  The suggestion is the max over
   cells, rounded up to 64 MiB.

On CPU hosts the measurement is the compile-time analysis only (XLA:CPU
additionally f32-legalizes bf16 compute, inflating temp bytes — see the
caveat in ``launch/dryrun.py``), so the printed suggestion is an upper
bound sanity check, not a refit; re-run on a real accelerator host to
refit the default.  Run with ``--json`` to archive the evidence next to
the benchmark artifacts.

With ``--write-profile PATH|auto`` the suggestion is folded into the
platform's ``CalibrationProfile`` as a sparse ``runtime_reserved`` cost
override (merged over any constants ``tools/calibrate.py`` already
fitted — the two tools share one profile file).  ``auto`` resolves to
the platform's default cache location, which ``TuneSpec.profile=
load_profile()`` / ``StageCostModel(profile=...)`` pick up on the next
run.  On CPU-sourced measurements the write is refused unless
``--force`` is given, for the f32-legalization reason above.

Usage:
    PYTHONPATH=src python tools/calibrate_reserved.py [--arch granite-3-8b]
        [--full] [--json PATH] [--write-profile PATH|auto]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Any, Dict, List


def measure_cell(arch: str, kind: str, *, reduced: bool,
                 batch: int, seq_len: int) -> Dict[str, Any]:
    """Compile one golden cell's real step and compare the executable's
    measured bytes against the modeled terms."""
    import jax
    import jax.numpy as jnp

    from repro import compat
    from repro.configs.base import ShapeConfig, get_arch
    from repro.core.costmodel import CostParams
    from repro.core.plan import single_stage_plan
    from repro.lowering import lower_plan
    from repro.models.zoo import build_model, input_specs
    from repro.training import optimizer as OPT
    from repro.training.step import make_serve_step, make_train_step

    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    n = len(jax.devices())
    cp = CostParams()

    shape = ShapeConfig("calib", seq_len, batch, kind)
    plan = single_stage_plan(
        cfg.num_layers, dp=n, tp=1, micro_batch=max(1, batch // n),
        grad_accum=max(1, batch // (n * max(1, batch // n)))
        if kind == "train" else 1,
        zero=0, ckpt_layers=0,
        **({} if kind == "train" else dict(remat_policy="none")))
    mesh = compat.make_mesh((n, 1), ("data", "model"))
    low = lower_plan(cfg, shape, plan, mesh)

    def attach(sds_tree, shardings):
        return jax.tree.map(
            lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
            sds_tree, shardings)

    with jax.set_mesh(mesh):
        if kind == "train":
            step = make_train_step(model, plan, mesh, lowered=low)
            state_abs = OPT.init_state(low.params_sds, low.axes_table,
                                       plan.stages[0])
            state_sds = attach(state_abs, step.state_shardings)
            batch_abs = input_specs(cfg, shape)
            batch_sds = attach(batch_abs, low.batch_shardings(batch_abs))
            compiled = step.fn.lower(state_sds, batch_sds).compile()
        else:  # decode
            step = make_serve_step(model, plan, mesh, batch, seq_len,
                                   lowered=low)
            p_sds = attach(low.params_sds, low.param_shardings())
            spec = input_specs(cfg, shape)
            cache_sds = attach(spec["caches"], step.batch_shardings)
            compiled = step.fn.lower(p_sds, spec["tokens"],
                                     cache_sds).compile()

    ma = compiled.memory_analysis()
    measured = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)

    # real allocator stats, where the backend keeps them (TPU/GPU)
    dev = jax.devices()[0]
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    allocator_peak = (stats or {}).get("peak_bytes_in_use")

    rep = low.memory_report()
    modeled_peak = rep.peak_bytes
    modeled_sans_reserved = modeled_peak - cp.runtime_reserved
    best = allocator_peak if allocator_peak is not None else measured
    implied = best - modeled_sans_reserved
    return {
        "arch": cfg.name, "kind": kind,
        "plan": f"dp{n}_tp1_z0",
        "measured_exec_bytes": int(measured),
        "allocator_peak_bytes": allocator_peak,
        "measurement_source": ("allocator" if allocator_peak is not None
                               else "memory_analysis"),
        "modeled_peak_bytes": float(modeled_peak),
        "modeled_sans_reserved_bytes": float(modeled_sans_reserved),
        "current_reserved_bytes": float(cp.runtime_reserved),
        "implied_reserved_bytes": float(implied),
    }


def suggest(cells: List[Dict[str, Any]]) -> float:
    """Max implied reserve over cells, rounded UP to 64 MiB (never
    suggest below zero: a negative implication means the analytic terms
    over-cover on that backend, which is safe)."""
    step = 64 * 2**20
    worst = max((c["implied_reserved_bytes"] for c in cells), default=0.0)
    return max(0.0, math.ceil(worst / step) * step)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) config — needs a real host")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--json", metavar="PATH")
    ap.add_argument("--write-profile", metavar="PATH|auto", default=None,
                    help="fold the suggested runtime_reserved into the "
                         "platform CalibrationProfile (auto = default "
                         "cache location)")
    ap.add_argument("--force", action="store_true",
                    help="write the profile even from a CPU-only "
                         "measurement (upper bound, not a refit)")
    args = ap.parse_args(argv)

    cells = []
    for kind in ("train", "decode"):
        c = measure_cell(args.arch, kind, reduced=not args.full,
                         batch=args.batch, seq_len=args.seq_len)
        cells.append(c)
        print(f"{c['arch']:24s} {kind:7s} source={c['measurement_source']:15s}"
              f" measured={c['measured_exec_bytes'] / 2**20:9.1f} MiB"
              f" modeled-sans-reserved="
              f"{c['modeled_sans_reserved_bytes'] / 2**20:9.1f} MiB"
              f" implied-reserved="
              f"{c['implied_reserved_bytes'] / 2**20:9.1f} MiB")

    cur = cells[0]["current_reserved_bytes"]
    sug = suggest(cells)
    on_accel = any(c["measurement_source"] == "allocator" for c in cells)
    print(f"current CostParams.runtime_reserved: {cur / 2**20:.0f} MiB")
    print(f"suggested (max over cells, 64 MiB-aligned): "
          f"{sug / 2**20:.0f} MiB"
          + ("" if on_accel else
             "  [CPU memory_analysis only — upper-bound sanity check; "
             "refit on an accelerator host]"))

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"cells": cells,
                       "current_reserved_bytes": cur,
                       "suggested_reserved_bytes": sug,
                       "accelerator_measurement": on_accel}, f, indent=2)
        print(f"wrote {args.json}")

    if args.write_profile:
        if not on_accel and not args.force:
            print("refusing --write-profile from a CPU-only measurement "
                  "(memory_analysis over-counts under f32 legalization); "
                  "re-run on an accelerator host or pass --force",
                  file=sys.stderr)
            return 1
        from repro.calibration.profile import (default_platform,
                                               load_profile, profile_path)
        platform = default_platform()
        path = (profile_path(platform) if args.write_profile == "auto"
                else args.write_profile)
        # merge over whatever tools/calibrate.py already fitted for this
        # platform; an absent file starts from the frozen defaults
        base = load_profile(platform=platform,
                            path=path if os.path.exists(str(path)) else None)
        prof = base.with_cost(runtime_reserved=sug)
        if prof.platform == "default":
            import dataclasses
            prof = dataclasses.replace(prof, platform=platform,
                                       source="calibrate_reserved")
        prof.save(path)
        print(f"wrote runtime_reserved={sug / 2**20:.0f} MiB into {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
