#!/usr/bin/env python
"""Fit the memory model's ``act_overhead`` to the TPU compiler's accounting.

The analytic memory terms match the unpadded bytes a train step holds, but
the TPU compiler's heap holds the step's temporaries (activations,
transients, logits) at a multiple of those bytes, and it refuses a program
that does not fit HBM.  This tool compiles the train step of each given
plan for a described (not attached) TPU, so it needs no chip, and prints,
per plan, the model's terms beside the compiler's HBM use:

* a refused program reports "Used X of C hbm"; the implied factor is
  ``(X - reserved - state) / (act + transient + logits)``, with the
  model's state bytes and the compiler's reserved + scoped bytes;
* an accepted program reports ``memory_analysis()`` (which may count
  buffers that are never live together, so it bounds nothing).

The suggested ``act_overhead`` is the largest implied factor, less one,
rounded up to 0.05; ``src/repro/calibration/profiles/tpu.json`` holds the
value in use.

    PYTHONPATH=src python tools/calibrate_compiler_memory.py \\
        --layers 4 --plan plan.json [--plan ...]

Each ``--plan`` is a path to a ``Plan.to_json()`` document or the JSON
itself.  Only one process at a time may load the TPU's library.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import re
import sys

_UNIT = {"": 1, "K": 2**10, "M": 2**20, "G": 2**30}


def _bytes(pattern: str, text: str):
    m = re.search(pattern + r"\s*\(?([0-9.]+)([KMG]?)", text)
    return None if m is None else float(m.group(1)) * _UNIT[m.group(2)]


def measure(cfg, shape, plan, devices) -> dict:
    """Model terms and the compiler's HBM use for one plan."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.lowering import lower_plan, memory_consistency
    from repro.models.zoo import build_model
    from repro.parallel.pipeline import make_pipeline_train_step
    from repro.training import optimizer as OPT
    from repro.training.step import make_train_step

    terms = {k: v["lowered"] for k, v in memory_consistency(
        cfg, shape, plan)["terms"].items()}
    st = plan.stages[0]
    S = plan.num_stages
    dims = ((S,) if S > 1 else ()) + (st.dp, st.tp)
    axes = (("stage",) if S > 1 else ()) + ("data", "model")
    mesh = Mesh(np.array(devices[:math.prod(dims)]).reshape(dims), axes)
    model = build_model(cfg)
    low = lower_plan(cfg, shape, plan, mesh)
    make = make_pipeline_train_step if S > 1 else make_train_step
    step = make(model, plan, mesh, lowered=low)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        OPT.init_state(low.params_sds, low.axes_table, st),
        step.state_shardings)
    gb, seq = shape.global_batch, shape.seq_len
    rows = (plan.grad_accum, gb // plan.grad_accum) if S > 1 else (gb,)
    batch = {k: jax.ShapeDtypeStruct(rows + (seq,), jnp.int32,
                                     sharding=NamedSharding(
                                         mesh, PartitionSpec()))
             for k in ("tokens", "labels")}
    out = {"plan": json.loads(plan.to_json()), "model_bytes": terms}
    temps = terms["act"] + terms["transient"] + terms["logits"]
    try:
        with jax.set_mesh(mesh):
            ma = step.fn.lower(state, batch).compile().memory_analysis()
        out["compiler"] = {"accepted": True, "memory_analysis": (
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)}
    except jax.errors.JaxRuntimeError as e:
        text = str(e)
        used = _bytes(r"Used", text)
        if used is None:
            raise
        reserved = (_bytes(r"reserved", text) or 0.0) + (
            _bytes(r"scoped", text) or 0.0)
        out["compiler"] = {"accepted": False, "used": used,
                           "capacity": _bytes(r"Used [0-9.]+[KMG]? of",
                                              text),
                           "reserved": reserved}
        out["implied_factor"] = (used - reserved - terms["state"]) / temps
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--plan", action="append", required=True)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from repro.configs.base import ShapeConfig, get_arch
    from repro.core.plan import Plan
    from repro.kernels import ops

    # this process's backend is the CPU: steer the step builders onto
    # their chip branches (real kernels, outputs pinned to host memory)
    ops._on_tpu = lambda: True
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology).devices
    cfg = get_arch(args.arch).replace(num_layers=args.layers)
    shape = ShapeConfig("compiler-memory", args.seq, args.global_batch,
                        "train")
    factors = []
    for text in args.plan:
        if not text.lstrip().startswith("{"):
            text = pathlib.Path(text).read_text()
        plan = Plan.from_json(text)
        row = measure(cfg, shape, plan, devices)
        print(json.dumps(row), flush=True)
        if "implied_factor" in row:
            factors.append(row["implied_factor"])
    if factors:
        print(f"suggested act_overhead: "
              f"{math.ceil((max(factors) - 1) * 20) / 20:.2f} "
              f"(largest implied factor {max(factors):.4f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
