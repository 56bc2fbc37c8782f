"""Compile each cell's tuned train step, and its plain reference, for a
described TPU v5e without one attached.  Nothing runs: this says whether
the TPU compiler accepts the programs and how many bytes each device
needs, before a cell spends chip time.

    JAX_PLATFORMS=cpu python3 perfbench/rehearse.py [workload ...]

One process loads the TPU compiler at a time."""
from __future__ import annotations

import os
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TOPOLOGY = {1: "v5e:2x2", 4: "v5e:2x2"}
V5E_KIND = "TPU v5 lite"


def _described_mesh(topo, plan):
    import numpy as np
    from jax.sharding import Mesh
    st = plan.stages[0]
    devs = np.array(topo.devices[: st.dp * st.tp]).reshape(st.dp, st.tp)
    return Mesh(devs, ("data", "model"))


def rehearse(cell, topo, log=print) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np
    from benchlib import program, reference
    from benchlib.model import from_doc, layout
    from repro.training import optimizer as OPT

    m = from_doc(cell.config)
    mix = cell.traffic
    seq, rows = int(mix["seq_len"]), int(mix["global_batch"])
    tuned = program.tune(m, cell.config_name, seq, rows, cell.chips, V5E_KIND)
    log(f"{cell.name}: plan {program.plan_summary(tuned.plan)}; predicted "
        f"{tuned.predicted_step_s:.4f} s/step, "
        f"{tuned.predicted_bytes / 2**30:.3f} GiB")
    prog = program.Program(m, cell.config_name, tuned.plan, mix["optimizer"],
                           seq, rows, mesh=_described_mesh(topo, tuned.plan))
    step = prog.step
    abstract = jax.eval_shape(lambda: OPT.init_state(
        {n: jax.ShapeDtypeStruct(s.shape, s.dtype)
         for n, s in prog.low.params_sds.items()},
        prog.low.axes_table, tuned.plan.stages[0]))
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        abstract, step.state_shardings)
    batch = {k: jax.ShapeDtypeStruct((rows, seq), jnp.int32)
             for k in ("tokens", "labels")}
    t0 = time.perf_counter()
    with jax.set_mesh(prog.mesh):
        compiled = step.fn.lower(state, batch).compile()
    ma = compiled.memory_analysis()
    out = {"step_compile_s": time.perf_counter() - t0,
           "step_args_gib": ma.argument_size_in_bytes / 2**30,
           "step_temp_gib": ma.temp_size_in_bytes / 2**30,
           "kernel": "tpu_custom_call" in compiled.as_text()}
    log(f"  step: {out}")

    rmesh = None if cell.chips == 1 else Mesh(
        np.array(topo.devices[: cell.chips]), ("x",))
    sh = reference.weight_shardings(m, rmesh) or {
        n: jax.sharding.SingleDeviceSharding(topo.devices[0])
        for n in layout(m)}
    w = {n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sh[n])
         for n, (s, _) in layout(m).items()}
    one = (jax.sharding.SingleDeviceSharding(topo.devices[0])
           if rmesh is None else NamedSharding(rmesh, P()))
    toks = jax.ShapeDtypeStruct((seq,), jnp.int32, sharding=one)
    acc = jax.jit(lambda a, w, t, l: reference.accumulate(a, w, t, l, m),
                  donate_argnums=(0,))
    t0 = time.perf_counter()
    ma = acc.lower(w, w, toks, toks).compile().memory_analysis()
    out.update(ref_compile_s=time.perf_counter() - t0,
               ref_args_gib=ma.argument_size_in_bytes / 2**30,
               ref_temp_gib=ma.temp_size_in_bytes / 2**30,
               ref_out_gib=ma.output_size_in_bytes / 2**30)
    log(f"  reference: {out}")
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH_DIR))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies
    import repro.calibration as calibration
    import repro.kernels.ops as ops
    from benchlib import spec

    # the tuner takes the TPU's calibration profile, and the step builders
    # their TPU branches (kernel compiled, state offloaded), though the
    # process's backend is the CPU
    real_profile = calibration.load_profile
    calibration.load_profile = lambda *a, **k: real_profile(platform="tpu")
    ops._on_tpu = lambda: True
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)

    bench = spec.load_benchmark(ROOT)
    names = (argv if argv else [w["name"] for w in bench["workloads"]])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        rehearse(spec.cell(bench, name, ROOT), topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
