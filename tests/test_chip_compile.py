"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

Nothing runs: each case compiles for a described (not attached) v5e chip,
so the chip's compiler refuses here what interpret mode accepts, such as a
block shape off the (8, 128) tiling or more VMEM than a kernel may use.
Each case asserts that the kernel was really lowered to the chip
(``tpu_custom_call`` in the compiled text) and that the program fits one
chip's 16 GiB.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU's
library, and the test workers each import every test file.
"""
import functools
import importlib.util
import os
import pathlib
import re
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan_pallas

HBM_BYTES = 16 * 2**30
SEQ = 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # loading libtpu installs a failure-signal handler that prints a stack
    # trace into the shared test output when the worker is terminated;
    # keep SIGTERM's default, silent behaviour
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer ``ops`` onto its chip branch (real kernels, not interpret
    mode): the process's own backend is the CPU."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compile(fn, shardings, *shapes):
    sds = [jax.ShapeDtypeStruct(s, d, sharding=shardings)
           for s, d in shapes]
    compiled = jax.jit(fn).lower(*sds).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel not lowered for the chip"
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, total
    return compiled


def _granite_qkv():
    cfg = get_arch("granite-3-8b")
    hd = cfg.d_model // cfg.num_heads
    return ((1, SEQ, cfg.num_heads, hd), jnp.bfloat16), \
        ((1, SEQ, cfg.num_kv_heads, hd), jnp.bfloat16)


def test_flash_forward_granite_widths(one_chip):
    cfg = get_arch("granite-3-8b")
    hd = cfg.d_model // cfg.num_heads
    fn = functools.partial(flash_attention_fwd, causal=True, q_block=512,
                           kv_block=512, interpret=False)
    shape = ((cfg.num_heads, SEQ, hd), jnp.bfloat16)
    _compile(fn, one_chip, shape, shape, shape)


def test_attention_pallas_forward_gqa(one_chip, on_tpu):
    q, kv = _granite_qkv()
    fn = functools.partial(ops.attention, causal=True, impl="pallas",
                           q_block=512, kv_block=512)
    _compile(fn, one_chip, q, kv, kv)


def test_attention_pallas_backward_gqa(one_chip, on_tpu):
    q, kv = _granite_qkv()

    def loss(q_, k_, v_):
        out = ops.attention(q_, k_, v_, causal=True, impl="pallas",
                            q_block=512, kv_block=512)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, q, kv, kv)


@pytest.mark.parametrize("rows", [4096, 3])
def test_rmsnorm(one_chip, rows):
    d = get_arch("granite-3-8b").d_model
    fn = functools.partial(rmsnorm, row_block=256, interpret=False)
    _compile(fn, one_chip, ((rows, d), jnp.bfloat16), ((d,), jnp.bfloat16))


def test_ssd_scan_zamba2_widths(one_chip):
    cfg = get_arch("zamba2-2.7b")
    heads = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    p, n = cfg.ssm_head_dim, cfg.ssm_state
    fn = functools.partial(ssd_scan_pallas, chunk=256, interpret=False)
    _compile(fn, one_chip,
             ((1, SEQ, heads, p), jnp.bfloat16),
             ((1, SEQ, heads), jnp.float32),
             ((heads,), jnp.float32),
             ((1, SEQ, heads, n), jnp.bfloat16),
             ((1, SEQ, heads, n), jnp.bfloat16))


def test_offloaded_train_step(one_chip, on_tpu, monkeypatch):
    """The chip's train step for a plan with host-offloaded optimizer
    state: outputs pinned by ``out_shardings`` (host slices stay in
    ``pinned_host``) and the state donated."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.plan import single_stage_plan
    from repro.lowering import lower_plan
    from repro.models.zoo import build_model
    from repro.training import optimizer as OPT
    from repro.training.step import make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_arch("granite-3-8b").reduced()
    plan = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=2,
                             grad_accum=2, zero=0, oo=0.5, ckpt_layers=1)
    plan = plan.replace(attn_impl="pallas", use_pallas=True)
    mesh = Mesh(np.array([one_chip._device]).reshape(1, 1),
                ("data", "model"))
    model = build_model(cfg)
    low = lower_plan(cfg, None, plan, mesh)
    step = make_train_step(model, plan, mesh, lowered=low)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        OPT.init_state(low.params_sds, low.axes_table, plan.stages[0]),
        step.state_shardings)
    batch = {k: jax.ShapeDtypeStruct((4, 256), jnp.int32,
                                     sharding=one_chip)
             for k in ("tokens", "labels")}
    with jax.set_mesh(mesh):
        compiled = step.fn.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    kinds = [s.memory_kind
             for s in jax.tree.leaves(compiled.output_shardings[0])]
    assert kinds.count("pinned_host") == sum(
        OPT.is_split(leaf) for e in ("master", "mu", "nu")
        for leaf in state[e].values()) > 0
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0          # the state is donated


FLASH_BWD_KERNELS = ("flash_lse", "flash_bwd_dkv", "flash_bwd_dq")
ROOFLINE_READER = (pathlib.Path(__file__).resolve().parents[1] / "perfbench"
                   / "metrics" / "flash_fwd_roofline.py")


def _chip_calls(compiled):
    """The program's text with operand shapes, as the profiler's trace
    names each operation, and its Mosaic custom calls."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_backend_config = False
    text = "\n".join(m.to_string(opts) for m in
                     compiled.runtime_executable().hlo_modules())
    return text, [line for line in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in line]


def _kernel_name(call):
    """The ``name=`` of a custom call's pallas_call, None if it has none."""
    scope = re.search(r'op_name="([^"]*)"', call).group(1).split("/")
    return scope[-2] if scope[-2] in FLASH_BWD_KERNELS else None


def test_train_step_flash_backward_kernels(one_chip, on_tpu, monkeypatch):
    """A reduced granite train step (head_dim 128, full remat) takes the
    Pallas lse, dK/dV and dQ kernels; the benchmark's roofline reader
    finds the forward kernel's calls and no other; and no f32
    (batch*heads, seq, kv_block) score block is left outside the
    kernels, where the blocked-jnp backward, compiled alongside, has
    them."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.plan import single_stage_plan
    from repro.lowering import lower_plan
    from repro.models.zoo import build_model
    from repro.training import optimizer as OPT
    from repro.training.step import make_train_step

    monkeypatch.syspath_prepend(str(ROOFLINE_READER.parents[1]))
    spec = importlib.util.spec_from_file_location("flash_fwd_roofline",
                                                  ROOFLINE_READER)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_arch("granite-3-8b").reduced().replace(head_dim=128)
    plan = single_stage_plan(cfg.num_layers, dp=1, tp=1, micro_batch=2,
                             grad_accum=2, zero=0, oo=0.0,
                             ckpt_layers=cfg.num_layers)
    plan = plan.replace(attn_impl="pallas", use_pallas=True)
    seq, kv_block = 1024, plan.kernel.attn_kv_block
    mesh = Mesh(np.array([one_chip._device]).reshape(1, 1),
                ("data", "model"))
    low = lower_plan(cfg, None, plan, mesh)
    step = make_train_step(build_model(cfg), plan, mesh, lowered=low)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        OPT.init_state(low.params_sds, low.axes_table, plan.stages[0]),
        step.state_shardings)
    batch = {k: jax.ShapeDtypeStruct((4, seq), jnp.int32,
                                     sharding=one_chip)
             for k in ("tokens", "labels")}
    scores = "f32[%d,%d,%d]" % (2 * cfg.num_heads, seq, kv_block)

    def compile_step():
        jax.clear_caches()          # trace again: count the path taken
        with jax.set_mesh(mesh):
            return _chip_calls(step.fn.lower(state, batch).compile())

    before = ops.backward_paths.copy()
    text, calls = compile_step()
    assert ops.backward_paths - before == {"pallas": 1}
    forward = [c for c in calls if _kernel_name(c) is None]
    read = [c for c in calls if reader._shape(c) is not None]
    assert read == forward and forward       # forward pass and remat
    for name in FLASH_BWD_KERNELS:
        assert [_kernel_name(c) for c in calls].count(name) == 1, name
    assert scores not in text

    # the same step on the blocked-jnp backward: the check above can fail
    monkeypatch.setattr(ops, "_pallas_bwd", lambda *a: False)
    jnp_text, jnp_calls = compile_step()
    assert ops.backward_paths - before == {"pallas": 1, "jnp": 1}
    assert [c for c in jnp_calls if reader._shape(c) is not None] \
        == jnp_calls and len(jnp_calls) == len(forward)
    assert scores in jnp_text
