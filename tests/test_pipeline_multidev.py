"""Multi-device integration tests (subprocess: these need
--xla_force_host_platform_device_count, which must NOT leak into the other
tests' single-device jax runtime)."""
import json
import pathlib
import subprocess
import sys

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _run(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": SRC, "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env and k != "XLA_FLAGS"})
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


PIPELINE_NUMERIC = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_arch
from repro.core.plan import Plan, StageConfig
from repro.models.zoo import build_model
from repro.parallel.pipeline import make_pipeline_train_step
import repro.training.optimizer as OPT
from repro.models.common import ExecConfig

cfg = get_arch('granite-3-8b').reduced().replace(num_layers=4)
model = build_model(cfg)
from repro import compat
mesh = compat.make_mesh((2, 2, 2), ('stage', 'data', 'model'))
G, b = 2, 2
stages = tuple(StageConfig(layers=2, micro_batch=b, dp=2, tp=2, zero=1,
                           ckpt_layers=2 if i == 0 else 0)
               for i in range(2))
plan = Plan(grad_accum=G, stages=stages)
with jax.set_mesh(mesh):
    params, axes = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (G, 4, 64), 0, cfg.vocab_size)
    labels = jax.random.randint(key, (G, 4, 64), 0, cfg.vocab_size)
    ec = ExecConfig(ckpt_layers=0, remat_policy='none')
    ref = np.mean([float(model.loss_fn(params,
        {'tokens': tokens[i], 'labels': labels[i]}, ec)) for i in range(G)])
    step = make_pipeline_train_step(model, plan, mesh, donate=False)
    state = OPT.init_state(params, axes, plan.stages[0])
    state = jax.device_put(state, step.state_shardings)
    state2, m = step.fn(state, {'tokens': tokens, 'labels': labels})
    diff = abs(float(m['loss']) - ref)
    assert diff < 5e-3, (float(m['loss']), ref)
    assert float(m['grad_norm']) > 0
    # one more step changes the loss (optimizer applied across stages)
    state3, m2 = step.fn(state2, {'tokens': tokens, 'labels': labels})
    assert float(m2['loss']) < float(m['loss'])
    print('PIPELINE_OK', diff)
"""


def test_pipeline_matches_reference():
    out = _run(PIPELINE_NUMERIC, devices=8)
    assert "PIPELINE_OK" in out


SINGLE_STAGE_SPMD = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import get_arch
from repro.core.plan import single_stage_plan
from repro.models.zoo import build_model
from repro.training.step import make_train_step, init_sharded_state
from repro.parallel import sharding as SH

cfg = get_arch('qwen2-moe-a2.7b').reduced()
model = build_model(cfg)
mesh = compat.make_mesh((2, 2), ('data', 'model'))
plan = single_stage_plan(cfg.num_layers, dp=2, tp=2, micro_batch=2,
                         grad_accum=2, zero=2,
                         ckpt_layers=cfg.num_layers // 2)
with jax.set_mesh(mesh):
    step = make_train_step(model, plan, mesh, donate=False)
    state, sh = init_sharded_state(model, plan, mesh, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    batch = {'tokens': jax.random.randint(key, (8, 64), 0, cfg.vocab_size),
             'labels': jax.random.randint(key, (8, 64), 0, cfg.vocab_size)}
    losses = []
    for _ in range(3):
        state, m = step.fn(state, batch)
        losses.append(float(m['loss']))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
    print('SPMD_OK', losses)
"""


def test_single_stage_spmd_zero2():
    out = _run(SINGLE_STAGE_SPMD, devices=4)
    assert "SPMD_OK" in out


OFFLOAD_STATE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import get_arch
from repro.core.plan import single_stage_plan
from repro.models.zoo import build_model
from repro.training.step import make_train_step, init_sharded_state

cfg = get_arch('granite-3-8b').reduced()
model = build_model(cfg)
mesh = compat.make_mesh((2, 1), ('data', 'model'))
# oo=0.5 -> half the stacked optimizer state host-offloaded (pinned_host)
plan = single_stage_plan(cfg.num_layers, dp=2, tp=1, micro_batch=2,
                         grad_accum=1, zero=1, oo=0.5, wo=0.5,
                         ckpt_layers=cfg.num_layers)
with jax.set_mesh(mesh):
    step = make_train_step(model, plan, mesh, donate=False)
    state, sh = init_sharded_state(model, plan, mesh, jax.random.PRNGKey(0))
    kinds = {l.sharding.memory_kind for l in jax.tree.leaves(state['mu'])}
    assert 'pinned_host' in kinds, kinds

    key = jax.random.PRNGKey(1)
    batch = {'tokens': jax.random.randint(key, (4, 64), 0, cfg.vocab_size),
             'labels': jax.random.randint(key, (4, 64), 0, cfg.vocab_size)}
    l0 = None
    for _ in range(3):
        state, m = step.fn(state, batch)
        if l0 is None: l0 = float(m['loss'])
    assert float(m['loss']) < l0
    print('OFFLOAD_OK')
"""


def test_host_offloaded_optimizer_state():
    out = _run(OFFLOAD_STATE, devices=2)
    assert "OFFLOAD_OK" in out


ELASTIC = r"""
import jax, jax.numpy as jnp, numpy as np, tempfile
from repro import compat
from repro.configs.base import get_arch
from repro.core.plan import single_stage_plan
from repro.models.zoo import build_model
from repro.training.step import make_train_step, init_sharded_state
from repro.training.checkpoint import Checkpointer

cfg = get_arch('granite-3-8b').reduced()
model = build_model(cfg)
tmp = tempfile.mkdtemp()
# train on (2,1) mesh, checkpoint, restore onto (4,1) mesh
mesh_a = compat.make_mesh((2, 1), ('data', 'model'))
plan_a = single_stage_plan(cfg.num_layers, dp=2, tp=1, micro_batch=2,
                           grad_accum=1, zero=1)
key = jax.random.PRNGKey(1)
batch = {'tokens': jax.random.randint(key, (4, 64), 0, cfg.vocab_size),
         'labels': jax.random.randint(key, (4, 64), 0, cfg.vocab_size)}
with jax.set_mesh(mesh_a):
    step_a = make_train_step(model, plan_a, mesh_a, donate=False)
    state, _ = init_sharded_state(model, plan_a, mesh_a, jax.random.PRNGKey(0))
    state, m_a = step_a.fn(state, batch)
    ck = Checkpointer(tmp)
    ck.save(1, state)

mesh_b = compat.make_mesh((4, 1), ('data', 'model'))
plan_b = single_stage_plan(cfg.num_layers, dp=4, tp=1, micro_batch=1,
                           grad_accum=1, zero=2)
with jax.set_mesh(mesh_b):
    step_b = make_train_step(model, plan_b, mesh_b, donate=False)
    abs_state, sh_b = init_sharded_state(model, plan_b, mesh_b,
                                         jax.random.PRNGKey(0))
    stp, restored, _ = Checkpointer(tmp).restore(shardings=sh_b)
    state_b, m_b = step_b.fn(restored, batch)
    assert np.isfinite(float(m_b['loss']))
    # restored params equal saved ones
    w = 'layers/mlp/w_up' if 'layers/mlp/w_up' in restored['params'] else \
        sorted(restored['params'])[0]
    print('ELASTIC_OK', float(m_b['loss']))
"""


def test_elastic_restore_different_mesh():
    out = _run(ELASTIC, devices=4)
    assert "ELASTIC_OK" in out
