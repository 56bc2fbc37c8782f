"""The system under test, as the benchmark drives it: tune a cell with the
platform's calibration profile, lower the plan, build its train step,
place the state with the benchmark's weights, and read the state back.

Every use of the program's modules is in this file."""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchlib.model import Dense, base_key, draw, draw_leaf_rounded, layout

# What the program's dense decoder computes and cannot be told otherwise
# (models/layers.py): a configuration that states other values cannot run
FIXED = {"eps": 1e-6, "emb_mult": 1.0, "resid_mult": 1.0, "logits_div": 1.0}


def arch_config(m: Dense, name: str):
    from repro.configs.base import ArchConfig
    for key, want in FIXED.items():
        if getattr(m, key) != want:
            raise ValueError(f"{name}: the program runs {key}={want}, the "
                             f"configuration states {getattr(m, key)}")
    if not math.isclose(m.attn_scale, 1.0 / math.sqrt(m.head_dim)):
        raise ValueError(f"{name}: the program scales q.k by "
                         f"1/sqrt(head_dim), the configuration by "
                         f"{m.attn_scale}")
    return ArchConfig(
        name=name, family="dense", num_layers=m.layers, d_model=m.d_model,
        num_heads=m.heads, num_kv_heads=m.kv_heads, d_ff=m.d_ff,
        vocab_size=m.vocab, head_dim=m.head_dim, qkv_bias=m.qkv_bias,
        rope_theta=m.rope_theta, norm_type="rmsnorm", act="silu",
        mlp_gated=True, tie_embeddings=m.tied)


def adam_config(opt: dict):
    from repro.training.optimizer import AdamConfig
    return AdamConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                      eps=opt["eps"], weight_decay=opt["weight_decay"],
                      grad_clip=opt["grad_clip"])


@dataclass
class Tuned:
    plan: Any
    predicted_step_s: float
    predicted_bytes: float
    tune_s: float


def tune(m: Dense, name: str, seq: int, global_batch: int, chips: int,
         device_kind: str) -> Tuned:
    """``repro.core.tuner.tune`` in the ``mist`` space with the platform's
    calibration profile, as ``repro.launch.train --tune`` runs it.  The
    plan's attention is set to the tiled Pallas kernel, the path the cost
    model prices (the tuner's default plan names the naive one)."""
    from repro.calibration import load_profile
    from repro.configs.base import ShapeConfig
    from repro.core.hardware import spec_for_device_kind
    from repro.core.tuner import tune as program_tune
    from repro.lowering import memory_consistency
    cfg = arch_config(m, name)
    shape = ShapeConfig(name, seq, global_batch, "train")
    hw = spec_for_device_kind(device_kind)
    profile = load_profile()
    t0 = time.perf_counter()
    rep = program_tune(cfg, shape, chips, space="mist", workers=1, hw=hw,
                       profile=profile)
    tune_s = time.perf_counter() - t0
    if rep.plan is None:
        raise RuntimeError(f"{name}: the tuner found no plan")
    plan = rep.plan.replace(attn_impl="pallas", use_pallas=True)
    if plan.num_stages != 1:
        raise RuntimeError(f"{name}: the tuner chose a {plan.num_stages}-"
                           f"stage plan; the benchmark drives one stage")
    predicted = memory_consistency(cfg, shape, plan, hw=hw,
                                   profile=profile)["predicted_bytes"]
    return Tuned(plan, float(rep.objective), float(predicted), tune_s)


def plan_summary(plan) -> dict:
    st = plan.stages[0]
    return {"G": plan.grad_accum, "mb": st.micro_batch, "dp": st.dp,
            "tp": st.tp, "zero": st.zero, "ckpt": st.ckpt_layers,
            "wo": st.wo, "oo": st.oo, "ao": st.ao,
            "attn": plan.attn_impl}


class Program:
    """The compiled train step of a tuned plan and its state."""

    def __init__(self, m: Dense, name: str, plan, opt: dict,
                 seq: int, global_batch: int, mesh=None):
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_plan_mesh
        from repro.lowering import lower_plan
        from repro.models.zoo import build_model
        from repro.training.step import make_train_step
        self.m, self.plan = m, plan
        self.cfg = arch_config(m, name)
        st = plan.stages[0]
        self.mesh = mesh if mesh is not None else make_plan_mesh(st.dp, st.tp)
        self.low = lower_plan(self.cfg, ShapeConfig(name, seq, global_batch,
                                                    "train"), plan, self.mesh)
        want = {n: s for n, (s, _) in layout(m).items()}
        got = {n: tuple(s.shape) for n, s in self.low.params_sds.items()}
        if want != got:
            raise RuntimeError(f"the program's weights {got} are not the "
                               f"benchmark's layout {want}")
        self.step = make_train_step(build_model(self.cfg), plan, self.mesh,
                                    adam=adam_config(opt), lowered=self.low)
        self.state = None
        self._sumsq = jax.jit(lambda x: jnp.sum(jnp.square(
            x.astype(jnp.float32))))
        self._diff = {n: jax.jit(self._diff_fn(n), static_argnums=(2, 3, 4))
                      for n in layout(m)}

    def _diff_fn(self, name):
        m = self.m

        def diff(part, key, lo, hi, sharding):
            """Square norm of ``part`` minus rows [lo, hi) of the drawn
            weight, the draw laid out as the part is."""
            w0 = draw_leaf_rounded(m, key, name)
            if hi is not None:
                w0 = w0[lo:hi]
            w0 = jax.lax.with_sharding_constraint(w0, sharding)
            return jnp.sum(jnp.square(part - w0))
        return diff

    def init_state(self, seed: int) -> None:
        """The optimizer state of the plan, from the benchmark's weights:
        the program's ``init_sharded_state`` with the weights drawn by
        ``benchlib.model.draw`` in place of the model's own init (the
        params in one program, the f32 master cast from them in a
        second, host-offloaded slices moved out last)."""
        from jax.sharding import NamedSharding
        from repro.training import optimizer as OPT
        self.state = None
        shardings = self.low.state_shardings()
        dev = jax.tree.map(
            lambda s: NamedSharding(s.mesh, s.spec), shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        m, axes, stage = self.m, self.low.axes_table, self.plan.stages[0]
        self.key = base_key(seed)
        with jax.set_mesh(self.mesh):
            params = jax.jit(lambda k: draw(m, k),
                             out_shardings=dev["params"])(self.key)
            state = jax.jit(lambda p: OPT.init_state(p, axes, stage),
                            out_shardings=dev, donate_argnums=0)(params)
            self.state = jax.device_put(state, shardings)

    def run_step(self, batch) -> jax.Array:
        """One step through the compiled program; returns its loss, which
        is ready when the step is."""
        with jax.set_mesh(self.mesh):
            self.state, metrics = self.step.fn(self.state, batch)
        return metrics["loss"]

    def block(self) -> None:
        jax.block_until_ready(self.state)

    # -- reading the state back --------------------------------------------

    def _parts(self, leaf):
        from repro.training.optimizer import is_split
        if is_split(leaf):
            k, rest = leaf["host"].shape[0], leaf["dev"].shape[0]
            return [(leaf["host"], 0, k), (leaf["dev"], k, k + rest)]
        return [(leaf, 0, None)]

    def _on_device(self, x):
        if x.sharding.memory_kind == "device":
            return x
        return jax.device_put(x, x.sharding.with_memory_kind("device"))

    def grad_norms(self, b1: float) -> Dict[str, float]:
        """After the first step: each leaf's gradient as the optimizer took
        it, from its first moment mu = (1 - b1) * clipped gradient."""
        out = {}
        with jax.set_mesh(self.mesh):
            for n, leaf in self.state["mu"].items():
                ss = sum(float(self._sumsq(self._on_device(p)))
                         for p, _, _ in self._parts(leaf))
                out[n] = math.sqrt(ss) / (1.0 - b1)
        return out

    def change_norms(self) -> Dict[str, float]:
        """Each leaf's change of its f32 master copy since the weights
        were drawn."""
        out = {}
        with jax.set_mesh(self.mesh):
            for n, leaf in self.state["master"].items():
                ss = 0.0
                for p, lo, hi in self._parts(leaf):
                    p = self._on_device(p)
                    ss += float(self._diff[n](p, self.key, lo, hi,
                                              p.sharding))
                out[n] = math.sqrt(ss)
        return out

    def free(self) -> None:
        self.state = None
