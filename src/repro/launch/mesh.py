"""Production mesh construction (defined as functions so importing this
module never touches jax device state).  Meshes are built through
`repro.compat.make_mesh`, which pins every axis to Auto."""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    """Spec mesh: 16x16 (data, model) per pod; 2x16x16 (pod, data, model)
    for the two-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_plan_mesh(dp: int, tp: int, *, stages: int = 1,
                   pod: Optional[int] = None):
    """Mesh view for an arbitrary plan: (stage?, pod?, data, model)."""
    shape: Tuple[int, ...] = ()
    axes: Tuple[str, ...] = ()
    if stages > 1:
        shape += (stages,)
        axes += ("stage",)
    if pod and pod > 1:
        shape += (pod,)
        axes += ("pod",)
    shape += (dp, tp)
    axes += ("data", "model")
    return compat.make_mesh(shape, axes)


def make_host_mesh(n: Optional[int] = None, tp: int = 1):
    """Small CPU mesh for tests/examples."""
    n = n or len(jax.devices())
    dp = n // tp
    return compat.make_mesh((dp, tp), ("data", "model"))
