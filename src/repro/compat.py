"""jax helpers shared by the runtime and the analysis stack.

The repo targets the installed jax (0.9): meshes carry explicit axis
types, the ambient mesh is entered with ``jax.set_mesh`` and manual
regions use ``jax.shard_map``.  ``make_mesh`` pins every axis to Auto
(jax 0.9 defaults ``jax.make_mesh`` to Explicit axes, which the
GSPMD-annotated step builders do not use).

The module also gates jax *availability* for the analysis stack: the
tuner / cost-model path is pure numpy and must keep working in numpy-only
containers, with the optional jax tape backend (``Tape.lower_jax``,
``StageCostModel(backend=...)``) degrading cleanly when jax is absent.
Those callers probe ``has_jax()`` / ``require_jax()`` here instead of
importing jax themselves.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

try:
    import jax
except Exception as _e:          # numpy-only container: analysis-only mode
    jax = None                   # type: ignore[assignment]
    _JAX_IMPORT_ERROR: Exception = _e


def has_jax() -> bool:
    """Whether jax imported successfully (tape backends probe this)."""
    return jax is not None


def require_jax() -> Tuple["jax", "jax.numpy"]:
    """(jax, jax.numpy), or ImportError with the original import failure."""
    if jax is None:
        raise ImportError(
            "jax is unavailable in this environment; use the numpy tape "
            "backend") from _JAX_IMPORT_ERROR
    import jax.numpy as jnp
    return jax, jnp


def jax_x64_enabled() -> bool:
    """Whether jax currently produces 64-bit floats (honors both the
    global ``jax_enable_x64`` flag and the scoped ``jax.enable_x64``
    context).  The tape backends' bitwise-equivalence-to-numpy guarantee
    holds only when this is True; ``backend="auto"`` refuses jax
    otherwise."""
    if jax is None:
        return False
    import numpy as np
    import jax.numpy as jnp
    return jnp.result_type(float) == np.float64


def enable_x64():
    """Context manager forcing 64-bit jax types inside the block (the
    backend equivalence suite runs under it)."""
    if jax is None:
        raise ImportError("jax is unavailable; cannot enable x64")
    return jax.enable_x64(True)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis Auto."""
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(auto,) * len(axes))


HOST_MEMORY_KIND = "pinned_host"


@functools.lru_cache(maxsize=1)
def host_memory_kind() -> str:
    """Memory kind for host-offloaded state: ``"pinned_host"``.

    Every backend the repo runs on (TPU, and the CPU of jax 0.9) exposes
    it; a backend without it raises instead of quietly keeping offloaded
    state resident, so a plan's WO/OO/AO ratios always mean what the
    tuner priced.  Cached: callers probe it per pytree leaf, and a
    backend's memory spaces don't change within a process."""
    dev = jax.devices()[0]
    kinds = {m.kind for m in dev.addressable_memories()}
    if HOST_MEMORY_KIND not in kinds:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} has no pinned_host "
            f"memory (memory kinds: {sorted(kinds)}); host offload "
            f"(wo/oo/ao > 0) cannot be placed")
    return HOST_MEMORY_KIND
