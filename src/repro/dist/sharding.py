"""Mesh-scoped sharding context + logical-axis constraint helper.

The model code never names mesh axes directly: it annotates activations with
*logical* axes (``"batch"``, ``"seq"``, ``"vocab"``, ``"expert"``) via
:func:`constrain`.  Under :func:`use_sharding` those resolve to the active
mesh's physical axes ("batch" spans the worker axes ``("pod", "data")``,
vocab/expert go on ``"model"``); outside a mesh context — or on a dimension
the mesh extent does not divide — the annotation is a no-op.  This is what
lets one forward() serve the single-device smoke tests, the 8-host-device
subprocess tests, and the 512-chip dry-run unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

_STATE = threading.local()

# logical activation axis -> candidate mesh axes (filtered by presence).
LOGICAL_AXES = {
    "batch": ("pod", "data"),     # AMB worker axes (data parallel)
    "seq": (),                    # no sequence parallelism (future PR)
    "vocab": ("model",),
    "expert": ("model",),
    "model": ("model",),
    "heads": ("model",),
}


def active_mesh():
    """The mesh installed by the innermost :func:`use_sharding`, or None."""
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def use_sharding(mesh):
    """Install ``mesh`` as the ambient mesh for :func:`constrain` calls.

    Trace-time scoped: functions jitted *and traced* inside the context bake
    the constraints in; the same code traced outside is unconstrained.
    """
    prev = getattr(_STATE, "mesh", None)
    _STATE.mesh = mesh
    try:
        yield mesh
    finally:
        _STATE.mesh = prev


def worker_axes(mesh) -> tuple:
    """Mesh axes that enumerate AMB workers (everything but "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def _resolve(mesh, logical: Optional[str], dim: int):
    """Mesh axes for one logical axis on a dim of extent ``dim`` (or None)."""
    if logical is None:
        return None
    axes = tuple(a for a in LOGICAL_AXES[logical] if a in mesh.axis_names)
    if not axes:
        return None
    extent = int(np.prod([mesh.shape[a] for a in axes]))
    if extent <= 1 or dim % extent != 0:
        return None
    return axes if len(axes) > 1 else axes[0]


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """``with_sharding_constraint`` under the active mesh; no-op otherwise.

    One logical name (or None) per dimension of ``x``.  Axes whose mesh
    extent does not divide the dimension are dropped (replicated) rather
    than erroring — the whisper-vocab rule, same as ``params.param_spec``.
    """
    mesh = active_mesh()
    if mesh is None or not isinstance(x, jax.core.Tracer):
        return x                      # eager or unmeshed: annotation-free
    if len(logical_axes) != x.ndim:
        raise ValueError(
            f"constrain: {len(logical_axes)} logical axes for rank-{x.ndim}")
    spec = P(*(_resolve(mesh, name, d)
               for name, d in zip(logical_axes, x.shape)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
