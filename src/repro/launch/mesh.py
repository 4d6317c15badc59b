"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Functions (not module-level constants) so importing never touches jax
device state: the dry-run sets XLA_FLAGS for 512 host devices *before* any
jax import; smoke tests see the real single device.

Every mesh is built with ``AxisType.Auto`` axes.  The model relies on
GSPMD propagation: ``repro.dist.sharding.constrain`` adds
``with_sharding_constraint`` hints and leaves the rest to the compiler.
``jax.make_mesh`` defaults to Explicit axes, under which ops such as the
embedding gather refuse to infer an output sharding.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagation).

    ``devices``: the devices to lay out (default: all of them), e.g. those
    of a described topology for a compile with no chip attached.
    """
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (one v5e pod, 256 chips) or 2x16x16 (two pods, 512 chips).

    Axes: "data" = AMB workers (data parallel / FSDP), "model" =
    tensor/expert parallel inside a worker, "pod" = the cross-pod worker
    axis (consensus spans ("pod", "data") jointly).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, *, pod: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    ndev = len(jax.devices())
    need = data * model * pod
    if ndev < need:
        raise RuntimeError(f"need {need} devices, have {ndev} "
                           f"(set --xla_force_host_platform_device_count)")
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
