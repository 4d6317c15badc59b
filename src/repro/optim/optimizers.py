"""Pytree optimizers: the paper's dual averaging + AdamW/SGD baselines.

Dual averaging for deep networks generalises the paper's eq. (7) with
``h(w) = ||w - w(1)||^2`` (1-strongly convex, argmin = init — consistent with
eq. 2's ``w(1) = argmin h``), giving the closed-form prox

    w(t+1) = w(1) - z(t+1) / (2 beta(t+1)).

For convex problems with ``w(1) = 0`` this is exactly the paper's update.
On TPU the dual step ``z += g`` and the prox run as one Pallas kernel per
leaf (``repro.kernels.ops.dual_step``); a ``radius`` projection follows it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..core.dual_averaging import BetaSchedule

Array = jax.Array
PyTree = Any


class Optimizer:
    def init(self, params: PyTree) -> PyTree:
        raise NotImplementedError

    def apply(self, grads: PyTree, state: PyTree, params: PyTree,
              shardings: Optional[PyTree] = None) -> tuple[PyTree, PyTree]:
        """One update.  ``shardings``: the caller's ``NamedSharding`` per
        parameter leaf, for an update that runs a kernel which must be
        told the layout (None: unsharded)."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class DualAveragingOpt(Optimizer):
    beta: BetaSchedule = BetaSchedule(k=100.0, mu=1.0, scale=100.0)
    radius: Optional[float] = None    # optional L2 ball around init

    def init(self, params):
        return {
            "z": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "w0": jax.tree.map(lambda p: p.astype(jnp.float32), params),
            "t": jnp.zeros((), jnp.int32),
        }

    def apply(self, grads, state, params, shardings=None):
        from ..kernels import ops as kops
        t_new = state["t"] + 1
        beta = self.beta(t_new.astype(jnp.float32) + 1.0)
        shard = () if shardings is None else (shardings,)

        # z += g and the prox in one pass per leaf, z updated in place; a
        # projection needs the whole step's norm, so w stays f32 until it
        def step(z, g, w0, p, sharding=None):
            dtype = p.dtype if self.radius is None else jnp.float32
            z, w = kops.dual_step(z, g, w0, beta, dtype, sharding=sharding)
            if self.radius is not None:
                w = kops.project(w, w0, self.radius).astype(p.dtype)
            return z, w
        out = jax.tree.map(step, state["z"], grads, state["w0"], params,
                           *shard)
        z_new, new_params = jax.tree.transpose(
            jax.tree.structure(params), jax.tree.structure((0, 0)), out)
        return new_params, {"z": z_new, "w0": state["w0"], "t": t_new}


@dataclasses.dataclass(frozen=True)
class AdamW(Optimizer):
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0

    def init(self, params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"m": jax.tree.map(zeros, params),
                "v": jax.tree.map(zeros, params),
                "t": jnp.zeros((), jnp.int32)}

    def apply(self, grads, state, params, shardings=None):
        t = state["t"] + 1
        tf = t.astype(jnp.float32)
        c1 = 1.0 - self.b1 ** tf
        c2 = 1.0 - self.b2 ** tf

        def upd(m, v, g, p):
            g = g.astype(jnp.float32)
            m_new = self.b1 * m + (1 - self.b1) * g
            v_new = self.b2 * v + (1 - self.b2) * g * g
            step = (m_new / c1) / (jnp.sqrt(v_new / c2) + self.eps)
            p_new = p.astype(jnp.float32) - self.lr * (
                step + self.weight_decay * p.astype(jnp.float32))
            return m_new, v_new, p_new.astype(p.dtype)

        out = jax.tree.map(upd, state["m"], state["v"], grads, params)
        m_new = jax.tree.map(lambda o: o[0], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        v_new = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        p_new = jax.tree.map(lambda o: o[2], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return p_new, {"m": m_new, "v": v_new, "t": t}


@dataclasses.dataclass(frozen=True)
class Sgd(Optimizer):
    lr: float = 1e-2
    momentum: float = 0.0

    def init(self, params):
        if self.momentum:
            return {"v": jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)}
        return {}

    def apply(self, grads, state, params, shardings=None):
        if self.momentum:
            v_new = jax.tree.map(
                lambda v, g: self.momentum * v + g.astype(jnp.float32),
                state["v"], grads)
            p_new = jax.tree.map(
                lambda p, v: (p.astype(jnp.float32) - self.lr * v
                              ).astype(p.dtype), params, v_new)
            return p_new, {"v": v_new}
        p_new = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - self.lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return p_new, state


def make_optimizer(name: str, **kw) -> Optimizer:
    return {"dual_averaging": DualAveragingOpt, "adamw": AdamW,
            "sgd": Sgd}[name](**kw)
