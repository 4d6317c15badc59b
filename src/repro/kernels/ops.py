"""jit'd public wrappers around the Pallas kernels.

Every wrapper dispatches through :mod:`repro.kernels.router` — compiled
Pallas on TPU/GPU, the pure-jnp reference on CPU (interpret mode is a
debugging oracle, never a silent production path), overridable globally
via ``REPRO_KERNELS`` / ``TrainSpec.kernels`` and per call via ``force``
(the test suite's oracle sweeps).  The routing decision is made at trace
time and logged once by the router.

XLA cannot partition a Mosaic (Pallas TPU) kernel.  The elementwise
kernels therefore take the caller's layout as ``sharding`` (a
``NamedSharding`` of the array the caller passes): over more than one
device the kernel runs inside ``shard_map`` on each device's block.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import ref, router
from .dual_update import dual_update_pallas, dual_update_step_pallas
from .flash_attention import flash_attention_pallas
from .gossip_combine import (gossip_combine_pallas, quantized_combine_pallas,
                             stochastic_quantize_pallas)
from .rwkv6_scan import rwkv6_scan_pallas

Array = jax.Array


def _per_device(fn, sharding: Optional[NamedSharding], in_specs, out_specs):
    """``fn`` on each device's blocks when ``sharding`` spans devices."""
    if sharding is None or sharding.mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=sharding.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _spec(sharding: Optional[NamedSharding]) -> P:
    return P() if sharding is None else sharding.spec


def _stacked(spec: P) -> P:
    """The spec of ``(K,) + x.shape`` for an ``x`` laid out as ``spec``."""
    return P(None, *spec)


def dual_update(z: Array, w0: Array, beta: Array,
                radius: Optional[float] = None,
                force: Optional[str] = None,
                sharding: Optional[NamedSharding] = None) -> Array:
    """w = w0 - z/(2 beta), optionally projected onto ||w - w0|| <= radius.

    ``sharding``: the layout of ``z`` and ``w0``.
    """
    impl = router.resolve(force)
    if impl == "ref":
        w = ref.dual_update_ref(z, w0, beta)
    else:
        interpret = impl == "pallas_interpret"
        spec = _spec(sharding)
        w = _per_device(
            lambda z, w0, beta: dual_update_pallas(z, w0, beta,
                                                   interpret=interpret),
            sharding, (spec, spec, P()), spec)(z, w0, beta)
    return w if radius is None else project(w, w0, radius)


def project(w: Array, w0: Array, radius: float) -> Array:
    """f32 ``w`` projected onto the ball ||w - w0|| <= radius."""
    delta = w - w0.astype(jnp.float32)
    nrm = jnp.linalg.norm(delta.reshape(-1))
    return w0.astype(jnp.float32) + delta * jnp.minimum(
        1.0, radius / jnp.maximum(nrm, 1e-30))


def dual_step(z: Array, g: Array, w0: Array, beta: Array, out_dtype,
              force: Optional[str] = None,
              sharding: Optional[NamedSharding] = None):
    """One fused dual-averaging step of a leaf: (z + g, its prox in
    ``out_dtype``), with z updated in place where it is donated.

    ``sharding``: the layout of ``z``, ``g`` and ``w0``.
    """
    impl = router.resolve(force)
    if impl == "ref":
        return ref.dual_step_ref(z, g, w0, beta, out_dtype)
    interpret = impl == "pallas_interpret"
    spec = _spec(sharding)
    return _per_device(
        lambda z, g, w0, beta: dual_update_step_pallas(
            z, g, w0, beta, out_dtype=out_dtype, interpret=interpret),
        sharding, (spec, spec, spec, P()), (spec, spec))(z, g, w0, beta)


def gossip_combine(msgs, weights: Array, force: Optional[str] = None,
                   sharding: Optional[NamedSharding] = None) -> Array:
    """K-way weighted combine of neighbor messages: K arrays of one shape
    (or a (K, ...) array) -> one array of that shape, fp32.

    ``sharding``: the layout of each message.
    """
    impl = router.resolve(force)
    if impl == "ref":
        return ref.gossip_combine_ref(msgs, weights)
    interpret = impl == "pallas_interpret"
    msgs = list(msgs)
    spec = _spec(sharding)

    def local(ms, w):
        out = gossip_combine_pallas(ms, w, interpret=interpret)
        return out.reshape(ms[0].shape)

    return _per_device(local, sharding, ([spec] * len(msgs), P()),
                       spec)(msgs, weights)


def stochastic_quantize(m: Array, h: Array, rnd: Array, lo: Array,
                        scale: Array, levels: float = 255.0,
                        force: Optional[str] = None,
                        sharding: Optional[NamedSharding] = None):
    """Send half of a quantized gossip round: (levels u8, updated replica).

    ``sharding``: the layout of the ``(n, ...)`` worker-row arrays.
    """
    impl = router.resolve(force)
    if impl == "ref":
        return ref.stochastic_quantize_ref(m, h, rnd, lo, scale, levels)
    interpret = impl == "pallas_interpret"
    spec = _spec(sharding)
    return _per_device(
        lambda *a: stochastic_quantize_pallas(*a, levels=levels,
                                              interpret=interpret),
        sharding, (spec,) * 5, (spec, spec))(m, h, rnd, lo, scale)


def quantized_combine(m: Array, hnbr: Array, lvl: Array, lo: Array,
                      scale: Array, weights: Array,
                      force: Optional[str] = None,
                      sharding: Optional[NamedSharding] = None):
    """Receive half: fused dequantize + replica update + K-way combine.

    ``sharding``: the layout of the ``(n, ...)`` worker-row arrays.
    """
    impl = router.resolve(force)
    if impl == "ref":
        return ref.quantized_combine_ref(m, hnbr, lvl, lo, scale, weights)
    interpret = impl == "pallas_interpret"
    spec = _spec(sharding)
    taps = _stacked(spec)
    return _per_device(
        lambda *a: quantized_combine_pallas(*a, interpret=interpret),
        sharding, (spec, taps, taps, taps, taps, P()), (spec, taps))(
            m, hnbr, lvl, lo, scale, weights)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: int = 0, q_offset: int = 0,
                    force: Optional[str] = None) -> Array:
    """(B, H, Sq, hd) x (B, KV, Skv, hd) -> (B, H, Sq, hd)."""
    impl = router.resolve(force)
    if impl == "pallas_interpret":
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset, interpret=True)
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_offset=q_offset).astype(q.dtype)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)


def rwkv6_scan(r: Array, k: Array, v: Array, decay: Array, u: Array,
               force: Optional[str] = None) -> Array:
    """(BH, S, hd) wkv scan; u (BH, hd). Returns fp32 (BH, S, hd)."""
    impl = router.resolve(force)
    if impl == "pallas_interpret":
        return rwkv6_scan_pallas(r, k, v, decay, u, interpret=True)
    if impl == "ref":
        bh, s, hd = r.shape
        rr = lambda t: t.reshape(1, bh, s, hd)   # treat BH rows as heads
        y = ref.rwkv6_chunk_ref(rr(r), rr(k), rr(v), rr(decay),
                                u.reshape(bh, hd))
        return y.reshape(bh, s, hd)
    return rwkv6_scan_pallas(r, k, v, decay, u)
