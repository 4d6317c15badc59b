"""Pallas TPU kernels for the mesh gossip consensus phase.

Three kernels, one per dataflow stage of a consensus round (paper Alg. 1
line 16, plus the CHOCO-style delta compression of
:func:`repro.core.extensions.gossip_quantized`):

  * :func:`gossip_combine_pallas` — fp32 K-way weighted combine
    ``out = sum_k w_k * msg_k``: the K neighbor messages arrive as K
    arrays after the collective_permute exchange and the weighted
    accumulation is fused in a single VMEM pass instead of K separate
    scale-and-adds over an HBM-resident model-sized buffer.

  * :func:`stochastic_quantize_pallas` — the *send* half of a quantized
    round, fused in one pass per block: recompute ``diff = m - h``,
    stochastically round to ``levels = floor(u) + Bernoulli(frac(u))`` on
    the per-node uniform grid (lo/scale precomputed row-wide), and update
    the node's public replica ``h += lo + levels * scale``.  The uint8
    ``levels`` plane is the wire message — (32/bits)x fewer
    collective-permute bytes than the fp32 message.

  * :func:`quantized_combine_pallas` — the *receive* half, fused: for each
    of the K-1 neighbor taps, dequantize the received levels into the local
    replica ``hnbr_k += lo_k + levels_k * scale_k`` and accumulate the
    weighted combine ``out = w_0 * m + sum_k w_k * hnbr_k`` without ever
    materializing the dequantized messages in HBM.

The fusion boundary between the send and receive kernels is the ICI
exchange itself (the rolled uint8 planes); everything on either side of it
is one VMEM pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array
LANE = 128


# f32 elements of one (8, 128) tile: a message whose size is a multiple of
# this views as lane-dense (rows, 128) tiles without a copy
ALIGN = 8 * LANE


def _kernel(w_ref, *refs, k: int):
    msg_refs, o_ref = refs[:k], refs[k]
    acc = w_ref[0, 0] * msg_refs[0][...]
    for j in range(1, k):
        acc = acc + w_ref[0, j] * msg_refs[j][...]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def gossip_combine_pallas(msgs, weights: Array, *, block_rows: int = 512,
                          interpret: bool = False) -> Array:
    """msgs: K arrays of N elements each; weights: (K,). Returns (N,) fp32.

    The K messages come as separate arrays (a (K, N) array also iterates
    as K rows), so the caller never stacks K model-sized copies.  Each is
    viewed as (N / 128, 128) lane-dense rows, in place when N is a
    multiple of :data:`ALIGN`; any other N is padded (a copy) first.
    """
    msgs = [m.reshape(-1).astype(jnp.float32) for m in msgs]
    k, n = len(msgs), msgs[0].size
    pad = (-n) % ALIGN
    if pad:
        msgs = [jnp.pad(m, (0, pad)) for m in msgs]
    rows = (n + pad) // LANE
    block_rows = min(block_rows, rows)
    tile = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[pl.BlockSpec((1, k), lambda i: (0, 0))] + [tile] * k,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        # block i of the output reads only block i of each message, so it
        # may overwrite the first (self) message in place
        input_output_aliases={1: 0},
        interpret=interpret,
    )(weights.astype(jnp.float32).reshape(1, k),
      *[m.reshape(rows, LANE) for m in msgs])
    out = out.reshape(-1)
    return out[:n] if pad else out


# ---------------------------------------------------------------------------
# Quantized gossip: send half (stochastic quantize + replica update)
# ---------------------------------------------------------------------------

def _pad_rows(x: Array, block_rows: int):
    """(n, d) -> (n, rows_padded, LANE) plus the grid size along rows."""
    n, d = x.shape
    pad = (-d) % LANE
    x = jnp.pad(x, ((0, 0), (0, pad)))
    rows = x.shape[1] // LANE
    grid_r = -(-rows // block_rows)
    x = x.reshape(n, rows, LANE)
    x = jnp.pad(x, ((0, 0), (0, grid_r * block_rows - rows), (0, 0)))
    return x, grid_r


def _squantize_kernel(m_ref, h_ref, rnd_ref, lo_ref, scale_ref,
                      lvl_ref, hnew_ref, *, levels: float):
    lo = lo_ref[0, 0]
    scale = scale_ref[0, 0]
    diff = m_ref[...].astype(jnp.float32) - h_ref[...].astype(jnp.float32)
    u = (diff - lo) / scale
    fl = jnp.floor(u)
    lvl = fl + (rnd_ref[...] < (u - fl)).astype(jnp.float32)
    # clamp: the row max can round to u = levels + eps; an up-round there
    # would emit 2^bits, which wraps past the top of the uint8 wire plane
    lvl = jnp.minimum(lvl, levels)
    # Mosaic casts float <-> uint8 only by way of int32
    lvl_ref[...] = lvl.astype(jnp.int32).astype(jnp.uint8)
    hnew_ref[...] = h_ref[...].astype(jnp.float32) + lo + lvl * scale


@functools.partial(jax.jit,
                   static_argnames=("levels", "block_rows", "interpret"))
def stochastic_quantize_pallas(m: Array, h: Array, rnd: Array, lo: Array,
                               scale: Array, *, levels: float = 255.0,
                               block_rows: int = 512,
                               interpret: bool = False):
    """Quantize ``m - h`` onto the per-row uniform grid; update the replica.

    m, h, rnd: (n, d); lo, scale: (n, 1) row-wide grid (precomputed: the
    min and (max-min)/levels of ``m - h``; ``levels = 2^bits - 1``).
    Returns ``(levels (n, d) uint8, h_new (n, d) f32)`` with
    ``levels = min(floor(u) + [rnd < frac(u)], levels)``,
    ``u = (m - h - lo)/scale``, and ``h_new = h + lo + levels * scale`` —
    bit-identical to :func:`repro.core.extensions.quantize_unbiased`
    given the same ``rnd``.
    """
    n, d = m.shape
    mp, grid_r = _pad_rows(m, block_rows)
    hp, _ = _pad_rows(h, block_rows)
    rp, _ = _pad_rows(rnd, block_rows)

    lvl, hnew = pl.pallas_call(
        functools.partial(_squantize_kernel, levels=float(levels)),
        grid=(n, grid_r),
        in_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(mp.shape, jnp.uint8),
            jax.ShapeDtypeStruct(mp.shape, jnp.float32),
        ],
        interpret=interpret,
    )(mp, hp, rp, lo.astype(jnp.float32), scale.astype(jnp.float32))
    unpad = lambda x: x.reshape(n, -1)[:, :d]
    return unpad(lvl), unpad(hnew)


# ---------------------------------------------------------------------------
# Quantized gossip: receive half (dequantize + combine + replica update)
# ---------------------------------------------------------------------------

def _qcombine_kernel(m_ref, hnbr_ref, lvl_ref, lo_ref, scale_ref, w_ref,
                     out_ref, hnbr_new_ref, *, k: int):
    acc = w_ref[0, 0] * m_ref[...].astype(jnp.float32)
    for j in range(k - 1):
        h = (hnbr_ref[j].astype(jnp.float32)
             + lo_ref[j, 0, 0]
             + lvl_ref[j].astype(jnp.int32).astype(jnp.float32)
             * scale_ref[j, 0, 0])
        hnbr_new_ref[j] = h
        acc = acc + w_ref[0, j + 1] * h
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantized_combine_pallas(m: Array, hnbr: Array, lvl: Array, lo: Array,
                             scale: Array, weights: Array, *,
                             block_rows: int = 512,
                             interpret: bool = False):
    """Dequantize the K-1 received neighbor deltas and combine, one pass.

    m: (n, d) self messages; hnbr: (K-1, n, d) running neighbor replicas;
    lvl: (K-1, n, d) uint8 received levels; lo, scale: (K-1, n, 1) received
    grid scalars; weights: (K,) = [P_self, P_tap_1, ...].  Returns
    ``(out (n, d) f32, hnbr_new (K-1, n, d) f32)`` with
    ``hnbr_new[k] = hnbr[k] + lo_k + lvl_k * scale_k`` and
    ``out = weights[0] * m + sum_k weights[k+1] * hnbr_new[k]``.
    """
    km1, n, d = hnbr.shape
    k = km1 + 1
    mp, grid_r = _pad_rows(m, block_rows)
    stack = lambda x, dt: jnp.stack(
        [_pad_rows(x[j].astype(dt), block_rows)[0] for j in range(km1)])
    hp = stack(hnbr, jnp.float32)
    lp = stack(lvl, jnp.uint8)
    w2 = weights.astype(jnp.float32).reshape(1, k)

    out, hnew = pl.pallas_call(
        functools.partial(_qcombine_kernel, k=k),
        grid=(n, grid_r),
        in_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((km1, 1, block_rows, LANE),
                         lambda i, j: (0, i, j, 0)),
            pl.BlockSpec((km1, 1, block_rows, LANE),
                         lambda i, j: (0, i, j, 0)),
            pl.BlockSpec((km1, 1, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((km1, 1, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda i, j: (i, j, 0)),
            pl.BlockSpec((km1, 1, block_rows, LANE),
                         lambda i, j: (0, i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(mp.shape, jnp.float32),
            jax.ShapeDtypeStruct(hp.shape, jnp.float32),
        ],
        interpret=interpret,
    )(mp, hp, lp, lo.astype(jnp.float32), scale.astype(jnp.float32), w2)
    unpad = lambda x: x.reshape(*x.shape[:-2], -1)[..., :d]
    return unpad(out), unpad(hnew)
