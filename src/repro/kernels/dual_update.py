"""Pallas TPU kernels for the dual-averaging update phase (paper eq. 7).

  * :func:`dual_update_step_pallas` — the whole update of a leaf in one
    pass: ``z += g`` and the prox ``w = w0 - z / (2 beta)`` cast to the
    parameter dtype, on the leaf in its own layout (no flatten, pad or cast
    copies), with ``z`` updated in place.  Reads z, g, w0 and writes z, w
    once: 16 B an element at bf16 parameters.
  * :func:`dual_update_pallas` — the prox alone over a flattened leaf
    (``ops.dual_update``), which the tests hold the fused step to.

Both are purely memory-bound; z is fp32 and model-sized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

LANE = 128
DEFAULT_BLOCK = 1024 * LANE      # elements per VMEM tile (512 KiB fp32)
SUBLANE = 16                     # rows of a bf16 (16, 128) tile
STEP_BLOCK = 256 * 1024          # VMEM elements per fused-step block, lane
                                 # padding included: 16 B an element at bf16
                                 # params and g, 20 B at f32; double-buffered
                                 # that is 8-10 MiB of v5e's 16 MiB scoped VMEM


def _kernel(z_ref, w0_ref, beta_ref, o_ref):
    beta = beta_ref[0, 0]
    z = z_ref[...].astype(jnp.float32)
    w0 = w0_ref[...].astype(jnp.float32)
    o_ref[...] = w0 - z * (0.5 / beta)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dual_update_pallas(z: Array, w0: Array, beta: Array, *,
                       block: int = DEFAULT_BLOCK,
                       interpret: bool = False) -> Array:
    """Flattens, pads to (rows, LANE) tiles, runs the fused prox.

    z: any shape (fp32 dual); w0: same shape; beta: scalar.
    Returns fp32 array of z.shape.
    """
    shape = z.shape
    n = z.size
    rows_per_block = max(block // LANE, 8)
    zf = z.reshape(-1)
    wf = w0.reshape(-1)
    pad = (-n) % LANE
    if pad:
        zf = jnp.pad(zf, (0, pad))
        wf = jnp.pad(wf, (0, pad))
    rows = zf.size // LANE
    grid = -(-rows // rows_per_block)
    row_pad = grid * rows_per_block - rows
    z2 = jnp.pad(zf.reshape(rows, LANE), ((0, row_pad), (0, 0)))
    w2 = jnp.pad(wf.reshape(rows, LANE), ((0, row_pad), (0, 0)))
    beta2 = jnp.reshape(beta.astype(jnp.float32), (1, 1))

    out = pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((rows_per_block, LANE), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_block, LANE), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pl.ANY if False else None),
        ],
        out_specs=pl.BlockSpec((rows_per_block, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(z2.shape, jnp.float32),
        interpret=interpret,
    )(z2, w2, beta2)
    return out.reshape(-1)[:n].reshape(shape)


def _step(z, g, w0, beta, out_dtype):
    """The fused step's f32 arithmetic: (z + g, the prox in ``out_dtype``)."""
    z = z + g.astype(jnp.float32)
    w = w0.astype(jnp.float32) - z * (0.5 / beta)
    return z, w.astype(out_dtype)


def _step_kernel(z_ref, g_ref, w0_ref, beta_ref, z_out_ref, w_ref):
    z_out_ref[...], w_ref[...] = _step(z_ref[...], g_ref[...], w0_ref[...],
                                       beta_ref[0, 0], w_ref.dtype)


def _step_tile(rows: int, cols: int, block: int) -> tuple[int, int]:
    """A (r, c) block that takes about ``block`` elements of VMEM for a
    (rows, cols) view, where a row is padded to whole 128-lane tiles: whole
    rows where 16 of them fit (contiguous in HBM), else 16 rows of
    lane-aligned columns; a dimension the block covers is taken whole."""
    lanes = -(-cols // LANE) * LANE
    if lanes * SUBLANE <= block:
        return min(block // lanes // SUBLANE * SUBLANE, rows), cols
    c = max(block // SUBLANE // LANE * LANE, LANE)
    return min(SUBLANE, rows), min(c, cols)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "block", "interpret"))
def dual_update_step_pallas(z: Array, g: Array, w0: Array, beta: Array, *,
                            out_dtype, block: int = STEP_BLOCK,
                            interpret: bool = False) -> tuple[Array, Array]:
    """z_new = z + g (f32), w = (w0 - z_new / (2 beta)) in ``out_dtype``.

    z, w0: f32 of one shape; g: that shape, any float dtype; beta: scalar.
    A leaf of rank >= 2 is viewed as (prod(shape[:-1]), shape[-1]), a 1-D
    leaf as (1, n), and tiled by a 2-D grid whose edge blocks may be
    partial.  z is aliased to z_new: a donated z is updated in place.

    A leaf of rank >= 2 whose last dimension is not whole lanes (an MoE
    router's experts) is one XLA fusion of the same arithmetic instead:
    the TPU lays such a leaf out with its two minor dimensions swapped
    where that pads less, and a Mosaic kernel takes only row-major
    operands, so the kernel would copy each one in and out.
    """
    shape = z.shape
    if z.ndim >= 2 and shape[-1] % LANE:
        return _step(z, g, w0, beta.astype(jnp.float32), out_dtype)
    view = (1, z.size) if z.ndim < 2 else (z.size // shape[-1], shape[-1])
    r, c = _step_tile(*view, block)
    tile = pl.BlockSpec((r, c), lambda i, j: (i, j))
    z_new, w = pl.pallas_call(
        _step_kernel,
        grid=(pl.cdiv(view[0], r), pl.cdiv(view[1], c)),
        in_specs=[tile, tile, tile, pl.BlockSpec((1, 1), lambda i, j: (0, 0))],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct(view, jnp.float32),
                   jax.ShapeDtypeStruct(view, out_dtype)),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(z.reshape(view), g.reshape(view), w0.reshape(view),
      jnp.reshape(beta.astype(jnp.float32), (1, 1)))
    return z_new.reshape(shape), w.reshape(shape)
