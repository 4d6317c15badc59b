"""Compile the training path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one routed kernel (``repro.kernels.ops``,
``force="pallas"``) at a real leaf shape of qwen2-1.5b and compiles it
with the TPU compiler for a chip that is described, not attached.  That
refuses what interpret mode accepts: tiles not aligned to the layout,
VMEM over its limit, a program over the chip's memory.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers
import every test file.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# qwen2-1.5b leaves the dual-averaging prox visits (published widths)
EMBED = (151936, 1536)          # embed / unembed
UNEMBED = (1536, 151936)        # the output head, (d_model, vocabulary)
MLP = (12, 1536, 8960)          # gate / up stacked over 12 layers
NORM = (1536,)                  # final_norm, ln1/ln2 rows
KV_BIAS = (12, 256)             # bk / bv stacked over 12 layers
# phi3.5-moe's f32 router (d_model, experts) stacked over 32 layers: a
# narrow leaf, which the TPU lays out with its minor dimensions swapped
ROUTER = (32, 4096, 16)
# one worker's gossip row on the four-chip mesh: every parameter of the
# one-layer cut plus the eq.-6 weight column, padded to whole f32 tiles
PAYLOAD = 513546752 + 1 + 511
# the quantized kernels pad and copy their operands per call: at PAYLOAD
# that program needs 18.2 GiB, so they are compiled at the one-layer cut
# with an 8,192 vocabulary (still model-sized, not a multiple of 128)
Q_PAYLOAD = 71961600 + 1
TAPS = 3                        # ring: self + two neighbours


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a rehearsal compile cannot be read back without a chip; keep the
    # persistent cache out of it (reset: the cache memoises whether it is on)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, donate=(), kernel=True):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return compiled


@pytest.mark.parametrize("shape", [EMBED, NORM, KV_BIAS],
                         ids=["embed", "norm", "kv_bias"])
def test_dual_update_compiles(one_chip, shape):
    _compile(lambda z, w0, beta: ops.dual_update(z, w0, beta,
                                                 force="pallas"),
             one_chip, (shape, jnp.float32), (shape, jnp.float32),
             ((), jnp.float32))


@pytest.mark.parametrize("shape,dtype",
                         [(EMBED, jnp.bfloat16), (UNEMBED, jnp.bfloat16),
                          (MLP, jnp.bfloat16), (KV_BIAS, jnp.bfloat16),
                          (NORM, jnp.bfloat16), (ROUTER, jnp.float32)],
                         ids=["embed", "unembed", "mlp", "kv_bias", "norm",
                              "router"])
def test_dual_step_compiles_in_place(one_chip, shape, dtype):
    """The fused step is one pass on the leaf as it lies: no pad, no
    relayout of a leaf of rank >= 2, no temporaries, z updated in place
    (``dtype``: of g and the params).  It is one kernel, but on a leaf
    whose last dimension is not whole lanes, one XLA fusion."""
    f32 = (shape, jnp.float32)
    kernel = shape[-1] % 128 == 0
    compiled = _compile(lambda z, g, w0, beta: ops.dual_step(
        z, g, w0, beta, dtype, force="pallas"),
        one_chip, f32, (shape, dtype), f32, ((), jnp.float32),
        donate=(0,), kernel=kernel)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == kernel
    assert "pad(" not in text
    if len(shape) >= 2:
        assert "reshape(" not in text
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 2 ** 20
    assert ma.alias_size_in_bytes >= 4 * math.prod(shape)


@pytest.mark.parametrize("n", [PAYLOAD, 999], ids=["payload", "short"])
def test_gossip_combine_compiles(one_chip, n):
    _compile(lambda w, *m: ops.gossip_combine(m, w, force="pallas"),
             one_chip, ((TAPS,), jnp.float32), *[((n,), jnp.float32)] * TAPS)


def test_stochastic_quantize_compiles(one_chip):
    row = ((1, Q_PAYLOAD), jnp.float32)
    col = ((1, 1), jnp.float32)
    _compile(lambda m, h, r, lo, sc: ops.stochastic_quantize(
        m, h, r, lo, sc, 15.0, force="pallas"),
        one_chip, row, row, row, col, col)


def test_quantized_combine_compiles(one_chip):
    taps = TAPS - 1
    _compile(lambda m, h, lvl, lo, sc, w: ops.quantized_combine(
        m, h, lvl, lo, sc, w, force="pallas"),
        one_chip, ((1, Q_PAYLOAD), jnp.float32),
        ((taps, 1, Q_PAYLOAD), jnp.float32),
        ((taps, 1, Q_PAYLOAD), jnp.uint8),
        ((taps, 1, 1), jnp.float32), ((taps, 1, 1), jnp.float32),
        ((TAPS,), jnp.float32))
