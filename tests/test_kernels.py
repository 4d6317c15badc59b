"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.dual_averaging import BetaSchedule
from repro.kernels import ops, ref, router
from repro.kernels.dual_update import (LANE, STEP_BLOCK, SUBLANE,
                                       _step_tile, dual_update_pallas,
                                       dual_update_step_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gossip_combine import (gossip_combine_pallas,
                                          quantized_combine_pallas,
                                          stochastic_quantize_pallas)
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas
from repro.optim import DualAveragingOpt


# ---------------------------------------------------------------------------
# dual_update
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (128,), (1000, 37), (3, 5, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dual_update_sweep(shape, dtype):
    k = jax.random.PRNGKey(0)
    z = jax.random.normal(k, shape, jnp.float32)
    w0 = jax.random.normal(jax.random.fold_in(k, 1), shape, dtype)
    beta = jnp.float32(1.7)
    got = dual_update_pallas(z, w0, beta, interpret=True, block=2048)
    want = ref.dual_update_ref(z, w0, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dual_update_op_with_radius():
    z = jnp.full((16,), 100.0)
    w0 = jnp.zeros((16,))
    w = ops.dual_update(z, w0, jnp.float32(1.0), radius=1.0, force="ref")
    assert abs(float(jnp.linalg.norm(w)) - 1.0) < 1e-5


def _bits(x):
    return np.asarray(x.astype(jnp.float32)).view(np.uint32)


# (shape, block): partial edge blocks in both dimensions (16-row blocks of
# 1,024 and 128 columns), a leaf under one block, a 1-D leaf, a 3-D leaf,
# a leaf narrower than a lane tile (16-row blocks, a partial last one)
@pytest.mark.parametrize("shape,block", [((40, 3000), 16384),
                                         ((100, 300), 2048),
                                         ((12, 256), STEP_BLOCK),
                                         ((7,), STEP_BLOCK),
                                         ((3, 16, 129), 2048),
                                         ((300, 16), 2048)],
                         ids=["edges", "edges_narrow", "small", "1d", "3d",
                              "narrow"])
@pytest.mark.parametrize("gdtype", [jnp.float32, jnp.bfloat16],
                         ids=["g_f32", "g_bf16"])
@pytest.mark.parametrize("pdtype", [jnp.float32, jnp.bfloat16],
                         ids=["p_f32", "p_bf16"])
def test_dual_step_matches_two_pass_update(shape, block, gdtype, pdtype):
    """The fused step is the add, the prox kernel and the cast, bit for bit."""
    k = jax.random.PRNGKey(0)
    z = jax.random.normal(k, shape, jnp.float32)
    g = jax.random.normal(jax.random.fold_in(k, 1), shape, gdtype)
    w0 = jax.random.normal(jax.random.fold_in(k, 2), shape, jnp.float32)
    beta = jnp.float32(1.7)
    z_new, w = dual_update_step_pallas(z, g, w0, beta, out_dtype=pdtype,
                                       block=block, interpret=True)
    z_old = z + g.astype(jnp.float32)
    w_old = dual_update_pallas(z_old, w0, beta, interpret=True).astype(pdtype)
    assert z_new.dtype == jnp.float32 and w.dtype == pdtype
    assert z_new.shape == w.shape == shape
    np.testing.assert_array_equal(_bits(z_new), _bits(z_old))
    np.testing.assert_array_equal(_bits(w), _bits(w_old))


@pytest.mark.parametrize("view", [(131072, 16), (1, 1536), (12, 256),
                                  (151936, 1536), (1536, 151936),
                                  (10, 100), (1, 10_000_000)])
def test_step_tile_fits_block_in_vmem(view):
    """A fused-step block, its rows and lanes padded to whole (16, 128)
    tiles as VMEM holds them, takes at most ``STEP_BLOCK`` elements, and
    each side is tile-aligned or the whole dimension."""
    rows, cols = view
    r, c = _step_tile(rows, cols, STEP_BLOCK)
    assert r % SUBLANE == 0 or r == rows
    assert c % LANE == 0 or c == cols
    assert -(-r // SUBLANE) * SUBLANE * -(-c // LANE) * LANE <= STEP_BLOCK


def _small_tree():
    k = jax.random.PRNGKey(3)
    params = {"embed": jax.random.normal(k, (40, 24), jnp.bfloat16),
              "blocks": {"w": jax.random.normal(jax.random.fold_in(k, 1),
                                                (2, 24, 136), jnp.bfloat16),
                         "norm": jnp.ones((2, 24), jnp.bfloat16)},
              "head_b": jnp.zeros((9,), jnp.float32)}
    leaves, tree = jax.tree.flatten(params)
    grads = tree.unflatten([
        0.3 * jax.random.normal(jax.random.fold_in(k, 10 + i), p.shape,
                                p.dtype) for i, p in enumerate(leaves)])
    return params, grads


@pytest.mark.parametrize("route", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("radius", [None, 1.0], ids=["fused", "radius"])
def test_dual_averaging_apply_matches_two_pass_update(radius, route):
    """apply over a tree, two steps: the fused path (radius None) and the
    kept radius path both equal z += g, then the prox op, then the cast."""
    params, grads = _small_tree()
    opt = DualAveragingOpt(beta=BetaSchedule(k=10.0, mu=1.0, scale=10.0),
                           radius=radius)
    router.set_mode(route)
    try:
        state = opt.init(params)
        new_params, new_state = params, state
        for _ in range(2):
            z, w0 = new_state["z"], new_state["w0"]
            beta = opt.beta((new_state["t"] + 1).astype(jnp.float32) + 1.0)
            want_z = jax.tree.map(lambda z, g: z + g.astype(jnp.float32),
                                  z, grads)
            want_p = jax.tree.map(
                lambda z, w0, p: ops.dual_update(
                    z, w0, beta, radius, force=route).astype(p.dtype),
                want_z, w0, new_params)
            new_params, new_state = jax.jit(opt.apply)(
                grads, new_state, new_params)
            for got, want in ((new_state["z"], want_z),
                              (new_params, want_p)):
                assert (jax.tree.structure(got)
                        == jax.tree.structure(want))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                    assert a.dtype == b.dtype
                    np.testing.assert_array_equal(_bits(a), _bits(b))
        assert int(new_state["t"]) == 2
    finally:
        router.set_mode(None)


def test_prox_fault_still_leaves_params_unchanged(monkeypatch):
    """The benchmark's ``prox`` fault wraps apply: z moves, params do not."""
    from pathlib import Path
    monkeypatch.syspath_prepend(str(Path(__file__).resolve().parents[1]))
    monkeypatch.setattr(DualAveragingOpt, "apply", DualAveragingOpt.apply)
    from bench import faults
    faults.plant("prox")
    params, grads = _small_tree()
    opt = DualAveragingOpt()
    state = opt.init(params)
    new_params, new_state = opt.apply(grads, state, params)
    for a, b in zip(jax.tree.leaves(new_params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(_bits(a), _bits(b))
    for z, g in zip(jax.tree.leaves(new_state["z"]), jax.tree.leaves(grads)):
        np.testing.assert_array_equal(_bits(z), _bits(g.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# gossip_combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n", [(2, 100), (3, 4096), (5, 999)])
def test_gossip_combine_sweep(k, n):
    key = jax.random.PRNGKey(1)
    msgs = jax.random.normal(key, (k, n))
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (k,)))
    got = gossip_combine_pallas(msgs, w, interpret=True, block_rows=8)
    want = ref.gossip_combine_ref(msgs, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# quantized-gossip kernels (send: stochastic quantize; receive: combine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,bits", [(4, 300, 8), (3, 1024, 4), (8, 77, 8)])
def test_stochastic_quantize_sweep(n, d, bits):
    key = jax.random.PRNGKey(2)
    m = jax.random.normal(key, (n, d)) * 2.0
    h = jax.random.normal(jax.random.fold_in(key, 1), (n, d)) * 0.3
    rnd = jax.random.uniform(jax.random.fold_in(key, 2), (n, d))
    diff = m - h
    lo = diff.min(-1, keepdims=True)
    scale = jnp.maximum(diff.max(-1, keepdims=True) - lo, 1e-12) \
        / (2 ** bits - 1)
    lvl, hnew = stochastic_quantize_pallas(m, h, rnd, lo, scale,
                                           interpret=True, block_rows=4)
    lvl_r, hnew_r = ref.stochastic_quantize_ref(m, h, rnd, lo, scale)
    np.testing.assert_array_equal(np.asarray(lvl), np.asarray(lvl_r))
    np.testing.assert_allclose(np.asarray(hnew), np.asarray(hnew_r),
                               rtol=1e-5, atol=1e-5)
    assert int(lvl.max()) <= 2 ** bits - 1


@pytest.mark.parametrize("n,d,km1", [(4, 300, 2), (6, 129, 4)])
def test_quantized_combine_sweep(n, d, km1):
    key = jax.random.PRNGKey(3)
    m = jax.random.normal(key, (n, d))
    hnbr = jax.random.normal(jax.random.fold_in(key, 1), (km1, n, d))
    lvl = jax.random.randint(jax.random.fold_in(key, 2), (km1, n, d),
                             0, 256).astype(jnp.uint8)
    lo = jax.random.normal(jax.random.fold_in(key, 3), (km1, n, 1))
    scale = jax.random.uniform(jax.random.fold_in(key, 4),
                               (km1, n, 1)) * 0.01
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 5),
                                         (km1 + 1,)))
    got_o, got_h = quantized_combine_pallas(m, hnbr, lvl, lo, scale, w,
                                            interpret=True, block_rows=8)
    want_o, want_h = ref.quantized_combine_ref(m, hnbr, lvl, lo, scale, w)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

CASES = [
    # (B, H, KV, Sq, Skv, hd, causal, window)
    (1, 4, 4, 64, 64, 32, True, 0),        # MHA causal
    (2, 4, 2, 100, 100, 64, True, 0),      # GQA, ragged seq
    (1, 8, 2, 128, 128, 64, True, 32),     # sliding window
    (1, 2, 2, 64, 128, 32, False, 0),      # cross attention (no causal)
    (1, 4, 1, 257, 257, 64, True, 64),     # MQA, odd seq
]


@pytest.mark.parametrize("b,h,kv,sq,skv,hd,causal,window", CASES)
def test_flash_attention_sweep(b, h, kv, sq, skv, hd, causal, window):
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (b, h, sq, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, skv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, skv, hd))
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 4, 64, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 64),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 64),
                          jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, window=0,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_flash_attention_q_offset_decode_semantics():
    """q_offset positions queries mid-cache (decode-style masking)."""
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (1, 2, 8, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 32))
    got = flash_attention_pallas(q, k, v, causal=True, window=0, q_offset=40,
                                 block_q=8, block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=0,
                                   q_offset=40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bh,s,hd,chunk", [(2, 64, 32, 16), (4, 100, 64, 16),
                                           (1, 17, 64, 8), (3, 256, 64, 32)])
def test_rwkv6_scan_sweep(bh, s, hd, chunk):
    key = jax.random.PRNGKey(5)
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i), (bh, s, hd))
    r, k, v = mk(0), mk(1), mk(2)
    decay = 0.2 + 0.8 * jax.random.uniform(jax.random.fold_in(key, 3),
                                           (bh, s, hd))
    u = jax.random.normal(jax.random.fold_in(key, 4), (bh, hd))
    got = rwkv6_scan_pallas(r, k, v, decay, u, chunk=chunk, interpret=True)
    want = ref.rwkv6_chunk_ref(
        r.reshape(1, bh, s, hd), k.reshape(1, bh, s, hd),
        v.reshape(1, bh, s, hd), decay.reshape(1, bh, s, hd),
        u).reshape(bh, s, hd)
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 50))
def test_rwkv6_ops_matches_ref_property(seed):
    key = jax.random.PRNGKey(seed)
    bh, s, hd = 2, 37, 64
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i), (bh, s, hd))
    decay = 0.5 + 0.5 * jax.random.uniform(jax.random.fold_in(key, 9),
                                           (bh, s, hd))
    u = jax.random.normal(jax.random.fold_in(key, 4), (bh, hd))
    got = ops.rwkv6_scan(mk(0), mk(1), mk(2), decay, u,
                         force="pallas_interpret")
    want = ops.rwkv6_scan(mk(0), mk(1), mk(2), decay, u, force="ref")
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=3e-5)
