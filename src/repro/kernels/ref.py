"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``<name>`` in this package has ``ref.<name>_ref`` with identical
semantics; tests sweep shapes/dtypes and assert allclose between the kernel
(interpret=True on CPU) and these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def dual_update_ref(z: Array, w0: Array, beta: Array) -> Array:
    """Fused dual-averaging prox: w = w0 - z / (2 beta).  fp32 math."""
    return (w0.astype(jnp.float32)
            - z.astype(jnp.float32) / (2.0 * beta.astype(jnp.float32)))


def dual_step_ref(z: Array, g: Array, w0: Array, beta: Array, out_dtype):
    """One dual-averaging step: z_new = z + g, w = the prox of z_new in
    ``out_dtype``.  fp32 math.  Returns (z_new, w)."""
    z_new = z + g.astype(jnp.float32)
    return z_new, dual_update_ref(z_new, w0, beta).astype(out_dtype)


def gossip_combine_ref(msgs, weights: Array) -> Array:
    """Weighted neighbor combine: out = sum_k weights[k] * msgs[k].

    msgs: K arrays of one shape (or a (K, ...) array); weights: (K,).
    This is one row of m <- P m restricted to the K in-neighborhood
    messages (self included).  Elementwise f32 multiply-adds in tap order,
    as the kernel does them (a contraction over K would run at the
    backend's matmul precision).
    """
    w = weights.astype(jnp.float32)
    out = w[0] * msgs[0].astype(jnp.float32)
    for j in range(1, len(msgs)):
        out = out + w[j] * msgs[j].astype(jnp.float32)
    return out


def stochastic_quantize_ref(m: Array, h: Array, rnd: Array, lo: Array,
                            scale: Array, levels: float = 255.0):
    """Send half of a quantized gossip round (see gossip_combine kernels).

    Returns (levels (n, d) uint8, h_new (n, d) f32): stochastic rounding of
    ``m - h`` onto the row grid (lo, scale, ``levels = 2^bits - 1`` steps)
    using the uniform draws ``rnd``, plus the updated public replica
    ``h + lo + levels * scale``.
    """
    diff = m.astype(jnp.float32) - h.astype(jnp.float32)
    u = (diff - lo.astype(jnp.float32)) / scale.astype(jnp.float32)
    fl = jnp.floor(u)
    lvl = jnp.minimum(fl + (rnd < (u - fl)).astype(jnp.float32),
                      float(levels))
    h_new = h.astype(jnp.float32) + lo + lvl * scale
    return lvl.astype(jnp.uint8), h_new


def quantized_combine_ref(m: Array, hnbr: Array, lvl: Array, lo: Array,
                          scale: Array, weights: Array):
    """Receive half: dequantize K-1 neighbor deltas, update replicas, combine.

    m: (n, d); hnbr: (K-1, n, d); lvl: (K-1, n, d) uint8; lo, scale:
    (K-1, n, 1); weights: (K,).  Returns (out (n, d), hnbr_new (K-1, n, d)).
    """
    w = weights.astype(jnp.float32)
    hnbr_new = (hnbr.astype(jnp.float32)
                + lo.astype(jnp.float32)
                + lvl.astype(jnp.float32) * scale.astype(jnp.float32))
    out = w[0] * m.astype(jnp.float32)
    for j in range(hnbr.shape[0]):
        out = out + w[j + 1] * hnbr_new[j]
    return out, hnbr_new


def flash_attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
                        window: int = 0, q_offset: int = 0) -> Array:
    """Naive softmax attention oracle.

    q: (B, H, Sq, hd); k, v: (B, KV, Skv, hd); GQA via H = KV * G.
    Returns (B, H, Sq, hd) in fp32.
    """
    b, h, sq, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qf = q.astype(jnp.float32).reshape(b, kvh, g, sq, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgqh,bkch->bkgqc", qf, kf) / jnp.sqrt(hd)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(k.shape[2])
    mask = jnp.ones((sq, k.shape[2]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqc,bkch->bkgqh", p, vf)
    return out.reshape(b, h, sq, hd)


def rwkv6_chunk_ref(r: Array, k: Array, v: Array, decay: Array,
                    u: Array) -> Array:
    """RWKV6 wkv over the full sequence, chunk-free sequential oracle.

    r, k, v, decay: (B, H, S, hd); u: (H, hd) current-token bonus.
    Returns y (B, H, S, hd), fp32.  decay in (0, 1].
    """
    b, h, s, hd = r.shape
    rf, kf, vf, df = (t.astype(jnp.float32) for t in (r, k, v, decay))

    def step(state, inp):
        rt, kt, vt, dt = inp                     # (B,H,hd)
        kv = jnp.einsum("bhd,bhe->bhde", kt, vt)
        y = jnp.einsum("bhd,bhde->bhe", rt, state + u[None, :, :, None] * kv)
        state = dt[..., None] * state + kv
        return state, y

    st0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    xs = tuple(t.transpose(2, 0, 1, 3) for t in (rf, kf, vf, df))
    _, ys = jax.lax.scan(step, st0, xs)
    return ys.transpose(1, 2, 0, 3)


def mamba2_chunk_ref(x: Array, b_mat: Array, c_mat: Array,
                     decay: Array) -> Array:
    """Mamba2/SSD sequential oracle.

    x: (B, S, H, hd) dt-scaled inputs; b_mat, c_mat: (B, S, ns);
    decay: (B, S, H) in (0,1].  Returns y (B, S, H, hd), fp32.
    """
    bsz, s, h, hd = x.shape
    ns = b_mat.shape[-1]
    xf, bf, cf, df = (t.astype(jnp.float32) for t in (x, b_mat, c_mat, decay))

    def step(state, inp):
        xt, bt, ct, dt = inp
        state = dt[..., None, None] * state + jnp.einsum(
            "bhd,bs->bhds", xt, bt)
        y = jnp.einsum("bhds,bs->bhd", state, ct)
        return state, y

    st0 = jnp.zeros((bsz, h, hd, ns), jnp.float32)
    xs = (xf.transpose(1, 0, 2, 3), bf.transpose(1, 0, 2),
          cf.transpose(1, 0, 2), df.transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, st0, xs)
    return ys.transpose(1, 0, 2, 3)
