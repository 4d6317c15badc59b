"""Plain float32 reference of the AMB epoch, independent of the program.

The model is Qwen2's dense decoder as published (arXiv:2407.10671,
``Qwen/Qwen2-1.5B`` config.json): RMSNorm (eps from the config), grouped-
query attention with QKV bias, rotate-half RoPE, causal softmax, SwiGLU,
a final RMSNorm and an output head.  One departure, which the program
shares: the output head is a separate ``unembed`` matrix, while the
published config ties it to the embedding (``tie_word_embeddings``).

Everything runs in float32 with ``precision=HIGHEST`` matrix products; the
control (``quant="e4m3"``) computes every matrix product from operands
rounded to float8 e4m3 with one scale per tensor, the next precision below
the bfloat16 the configuration states.

The AMB epoch (paper eq. 3, 6, 7): worker i keeps its first b_i(t)
sequences; exact consensus takes the gradient of the loss averaged over
every kept token at the weights as stored, z += g, w = w0 - z / (2 beta(t
+ 1)), and w is stored in the leaf's own type (bfloat16 for the
matrices, as the configuration states), rounded to nearest even by
``lax.reduce_precision``, which XLA keeps: a plain cast there and back
may be dropped as excess precision.  Memory: the per-layer weights are formed from (w0, z) inside a
rematerialised layer, and the output head runs one sequence at a time,
so a step holds w0, z, its gradient and one layer's activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .counts import head_dim

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
E4M3_MAX = 448.0


def beta(t, k: float, scale: float, mu: float):
    """Dual-averaging beta(t) = k + scale * sqrt(t / mu) (Lemma 8)."""
    return k + scale * jnp.sqrt(jnp.asarray(t, F32) / mu)


def _fq(x):
    """Round to float8 e4m3 with one per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec, a, b):
    return jnp.einsum(spec, _fq(a), _fq(b), precision=HIGHEST)


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _fq(a), _fq(b)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _mm_fp8_bwd(spec, res, g):
    # the backward products take e4m3 operands too: the cotangent is
    # rounded like any other operand
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     *res)
    return vjp(_fq(g))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(quant, spec, a, b):
    if quant:
        return _mm_fp8(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over (B, S, heads, hd) at positions 0..S-1."""
    hd, s = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, quant, x, p):
    """One decoder layer on (B, S, d) float32."""
    b, s, _ = x.shape
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    eps = cfg["rms_norm_eps"]
    a = _rms(x, p["ln1"], eps)
    q = (_mm(quant, "bsd,de->bse", a, p["wq"]) + p["bq"]).reshape(b, s, h, hd)
    k = (_mm(quant, "bsd,de->bse", a, p["wk"]) + p["bk"]).reshape(b, s, kv, hd)
    v = (_mm(quant, "bsd,de->bse", a, p["wv"]) + p["bv"]).reshape(b, s, kv, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    # query head j reads key/value head j // (h / kv)
    k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
    sc = _mm(quant, "bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = _mm(quant, "bhqk,bkhd->bqhd", pr, v).reshape(b, s, h * hd)
    x = x + _mm(quant, "bse,ed->bsd", o, p["wo"])
    a = _rms(x, p["ln2"], eps)
    g = jax.nn.silu(_mm(quant, "bsd,df->bsf", a, p["w_gate"]))
    u = _mm(quant, "bsd,df->bsf", a, p["w_up"])
    return x + _mm(quant, "bsf,fd->bsd", g * u, p["w_down"])


def _stored(w, dtype):
    """``w`` rounded to ``dtype``, in float32."""
    if jnp.dtype(dtype) == F32:
        return w
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(w, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _primal(w0, z, beta_t):
    """The weights a step runs on: w0 - z / (2 beta) as stored, in
    w0's type; the gradient passes the rounding unchanged."""
    w = w0.astype(F32) - z / (2.0 * beta_t)
    return w + jax.lax.stop_gradient(_stored(w, w0.dtype) - w)


def loss_at(cfg, quant, w0, z, beta_t, tokens, labels, seq_w):
    """Mean next-token NLL over the kept tokens at w = w0 - z/(2 beta).

    ``seq_w`` (B,) is 1 for a kept sequence and 0 for a masked one; a
    label < 0 is not a target.
    """
    x = _primal(w0["embed"], z["embed"], beta_t)[tokens]

    @jax.checkpoint
    def body(x, wz):
        w0l, zl = wz
        p = jax.tree.map(lambda a, c: _primal(a, c, beta_t), w0l, zl)
        p = {"ln1": p["ln1"], "ln2": p["ln2"], **p["attn"], **p["mlp"]}
        return _layer(cfg, quant, x, p), None

    x, _ = jax.lax.scan(body, x, (w0["blocks"], z["blocks"]))
    x = _rms(x, _primal(w0["final_norm"], z["final_norm"], beta_t),
             cfg["rms_norm_eps"])
    head = _primal(w0["unembed"], z["unembed"], beta_t)

    @jax.checkpoint
    def seq_nll(xl):
        xs, ls = xl
        logits = _mm(quant, "sd,dv->sv", xs, head)
        keep = ls >= 0
        lab = jnp.maximum(ls, 0)
        gold = jnp.take_along_axis(logits, lab[:, None], -1)[:, 0]
        nll = (jax.nn.logsumexp(logits, -1) - gold) * keep
        return nll.sum(), keep.sum().astype(F32)

    sums, counts = jax.lax.map(seq_nll, (x, labels))
    return jnp.sum(seq_w * sums) / jnp.maximum(jnp.sum(seq_w * counts), 1.0)


def seq_weights(b, n_workers: int, per_worker: int):
    """(n * per,) 0/1: worker i keeps the first b_i of its block."""
    slot = np.arange(n_workers * per_worker)
    return jnp.asarray((slot % per_worker) < np.asarray(b)[slot // per_worker],
                       F32)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
def exact_step(cfg_items, quant, w0, z, beta_t, tokens, labels, seq_w):
    """One exact-consensus epoch: (loss, z + g), z donated."""
    cfg = dict(cfg_items)
    loss, gz = jax.value_and_grad(
        lambda z: loss_at(cfg, quant, w0, z, beta_t, tokens, labels, seq_w))(z)
    return loss, jax.tree.map(lambda a, g: a - 2.0 * beta_t * g, z, gz)


def cfg_items(cfg: dict) -> tuple:
    """The numbers of a configuration file the reference reads, hashable."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "rms_norm_eps",
            "rope_theta", "num_hidden_layers")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


@jax.jit
def leaf_norms(tree):
    """(leaves,) float32 L2 norm of every leaf."""
    return jnp.stack([jnp.linalg.norm(x.astype(F32).reshape(-1))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def stored_change_norms(w0, z, beta_t):
    """(leaves,) norm of w0 - z / (2 beta) as stored, less w0."""
    return jnp.stack([
        jnp.linalg.norm((_primal(a, c, beta_t) - a.astype(F32)).reshape(-1))
        for a, c in zip(jax.tree.leaves(w0), jax.tree.leaves(z))])


def run_exact(cfg: dict, w0, batches, bs, steps: int, beta_kw: dict,
              quant=None):
    """Readings of ``steps`` exact-consensus epochs from w0.

    ``batches``: per epoch (tokens, labels) host arrays of the global
    batch; ``bs``: per epoch the (n,) b_i.  Returns the loss per step,
    the leaf norms of z after step 1 (the first gradient) and those of
    the stored weights' change from w0 that step ``steps + 1`` runs on.
    """
    items = cfg_items(cfg)
    n = len(np.asarray(bs[0]))
    gb = batches[0][0].shape[0]
    z = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), w0)
    losses, grad1 = [], None
    for t in range(steps):
        tok, lab = batches[t]
        sw = seq_weights(bs[t], n, gb // n)
        loss, z = exact_step(items, quant, w0, z, beta(t + 1, **beta_kw),
                             jnp.asarray(tok), jnp.asarray(lab), sw)
        losses.append(float(loss))
        if t == 0:
            grad1 = np.asarray(leaf_norms(z))
    param = stored_change_norms(w0, z, beta(steps + 1, **beta_kw))
    return {"loss": losses, "grad1": grad1, "param": np.asarray(param)}


def worst_gap(prog, ref, keep=None) -> float:
    """max over leaves of | |prog| - |ref| | over the larger
    of the reference's norm of that leaf and of the median leaf."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = np.median(ref)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap))


def loss_gap(prog, ref) -> float:
    """max over steps of |loss - ref| / |ref|."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def moving_leaves(ref_grad1) -> np.ndarray:
    """Leaves whose reference first gradient is above a thousandth of the
    median leaf's: the others move by round-off alone and are left out of
    the change."""
    g = np.asarray(ref_grad1, np.float64)
    return g >= 1e-3 * np.median(g)


def large_changes(ref_param) -> np.ndarray:
    """Leaves whose stored change in the reference is at least a tenth of
    the median leaf's.  Below that the change is a handful of elements
    that crossed a rounding boundary of their type, and one element more
    or fewer moves the leaf's norm by as much as the norm itself."""
    c = np.asarray(ref_param, np.float64)
    return c >= 0.1 * np.median(c)
