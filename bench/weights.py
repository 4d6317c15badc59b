"""Weights made from the seed, on the device, in one jitted call.

The tree has the layout the program's dense decoder reads (stacked
``blocks`` with a leading layer axis, an untied ``embed`` and
``unembed``).  The reference regenerates the same values from the same
seed with the same call, so it takes no weights from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .counts import head_dim


def shapes(cfg: dict) -> dict:
    """name path -> (shape, dtype) of every leaf, in the program's tree."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff, v, L = cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    bf, f32 = jnp.dtype(cfg["torch_dtype"]), jnp.float32
    return {
        "embed": ((v, d), bf),
        "unembed": ((d, v), bf),
        "final_norm": ((d,), f32),
        "blocks": {
            "ln1": ((L, d), f32),
            "ln2": ((L, d), f32),
            "attn": {"wq": ((L, d, h * hd), bf), "wk": ((L, d, kv * hd), bf),
                     "wv": ((L, d, kv * hd), bf), "wo": ((L, h * hd, d), bf),
                     "bq": ((L, h * hd), bf), "bk": ((L, kv * hd), bf),
                     "bv": ((L, kv * hd), bf)},
            "mlp": {"w_gate": ((L, d, ff), bf), "w_up": ((L, d, ff), bf),
                    "w_down": ((L, ff, d), bf)},
        },
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def abstract(cfg: dict):
    """ShapeDtypeStructs of the tree."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(*s), shapes(cfg),
                        is_leaf=_is_spec)


def _leaf(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    if name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name.startswith("b"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:
        scale = 1.0 if name == "embed" else shape[-2] ** -0.5
        x = scale * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                                jnp.float32)
    return x.astype(dtype)


def make(cfg: dict, seed: int, shardings=None):
    """The weights of ``seed`` on the device, in one jitted call;
    ``shardings`` (a tree of the same structure) places them."""
    spec = shapes(cfg)
    paths = [("/".join(str(k.key) for k in p), s) for p, s in
             jax.tree_util.tree_flatten_with_path(spec, is_leaf=_is_spec)[0]]
    treedef = jax.tree.structure(spec, is_leaf=_is_spec)

    def build(key):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), path, *s)
            for i, (path, s) in enumerate(paths)])

    fn = jax.jit(build, out_shardings=shardings)
    return fn(jax.random.PRNGKey(seed))
