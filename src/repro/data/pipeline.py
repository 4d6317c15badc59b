"""Streaming synthetic data sources (the paper's workloads are *online*).

Every stream is deterministic in (seed, node, epoch, index) — the property
the AMB engine relies on so that node i's s-th sample of epoch t is the same
regardless of how many samples other nodes processed (i.i.d. from Q, paper
§3).  Streams generate on demand; nothing is materialised up front.

  * LinRegStream — §6.1: x ~ N(0, I_d), y = x.w* + N(0, 1e-3).
  * LogRegStream — §6.2 stand-in: 10-class Gaussian mixture, 784-dim
    ("MNIST-like"; MNIST itself is not available offline — DESIGN.md §7).
  * LMTokenStream — token sequences from a fixed-transition synthetic
    grammar, for LM training examples (b).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class LinRegStream:
    dim: int
    seed: int = 0
    noise_var: float = 1e-3

    def w_star(self) -> Array:
        return jax.random.normal(jax.random.PRNGKey(self.seed ^ 0x5757),
                                 (self.dim,), jnp.float32)

    def batch(self, node: int, epoch: int, size: int,
              w_star: Optional[Array] = None):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), node), epoch)
        kx, kn = jax.random.split(key)
        x = jax.random.normal(kx, (size, self.dim), jnp.float32)
        ws = self.w_star() if w_star is None else w_star
        y = x @ ws + jnp.sqrt(self.noise_var) * jax.random.normal(
            kn, (size,), jnp.float32)
        return x, y


@dataclasses.dataclass(frozen=True)
class LogRegStream:
    dim: int = 784
    num_classes: int = 10
    seed: int = 0
    spread: float = 2.0

    def class_means(self) -> Array:
        return self.spread * jax.random.normal(
            jax.random.PRNGKey(self.seed ^ 0xC1A5), (self.num_classes, self.dim),
            jnp.float32) / jnp.sqrt(self.dim)

    def batch(self, node: int, epoch: int, size: int):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), node), epoch)
        ky, kx = jax.random.split(key)
        y = jax.random.randint(ky, (size,), 0, self.num_classes)
        x = self.class_means()[y] + jax.random.normal(
            kx, (size, self.dim), jnp.float32)
        return x, y


@dataclasses.dataclass(frozen=True)
class LMTokenStream:
    """Synthetic token grammar: order-1 Markov chain with a planted
    block-diagonal transition structure (learnable, non-trivial entropy)."""

    vocab_size: int
    seq_len: int
    seed: int = 0
    num_blocks: int = 16

    def _transition_logits(self, tok: Array) -> Array:
        """Row ``tok`` of the (v, v) transition table, drawn on demand: a
        published vocabulary (152k) would need v^2 floats (92 GB) up front."""
        v = self.vocab_size
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed ^ 0x70CE), tok)
        base = jax.random.normal(key, (v,), jnp.float32) * 0.5
        blk = v // self.num_blocks or 1
        same = (jnp.arange(v) // blk) == (tok // blk)
        return base + 2.0 * same

    def batch(self, node: int, epoch: int, size: int) -> dict:
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self.seed), node), epoch)

        def seq(k):
            k0, ks = jax.random.split(k)
            first = jax.random.randint(k0, (), 0, self.vocab_size)

            def step(tok, kk):
                nxt = jax.random.categorical(kk, self._transition_logits(tok))
                return nxt, nxt

            _, rest = jax.lax.scan(step, first,
                                   jax.random.split(ks, self.seq_len - 1))
            return jnp.concatenate([first[None], rest])

        toks = jax.vmap(seq)(jax.random.split(key, size))
        labels = jnp.concatenate(
            [toks[:, 1:], jnp.full((size, 1), -1, toks.dtype)], axis=1)
        return {"tokens": toks, "labels": labels}


def make_stream(kind: str, **kw):
    return {"linreg": LinRegStream, "logreg": LogRegStream,
            "lm": LMTokenStream}[kind](**kw)


def shard_batch(batch, mesh, batch_axes=("data",)):
    """Deprecated alias for :func:`repro.data.loader.put_batch`.

    The historical implementation issued one ``device_put`` per leaf;
    the loader's put commits the whole batch tree in a single call (the
    runtime batches the transfers).  Kept as a thin alias for existing
    callers — new code should import ``put_batch`` (or better, feed the
    session through an :class:`repro.data.loader.InputSource`).
    """
    from .loader import put_batch
    return put_batch(batch, mesh, batch_axes)
