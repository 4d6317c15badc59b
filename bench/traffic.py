"""Traffic of training cells, made from the traffic file and the seed.

Two parts, both owned by the benchmark so that no change to the program
moves them:

* :class:`TokenRows`, the token rows every epoch trains on.  Rows of
  ``seq_len + 1`` ids are drawn i.i.d. from the file's ``tokens`` law
  (``{"law": "zipf", "exponent": s}``: P(id) proportional to
  (id + 1)^-s, as word ranks in text are) by
  a generator seeded with (``--seed``, epoch); a row's first
  ``seq_len`` ids are the inputs and its last ``seq_len`` the targets.
  It has the program's input-source interface (``n_workers``,
  ``per_worker``, ``batch(epoch)``), so the program's prefetcher moves
  the host rows to the device as it would rows read from disk.
* :func:`batch_sizes`, the b_i(t) the straggler model and the Lemma-6
  budget give each epoch.  Worker i's reference batch takes
  T_i = zeta + Exp(lambda) (paper App. I.2; 1 for the deterministic
  model), with linear progress, and the budget is (1 + n / b) times the
  mean batch time (Lemma 6), so b_i(t) is the largest k <= b/n with
  k T_i / E[T] <= (1 + n / b) b / n: the clock's seconds cancel.  The
  draw of epoch t is ``jax.random.exponential`` of
  ``fold_in(PRNGKey(schedule_seed), 10000 + t)``, the epoch key of the
  program's session, so every run sees the same b(t).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPOCH_KEY_OFFSET = 10_000
BLOCK = 256        # epochs drawn per call: one compiled shape for any run


def token_cdf(tokens: dict, vocab: int) -> np.ndarray:
    """Cumulative probabilities of the ids 0 .. vocab - 1."""
    if tokens["law"] != "zipf":
        raise ValueError(f"unknown token law {tokens['law']!r}")
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(tokens["exponent"])
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def token_rows(cdf: np.ndarray, seed: int, epoch: int, rows: int,
               seq_len: int) -> tuple[np.ndarray, np.ndarray]:
    """(tokens, labels), each (rows, seq_len) int32, of one epoch."""
    rng = np.random.default_rng([seed % (1 << 64), epoch])
    ids = np.searchsorted(cdf, rng.random((rows, seq_len + 1)), side="right")
    ids = np.minimum(ids, len(cdf) - 1).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


class TokenRows:
    """The cell's global batch of every epoch, on the host."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.n_workers = traffic["workers"]
        self.per_worker = traffic["batch_per_worker"]
        self.seq_len = traffic["seq_len"]
        self.seed = seed
        self.cdf = token_cdf(traffic["tokens"], vocab)

    @property
    def global_batch(self) -> int:
        return self.n_workers * self.per_worker

    def rows(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        return token_rows(self.cdf, self.seed, epoch, self.global_batch,
                          self.seq_len)

    def batch(self, epoch: int) -> dict:
        tokens, labels = self.rows(epoch)
        return {"tokens": tokens, "labels": labels}


@functools.partial(jax.jit, static_argnums=2)
def _exponentials(seed, epochs, n: int):
    key = jax.random.PRNGKey(seed)
    return jax.vmap(lambda t: jax.random.exponential(
        jax.random.fold_in(key, EPOCH_KEY_OFFSET + t), (n,)))(epochs)


def _relative_times(clock: dict, n: int, epochs: np.ndarray) -> np.ndarray:
    """(epochs, n) reference-batch times over their mean."""
    if clock["straggler"] == "deterministic":
        return np.ones((len(epochs), n))
    if clock["straggler"] != "shifted_exp":
        raise ValueError(f"no schedule for straggler {clock['straggler']!r}")
    padded = np.zeros(-(-len(epochs) // BLOCK) * BLOCK, np.int32)
    padded[:len(epochs)] = epochs
    e = np.concatenate([
        np.asarray(_exponentials(clock["schedule_seed"],
                                 jnp.asarray(padded[i:i + BLOCK]), n))
        for i in range(0, len(padded), BLOCK)])[:len(epochs)].astype(np.float64)
    zeta, lam = clock["zeta"], clock["lam"]
    return (zeta + e / lam) / (zeta + 1.0 / lam)


def batch_sizes(traffic: dict, epochs) -> np.ndarray:
    """(len(epochs), n) int b_i(t) of the given absolute epochs."""
    clock = dict(traffic["clock"], schedule_seed=traffic["schedule_seed"])
    if clock.get("compute_time") is not None:
        raise ValueError("a pinned compute budget has no Lemma-6 schedule")
    n, per = traffic["workers"], traffic["batch_per_worker"]
    epochs = np.asarray(epochs, np.int64)
    rel = _relative_times(clock, n, epochs)
    budget = (1.0 + n / (n * per)) * per          # in mean gradient times
    k = np.arange(1, per + 1)
    return (k * rel[..., None] <= budget).sum(-1)
