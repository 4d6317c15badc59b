"""Distributed AMB train steps on real device meshes (paper §3 -> SPMD).

This module is the thin top of a three-layer stack:

  * :mod:`repro.dist.consensus` — pluggable :class:`ConsensusStrategy`
    implementations (exact all-reduce, tap-decomposed ring/torus gossip,
    CHOCO-style 8/4-bit quantized gossip) that agree the per-worker
    message stack ``(n, D) -> (n, D)``.
  * :mod:`repro.dist.pipeline` — the staleness-1 *pipelined* epoch
    (``core.extensions.run_amb_pipelined`` semantics): round-r gossip of
    epoch t overlaps the forward/backward of epoch t+1.
  * this module — the sequential train steps, sharing the variable-
    minibatch masking (eq. 3) and the eq.-6 weighted normalisation:

      - :func:`make_train_step` — *exact consensus* (eps = 0, the
        master/worker limit): one global weighted-loss backward pass whose
        gradient is exactly ``sum_i b_i g_i / sum_i b_i``, updated by any
        :class:`repro.optim.Optimizer`.
      - :func:`make_gossip_train_step` — *decentralized consensus*
        (Lemma 1 regime): every worker keeps its own dual replica
        ``z_i``, computes its local masked gradient at its own primal
        ``w_i = prox(z_i)``, packs the messages ``n b_i (z_i + g_i)``
        with the scalar ``n b_i`` alongside (so the eq.-6 normaliser is
        itself agreed by consensus), and hands the stack to whatever
        :class:`ConsensusStrategy` the :class:`AMBConfig` names.

Workers are the product of the non-"model" mesh axes, so a multi-pod
("pod", "data", "model") mesh gossips jointly across pod x data; with
``graph="torus"`` the gossip taps follow the physical (pod, data) extents
— each roll permutes along exactly one mesh axis.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import consensus as cns
from ..core.dual_averaging import BetaSchedule
from .consensus import (ConsensusStrategy, GossipConsensus, make_strategy,
                        torus_shape_for_mesh)
from .params import tree_shardings
from .redundancy import CodedAssignment, epoch_weights
from .sharding import worker_axes

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class AMBConfig:
    """Static AMB step configuration (consensus + dual-averaging knobs)."""

    consensus: str = "exact"          # exact | gossip | gossip_q8 | gossip_q4
    gossip_rounds: int = 5            # r (fp32-equivalent budget; quantized
                                      # strategies get (32/bits)x this)
    graph: str = "ring"               # worker communication graph
    torus_shape: Optional[tuple] = None   # (rows, cols); default from mesh
    lazy: float = 0.5                 # lazy-Metropolis mixing (PSD P)
    beta: BetaSchedule = BetaSchedule()   # gossip-path dual averaging
    radius: Optional[float] = None
    seed: int = 0                     # quantized-gossip PRNG stream
    active: Optional[tuple] = None    # elastic worker mask (None = all);
                                      # gossip taps rebuild on the induced
                                      # active subgraph
    noise_stats: bool = False         # emit grad_sq_norm / grad_var metrics
                                      # (repro.control telemetry); opt-in so
                                      # default step graphs stay byte-
                                      # identical
    redundancy: int = 1               # rho: coded data replication factor
                                      # (repro.dist.redundancy; 1 = uncoded,
                                      # bit-exact legacy path)
    relayout: bool = True             # elastic membership phase 2: re-lay
                                      # the survivors onto a smaller ring/
                                      # torus (taps stay collective-permute)
                                      # instead of the dense masked P @ m


def strategy_from_config(amb: AMBConfig, mesh) -> ConsensusStrategy:
    """The configured :class:`ConsensusStrategy` for this mesh's workers."""
    n = num_workers(mesh)
    tshape = amb.torus_shape
    if tshape is None and amb.graph == "torus":
        tshape = torus_shape_for_mesh(mesh)
    return make_strategy(amb.consensus, n, rounds=amb.gossip_rounds,
                         graph=amb.graph, lazy=amb.lazy, torus_shape=tshape,
                         active=amb.active, relayout=amb.relayout,
                         mesh=mesh)


def assignment_from_config(amb: AMBConfig, n: int
                           ) -> Optional[CodedAssignment]:
    """The coded data placement, or None for the uncoded bit-exact path."""
    if amb.redundancy <= 1:
        return None
    return CodedAssignment(n, amb.redundancy)


# ---------------------------------------------------------------------------
# Workers and variable-minibatch masking
# ---------------------------------------------------------------------------

def num_workers(mesh) -> int:
    """Workers = product of the non-"model" axis extents (pod x data)."""
    return int(np.prod([int(mesh.shape[a]) for a in worker_axes(mesh)],
                       dtype=np.int64)) if worker_axes(mesh) else 1


def seq_weights_from_b(b: Array, global_batch: int, n_workers: int) -> Array:
    """Per-sequence 0/1 inclusion weights from per-worker counts b_i(t).

    The global batch is laid out in ``n_workers`` contiguous blocks of
    ``global_batch // n_workers`` sequences; worker i's first ``b_i`` slots
    are included (paper eq. 3 with static shapes).  Returns (global_batch,)
    float32.
    """
    if global_batch % n_workers:
        raise ValueError(f"global_batch {global_batch} not divisible by "
                         f"{n_workers} workers")
    per = global_batch // n_workers
    idx = jnp.arange(global_batch)
    return ((idx % per) < b[idx // per]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Ring gossip along the worker dim (compatibility wrappers)
# ---------------------------------------------------------------------------

def ring_p(n: int, lazy: float = 0.5) -> np.ndarray:
    """Lazy-Metropolis ring weights (the worker-axis P; circulant)."""
    if n < 2:
        return np.ones((1, 1))
    return cns.metropolis_weights(cns.ring_graph(n), lazy=lazy)


def ring_gossip(flat: Array, rounds: int, lazy: float = 0.5) -> Array:
    """``rounds`` rounds of ring-Metropolis gossip over dim 0 of (n, D).

    Kept as the historical entry point; now a thin wrapper over
    :class:`repro.dist.consensus.GossipConsensus` with ``graph="ring"`` —
    identical taps, identical Pallas combine, identical numerics.
    """
    return GossipConsensus(flat.shape[0], rounds, "ring", lazy).combine(flat)


# ---------------------------------------------------------------------------
# Message pack / unpack (shared with repro.dist.pipeline)
# ---------------------------------------------------------------------------

def pack_messages(z, grads, nb: Array, n: int) -> Array:
    """Stack ``n b_i (z_i + g_i)`` rows with the scalar ``n b_i`` appended.

    z / grads: trees of (n, *param) leaves; nb: (n,).  Returns (n, D+1)
    fp32 — the consensus payload whose last column carries the eq.-6
    normaliser through the same consensus operator.
    """
    leaves = jax.tree.leaves(z)
    gleaves = jax.tree.leaves(grads)
    return jnp.concatenate(
        [(nb.reshape((n,) + (1,) * (zl.ndim - 1))
          * (zl + gl.astype(jnp.float32))).reshape(n, -1)
         for zl, gl in zip(leaves, gleaves)] + [nb.reshape(n, 1)], axis=1)


def flatten_dual(z, n: int) -> Array:
    """(n, W) row-stack of a dual tree — :func:`pack_messages`' leaf
    layout, without the weight column.  The single source of truth for
    that layout, shared with :mod:`repro.dist.async_epochs`' snapshot
    increments."""
    return jnp.concatenate([zl.reshape(n, -1) for zl in jax.tree.leaves(z)],
                           axis=1)


def unflatten_dual(flat: Array, z, n: int):
    """Invert :func:`flatten_dual` onto the structure of ``z``."""
    leaves, treedef = jax.tree.flatten(z)
    sizes = [int(np.prod(l.shape[1:], dtype=np.int64)) for l in leaves]
    splits = np.cumsum(sizes)[:-1].tolist()
    return jax.tree.unflatten(treedef, [
        part.reshape((n,) + l.shape[1:])
        for part, l in zip(jnp.split(flat, splits, axis=1), leaves)])


def unpack_duals(out: Array, z, n: int):
    """Invert :func:`pack_messages` on a consensus output.

    Normalises by the agreed scalar column; a worker whose gossip
    neighborhood processed no samples (scalar ~ 0, e.g. a straggler-wiped
    epoch) keeps its dual unchanged — matching the exact path, where a
    zero gradient leaves z alone.
    """
    denom = jnp.maximum(out[:, -1:], 1e-12)
    zcat = flatten_dual(z, n)
    zflat = jnp.where(out[:, -1:] > 1e-6, out[:, :-1] / denom, zcat)
    return unflatten_dual(zflat, z, n)


# ---------------------------------------------------------------------------
# Exact-consensus train step (eps = 0)
# ---------------------------------------------------------------------------

def make_train_step(cfg, opt, mesh, amb: AMBConfig = AMBConfig()):
    """step(params, opt_state, batch, b) -> (params, opt_state, metrics).

    ``batch`` is the global batch (leading dim sharded over the worker
    axes); ``b`` the (n_workers,) per-worker minibatch sizes for this
    epoch.  The weighted loss's gradient equals the paper's eq.-6 global
    gradient, and ``opt`` applies the update (dual averaging: z += g,
    w = prox(z, beta)) on the parameters' :func:`tree_shardings` layout.
    Under coded redundancy (``amb.redundancy > 1``) the 0/1 eq.-3
    weights become the ``1/copies`` decode weights of
    :mod:`repro.dist.redundancy` and ``global_batch`` counts *distinct*
    covered samples.
    """
    from ..models import lm_loss     # deferred: models imports dist.sharding
    n = num_workers(mesh)
    assignment = assignment_from_config(amb, n)

    def step(params, opt_state, batch, b):
        gb = jax.tree.leaves(batch)[0].shape[0]
        per = gb // n
        if assignment is None:
            sw = seq_weights_from_b(b, gb, n)
            gbatch = jnp.sum(jnp.minimum(b, per))
        else:
            sw2, bw = epoch_weights(b, n, per, assignment)
            sw, gbatch = sw2.reshape(gb), bw.sum()

        def loss_fn(p):
            total, m = lm_loss(p, cfg, batch, sw)
            return total, m

        # stable step-phase names for the profiler's op metadata
        with jax.named_scope("amb.fwd_bwd"):
            (_, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        with jax.named_scope("amb.dual_update"):
            new_params, new_state = opt.apply(
                grads, opt_state, params,
                shardings=tree_shardings(params, mesh))
        metrics = {"loss": m["loss"], "aux": m["aux"], "ntok": m["ntok"],
                   "global_batch": gbatch}
        return new_params, new_state, metrics

    return step


# ---------------------------------------------------------------------------
# Decentralized gossip train step (per-worker dual replicas)
# ---------------------------------------------------------------------------

def _prox_leaf(z_leaf, w0_leaf, beta_t, radius: Optional[float]):
    """Paper eq.-7 prox with h(w) = ||w - w0||^2 (f32 math, w0 dtype out)."""
    w0f = w0_leaf.astype(jnp.float32)
    w = w0f - z_leaf / (2.0 * beta_t)
    if radius is not None:
        delta = w - w0f
        nrm = jnp.linalg.norm(delta.reshape(-1))
        w = w0f + delta * jnp.minimum(1.0, radius / jnp.maximum(nrm, 1e-30))
    return w.astype(w0_leaf.dtype)


def _local_grads(cfg, state, batch, sw, beta_t, radius, n, per):
    """vmapped per-worker masked gradients at each worker's own primal.

    ``sw``: (n, per) per-sequence weights — the 0/1 eq.-3 mask, or the
    fractional ``1/copies`` decode weights under coded redundancy
    (:func:`repro.dist.redundancy.epoch_weights`).  Returns (grads tree
    of (n, *param), losses (n,)).
    """
    from ..models import lm_loss     # deferred: models imports dist.sharding
    local = jax.tree.map(
        lambda x: x.reshape((n, per) + x.shape[1:]), batch)

    def local_grad(z_i, batch_i, sw_i):
        p_i = jax.tree.map(
            lambda w0l, zl: _prox_leaf(zl, w0l, beta_t, radius),
            state["w0"], z_i)

        def loss_fn(p):
            total, m = lm_loss(p, cfg, batch_i, sw_i)
            return total, m["loss"]

        (_, loss_i), g_i = jax.value_and_grad(loss_fn, has_aux=True)(p_i)
        return g_i, loss_i

    return jax.vmap(local_grad)(state["z"], local, sw)


def grad_noise_stats(grads, bw: Array) -> dict:
    """Cheap minibatch gradient-noise signals from per-worker gradients.

    ``grads``: tree of (n, *param) per-worker mean gradients; ``bw``: the
    (n,) effective per-worker sample counts (0 for masked workers, whose
    weight then vanishes).  Returns two scalars for
    :mod:`repro.control.telemetry`:

      * ``grad_sq_norm`` — ``||gbar||^2`` of the eq.-6 b-weighted mean
        gradient (biased up by ``tr(Sigma)/B``; telemetry corrects);
      * ``grad_var`` — the b-weighted between-worker dispersion
        ``sum_i (b_i/B) ||g_i - gbar||^2``, expectation
        ``tr(Sigma) (n-1)/B`` — a noise estimate that costs two scalar
        reductions, no extra backward pass.
    """
    w = bw / jnp.maximum(bw.sum(), 1.0)
    sq = jnp.float32(0.0)
    var = jnp.float32(0.0)
    for g in jax.tree.leaves(grads):
        flat = g.astype(jnp.float32).reshape(g.shape[0], -1)
        gbar = jnp.tensordot(w, flat, axes=(0, 0))
        sq = sq + jnp.sum(gbar * gbar)
        var = var + jnp.sum(w[:, None] * (flat - gbar) ** 2)
    return {"grad_sq_norm": sq, "grad_var": var}


def _init_gossip_state(params, mesh, n, waxes):
    """Per-worker dual replicas sharded along the worker axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    zshard = NamedSharding(mesh, P(waxes if n > 1 else None))

    def zeros(p):
        # made in place on every device: a (n, *param) f32 stack built on
        # one device first would need n times that worker's share there
        return jnp.zeros((n,) + p.shape, jnp.float32, device=zshard)

    return {"z": jax.tree.map(zeros, params),
            "w0": params,            # prox anchor w(1), original dtypes
            "t": jnp.zeros((), jnp.int32)}


def make_gossip_train_step(cfg, mesh, amb: AMBConfig):
    """Returns (init_state, step) for the decentralized AMB protocol.

    State: ``z`` — per-worker dual replicas, each leaf (n_workers, *param);
    ``w0`` — the shared init (prox anchor, paper eq. 2); ``t`` — epoch
    count.  step(state, batch, b) -> (state, metrics).  The consensus
    phase is whatever :class:`ConsensusStrategy` ``amb`` names (exact
    average, ring/torus gossip, quantized gossip).
    """
    n = num_workers(mesh)
    waxes = worker_axes(mesh)
    beta, radius = amb.beta, amb.radius
    strategy = strategy_from_config(amb, mesh)
    assignment = assignment_from_config(amb, n)
    qkey = jax.random.PRNGKey(amb.seed)

    def init_state(params):
        return _init_gossip_state(params, mesh, n, waxes)

    def step(state, batch, b):
        gb = jax.tree.leaves(batch)[0].shape[0]
        per = gb // n
        t = state["t"]
        beta_t = beta(t.astype(jnp.float32) + 1.0)   # beta used for w(t)
        sw, bw = epoch_weights(b, n, per, assignment)
        grads, losses = _local_grads(cfg, state, batch, sw, beta_t, radius,
                                     n, per)

        msg = pack_messages(state["z"], grads, n * bw, n)
        out = strategy.combine(msg, key=jax.random.fold_in(qkey, t))
        z_new = unpack_duals(out, state["z"], n)

        bsum = jnp.maximum(bw.sum(), 1.0)
        metrics = {"loss": jnp.sum(bw * losses) / bsum,
                   "global_batch": bw.sum(),
                   "beta": beta(t.astype(jnp.float32) + 2.0)}
        if amb.noise_stats:
            metrics.update(grad_noise_stats(grads, bw))
        return {"z": z_new, "w0": state["w0"], "t": t + 1}, metrics

    return init_state, step


def gossip_primal(state, amb: AMBConfig):
    """Node-averaged primal w̄(t) from a gossip-step state (checkpointing /
    eval): the same prox the train step applies, on the worker-mean dual.

    Under an elastic ``amb.active`` mask only the active workers' dual
    replicas are averaged — a departed worker's replica is frozen at its
    leave-time value (identity gossip row) and would otherwise bias the
    checkpoint away from the active set's consensus iterate.
    """
    t = state["t"].astype(jnp.float32)
    beta_t = amb.beta(t + 1.0)
    if amb.active is None:
        zbar = lambda z: z.mean(0)
    else:
        w = np.asarray(amb.active, np.float32)
        w = jnp.asarray(w / w.sum())

        def zbar(z):
            return jnp.tensordot(w, z, axes=(0, 0))

    return jax.tree.map(
        lambda w0, z: _prox_leaf(zbar(z), w0, beta_t, amb.radius),
        state["w0"], state["z"])
