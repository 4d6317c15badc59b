"""Pluggable consensus strategies for the mesh AMB stack (paper §3).

The consensus phase of the paper's epoch update is an operator on the
per-worker message stack: ``(n, D) -> (n, D)``.  The train steps in
:mod:`repro.dist.amb` and :mod:`repro.dist.pipeline` are written against
the :class:`ConsensusStrategy` interface and stay agnostic to *how* the
workers agree:

  * :class:`ExactConsensus` — the r -> infinity / master-worker limit
    (eps = 0): every worker ends up holding the global mean.  On a mesh
    this lowers to one all-reduce over the worker axes.
  * :class:`GossipConsensus` — r synchronous rounds of Metropolis gossip
    over any :func:`repro.core.consensus.build_graph` topology.  For
    group-circulant graphs (ring over Z_n, torus over Z_rows x Z_cols —
    the TPU ICI shapes) each round decomposes into K neighbor taps:
    rolls of the worker dim (collective-permutes under SPMD) plus one
    fused K-way weighted combine
    (:func:`repro.kernels.gossip_combine.gossip_combine_pallas`).
    Non-decomposable graphs (star, Erdos-Renyi, the paper's Fig. 2 graph)
    fall back to the dense ``P @ m`` of :func:`repro.core.consensus.gossip`.
  * :class:`QuantizedGossipConsensus` — the same taps, but each round's
    wire message is the CHOCO-style stochastically-quantized *delta*
    against a public replica, exactly the numerics of
    :func:`repro.core.extensions.gossip_quantized` (8/4-bit), with the
    quantize and dequantize+combine halves fused by the Pallas kernels in
    :mod:`repro.kernels.gossip_combine`.  The uint8 level planes (2/byte
    at 4-bit) are what crosses the ICI — (32/bits)x more rounds per T_c
    byte budget.

:func:`make_strategy` builds the right strategy from an
:class:`repro.dist.amb.AMBConfig` plus the mesh (the torus shape defaults
to the physical worker-axis extents).

Elastic membership (worker churn) has two regimes.  Ring/torus fleets
**relayout**: the survivors are re-enumerated onto a smaller ring/torus
whose operator is circulant again, so every round stays on the
collective-permute + fused-combine fast path — including the uint8
quantized wire planes (:class:`SurvivorTaps`).  Non-circulant graphs
(and ``relayout=False``) fall back to the dense induced-subgraph
operator of :func:`masked_metropolis`.  A single survivor degenerates
to the identity; an all-inactive mask is rejected.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import consensus as cns
from ..kernels import ops as kops
from ..kernels.gossip_combine import ALIGN
from .sharding import worker_axes

Array = jax.Array


# ---------------------------------------------------------------------------
# Group-circulant tap decomposition
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Taps:
    """``(P @ m)[i] = sum_k weights[k] * m[i + offsets[k]]`` over Z_shape.

    ``shape`` is the cyclic-group factorization of the worker index —
    ``(n,)`` for a ring, ``(rows, cols)`` for a torus.  Implemented as
    ``roll(m, -offset)`` per tap, which lowers to a collective-permute
    when the rolled dims are mesh-sharded.
    """

    offsets: tuple            # tuple of int tuples, one per tap
    weights: np.ndarray       # (K,) float32, self tap first
    shape: tuple              # cyclic-group shape, prod(shape) == n

    @property
    def k(self) -> int:
        return len(self.offsets)

    def take(self, x: Array, i: int) -> Array:
        """The i-th tap's neighbor view: ``out[r] = x[r + offsets[i]]``."""
        return roll_by_offset(x, self, self.offsets[i])


def group_taps(p: np.ndarray, shape: Sequence[int]) -> Optional[Taps]:
    """Decompose a group-circulant P into neighbor taps, or None.

    Valid iff ``P[i, j]`` depends only on the elementwise difference
    ``coord(j) - coord(i)`` mod ``shape`` (true for Metropolis weights on
    any vertex-transitive graph laid out over the cyclic group — ring,
    torus).  Validated by reconstructing P; returns None on mismatch so
    callers can fall back to the dense operator.
    """
    shape = tuple(int(s) for s in shape)
    n = p.shape[0]
    if int(np.prod(shape)) != n:
        return None
    offsets, weights = [], []
    for j in range(n):
        if p[0, j] != 0.0:
            offsets.append(np.unravel_index(j, shape))
            weights.append(float(p[0, j]))
    # self tap first (offset all-zeros), if present
    order = sorted(range(len(offsets)),
                   key=lambda i: (any(offsets[i]), offsets[i]))
    offsets = [offsets[i] for i in order]
    weights = [weights[i] for i in order]
    # validate: rebuild P from the taps
    rebuilt = np.zeros_like(p)
    coords = np.stack(np.unravel_index(np.arange(n), shape), axis=1)
    for off, w in zip(offsets, weights):
        dest = np.ravel_multi_index(
            tuple((coords[:, a] + off[a]) % shape[a]
                  for a in range(len(shape))), shape)
        rebuilt[np.arange(n), dest] += w
    if not np.allclose(rebuilt, p, atol=1e-12):
        return None
    return Taps(offsets=tuple(tuple(int(o) for o in off) for off in offsets),
                weights=np.asarray(weights, np.float32), shape=shape)


def masked_metropolis(adj: np.ndarray, active, lazy: float) -> np.ndarray:
    """Metropolis weights on the subgraph induced by the ``active`` mask.

    Elastic membership (worker join/leave): edges touching an inactive
    worker are removed and the Metropolis degrees re-derived on the
    induced subgraph, so active workers re-weight their remaining
    neighbors instead of waiting on a departed one.  Inactive workers
    become identity rows (they neither send nor relay; their stale dual
    survives untouched until they rejoin).  The active subgraph must stay
    connected — a partitioned fleet cannot reach consensus.

    This is the *dense* membership operator — ``P @ m`` per round.  It
    remains the fallback for non-circulant graphs (and the
    ``relayout=False`` A/B baseline); ring/torus fleets normally take
    :func:`survivor_taps` instead, which reconnects the survivors on a
    fresh ring/torus (so non-adjacent failures never partition it) and
    keeps the collective-permute fast path.
    """
    active = np.asarray(active, dtype=bool)
    adj = np.asarray(adj, dtype=bool) & active[None, :] & active[:, None]
    n_act = int(active.sum())
    if n_act >= 2 and not cns.is_connected(adj[np.ix_(active, active)]):
        raise ValueError("active worker subgraph is disconnected; "
                         "consensus cannot mix across the partition")
    return cns.metropolis_weights(adj, lazy=lazy)


def roll_by_offset(x: Array, taps: Taps, off) -> Array:
    """``out[i] = x[i + off]`` over the taps' cyclic group (one tap)."""
    full = x.reshape(taps.shape + x.shape[1:])
    axes = tuple(range(len(taps.shape)))
    return jnp.roll(full, tuple(-o for o in off), axis=axes).reshape(x.shape)


# ---------------------------------------------------------------------------
# Survivor relayout (elastic membership phase 2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SurvivorTaps:
    """Tap decomposition of a *survivor-relayout* gossip operator.

    :func:`masked_metropolis` keeps the survivors on the physical graph's
    induced subgraph — which loses the group-circulant structure the roll
    taps need (and can even disconnect), forcing the dense ``P @ m``
    slow path whenever a worker is down.  Relayout instead re-enumerates
    the ``n_act`` survivors (by physical index) as ranks of a *fresh*
    ring / torus over ``Z_{n_act}``: the small operator is circulant
    again, so it tap-decomposes, and each survivor-rank offset becomes a
    small set of **physical** worker-axis rolls — rank r's tap-``i``
    neighbor sits ``delta = p_{r+o_i} - p_r (mod n)`` physical slots
    away, and survivors with equal ``delta`` share one roll.  ``take``
    therefore lowers to at most a handful of collective-permutes plus
    masked selects per tap, keeping churned fleets on the fast path (and
    on the uint8 wire planes: the rolls work on any dtype).

    Fields: ``offsets`` / ``weights`` / ``shape`` describe the small
    operator on survivor ranks (self tap first, ``prod(shape) ==
    n_act``); ``hops[i]`` is the physical realisation of tap i — a tuple
    of ``(delta, mask)`` pairs with disjoint (n,) bool masks selecting
    which physical rows read from ``delta`` slots ahead; ``active`` is
    the membership mask, ``n`` the full fleet size.  Inactive rows are
    identity rows (their stale dual survives until rejoin) — the
    strategies re-select them after the combine.
    """

    offsets: tuple            # survivor-rank offsets, self tap first
    weights: np.ndarray       # (K,) float32
    shape: tuple              # survivor group shape, prod == n_act
    hops: tuple               # per tap: ((delta, (n,) bool mask), ...)
    active: np.ndarray        # (n,) bool membership mask
    n: int                    # full fleet size

    @property
    def k(self) -> int:
        return len(self.offsets)

    def take(self, x: Array, i: int) -> Array:
        """Tap i's neighbor view on the *physical* axis.

        Row ``p`` of the result holds ``x[p + delta_p]`` for active rows
        (``delta_p`` from the rank relayout) and 0 for inactive rows —
        the hop masks are disjoint, so the masked rolls just sum.  Works
        for any dtype (fp32 payloads and uint8 wire planes alike).
        """
        if i == 0:
            return x
        out = None
        for delta, mask in self.hops[i]:
            m = jnp.asarray(mask).reshape((self.n,) + (1,) * (x.ndim - 1))
            rolled = jnp.roll(x, -delta, axis=0) if delta else x
            part = jnp.where(m, rolled, jnp.zeros((), x.dtype))
            out = part if out is None else out + part
        return out if out is not None else jnp.zeros_like(x)

    def dense(self) -> np.ndarray:
        """The (n, n) operator this realises (tests / spectral checks):
        the relayout P on the survivor block, identity rows elsewhere."""
        p = np.zeros((self.n, self.n))
        idx = np.arange(self.n)
        for w, hop in zip(self.weights, self.hops):
            for delta, mask in hop:
                rows = idx[mask]
                p[rows, (rows + delta) % self.n] += float(w)
        inact = ~np.asarray(self.active, bool)
        p[inact, idx[inact]] = 1.0
        return p


def survivor_taps(active, graph: str = "ring", lazy: float = 0.5
                  ) -> Optional[SurvivorTaps]:
    """Relayout the active set onto a fresh ring/torus; None if the tap
    form is unavailable (< 2 survivors, or a non-circulant relayout).

    The survivor count picks the relayout shape: a ring over the
    ``n_act`` survivors, or — when the original graph was a torus and
    ``n_act`` factors into a true 2-D torus — the most-square
    ``rows x cols`` torus.  The construction is validated by rebuilding
    the dense operator and comparing against the embedded small P.
    """
    act = np.asarray(active, dtype=bool)
    n = act.size
    surv = np.nonzero(act)[0]
    n_act = surv.size
    if n_act < 2:
        return None
    if graph == "torus":
        rows, cols = _default_torus(n_act)
        if rows >= 2 and cols >= 2:
            shape, adj = (rows, cols), cns.torus_graph(rows, cols)
        else:                       # prime / tiny survivor counts: ring
            shape, adj = (n_act,), cns.ring_graph(n_act)
    elif graph == "ring":
        shape, adj = (n_act,), cns.ring_graph(n_act)
    else:
        return None
    p_small = cns.metropolis_weights(adj, lazy=lazy)
    taps_small = group_taps(p_small, shape)
    if taps_small is None:
        return None
    coords = np.stack(np.unravel_index(np.arange(n_act), shape), axis=1)
    hops = []
    for off in taps_small.offsets:
        src_rank = np.ravel_multi_index(
            tuple((coords[:, a] + off[a]) % shape[a]
                  for a in range(len(shape))), shape)
        delta = (surv[src_rank] - surv) % n       # physical roll per rank
        tap_hops = []
        for d in sorted({int(x) for x in delta}):
            mask = np.zeros(n, dtype=bool)
            mask[surv[delta == d]] = True
            tap_hops.append((d, mask))
        hops.append(tuple(tap_hops))
    taps = SurvivorTaps(offsets=taps_small.offsets,
                        weights=taps_small.weights, shape=shape,
                        hops=tuple(hops), active=act.copy(), n=n)
    # validate: the physical realisation must equal the embedded small P
    emb = np.eye(n)
    emb[np.ix_(surv, surv)] = p_small
    if not np.allclose(taps.dense(), emb, atol=1e-12):
        return None
    return taps


def _mask_rows(out: Array, orig: Array, active) -> Array:
    """Re-select inactive workers' original rows (identity rows) after a
    survivor-tap combine; no-op for full-fleet operators."""
    if active is None:
        return out
    mask = jnp.asarray(np.asarray(active, bool)).reshape(
        (-1,) + (1,) * (out.ndim - 1))
    return jnp.where(mask, out, orig)


def _row_sharding(mesh) -> Optional[NamedSharding]:
    """Layout of a worker-row stack ``(n, ...)`` on ``mesh``: rows over
    the worker axes.  The routed kernels run per device under it."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P(worker_axes(mesh) or None))


# ---------------------------------------------------------------------------
# Strategy interface
# ---------------------------------------------------------------------------

class ConsensusStrategy:
    """Operator on the per-worker message stack: (n, D) -> (n, D).

    ``combine`` runs the whole consensus phase (all rounds).  ``key`` is
    only consumed by stochastic strategies (quantized gossip) and may be
    None otherwise.  ``wire_bytes_per_round`` is the per-worker payload a
    single round puts on the interconnect — what the multi-pod benchmarks
    report.
    """

    name: str = "base"

    def combine(self, msg: Array, key: Optional[Array] = None) -> Array:
        raise NotImplementedError

    def wire_bytes_per_round(self, d: int) -> int:
        raise NotImplementedError

    def __call__(self, msg: Array, key: Optional[Array] = None) -> Array:
        return self.combine(msg, key)


@dataclasses.dataclass(frozen=True)
class ExactConsensus(ConsensusStrategy):
    """eps = 0: every worker holds the global mean (one all-reduce)."""

    n: int
    name: str = dataclasses.field(default="exact", init=False)

    def combine(self, msg: Array, key: Optional[Array] = None) -> Array:
        return cns.exact_average(msg.astype(jnp.float32))

    def wire_bytes_per_round(self, d: int) -> int:
        return 4 * d          # fp32 all-reduce payload (ring: 2x in+out)


class _TapGossip(ConsensusStrategy):
    """Shared P/tap construction for the gossip strategies.

    Elastic membership: an ``active`` mask with >= 2 survivors on a
    ring/torus relays out via :func:`survivor_taps` (collective-permute
    fast path preserved; ``relayout=False`` forces the legacy dense
    :func:`masked_metropolis` operator for A/B benchmarking).  A single
    survivor degenerates to the identity (no permutes, no dense op);
    an all-inactive mask is rejected — there is no operator to build.
    """

    def __init__(self, n: int, rounds: int, graph: str = "ring",
                 lazy: float = 0.5, torus_shape: Optional[tuple] = None,
                 active: Optional[Sequence[bool]] = None,
                 relayout: bool = True, mesh=None):
        self.n = int(n)
        self.rows = _row_sharding(mesh)
        self.rounds = int(rounds)
        self.graph = graph
        self.lazy = float(lazy)
        self.relayout = bool(relayout)
        self.identity = False
        self.active = None if active is None or all(active) \
            else tuple(bool(a) for a in active)
        if n < 2:
            self.p, self.taps = np.ones((1, 1)), None
            return
        if graph == "torus":
            rows, cols = torus_shape or _default_torus(n)
            if rows * cols != n:
                raise ValueError(f"torus {rows}x{cols} != {n} workers")
            adj = cns.torus_graph(rows, cols)
            shape = (rows, cols)
        else:
            adj = cns.build_graph(graph, n)
            shape = (n,)
        if self.active is not None:
            if len(self.active) != n:
                raise ValueError(f"active mask has {len(self.active)} "
                                 f"entries for {n} workers")
            n_act = sum(self.active)
            if n_act == 0:
                raise ValueError("at least one worker must stay active; "
                                 "an all-inactive fleet has no consensus "
                                 "operator")
            if n_act == 1:
                # single survivor: consensus degenerates to the identity
                # — no permutes, no dense operator, dual untouched
                self.identity = True
                self.p, self.taps = np.eye(n), None
                return
            if self.relayout:
                self.taps = survivor_taps(self.active, graph, lazy)
                if self.taps is not None:
                    self.p = self.taps.dense()
                    return
            # dense fallback: masked Metropolis on the induced subgraph
            # (non-circulant graphs, or relayout explicitly disabled)
            self.p = masked_metropolis(adj, self.active, lazy)
            self.taps = None
        else:
            self.p = cns.metropolis_weights(adj, lazy=lazy)
            self.taps = group_taps(self.p, shape)

    def wire_bytes_per_round(self, d: int) -> int:
        k = self.taps.k if self.taps is not None else self.n
        return 4 * d * (k - 1)     # fp32 message to each neighbor


class GossipConsensus(_TapGossip):
    """r rounds of Metropolis gossip; tap-decomposed where possible.

    Per round (group-circulant graphs): one roll per neighbor tap — a
    collective-permute under SPMD — and one fused K-way weighted combine
    on TPU.  Numerically identical to
    ``repro.core.consensus.gossip(m, P, rounds)``.
    """

    name = "gossip"

    def combine(self, msg: Array, key: Optional[Array] = None) -> Array:
        m = msg.astype(jnp.float32)
        if self.n < 2 or self.rounds < 1 or self.identity:
            return m
        if self.taps is None:        # dense fallback (non-circulant graph)
            return cns.gossip(m, jnp.asarray(self.p, jnp.float32),
                              self.rounds)
        w = jnp.asarray(self.taps.weights)
        # whole (8, 128) f32 tiles per row, once: the combine kernel then
        # reads every round's neighbour views in place
        d = m.shape[1]
        pad = (-d) % ALIGN

        def one_round(_, cur):
            return kops.gossip_combine(
                [self.taps.take(cur, i) for i in range(self.taps.k)], w,
                sharding=self.rows)

        out = jax.lax.fori_loop(0, self.rounds, one_round,
                                jnp.pad(m, ((0, 0), (0, pad))))[:, :d]
        # survivor relayout: inactive workers keep their rows (identity);
        # no active row ever reads an inactive one, so one final select
        # equals the dense masked operator's per-round identity rows
        return _mask_rows(out, m, getattr(self.taps, "active", None))


class QuantizedGossipConsensus(_TapGossip):
    """Delta-compressed gossip: ``repro.core.extensions.gossip_quantized``
    laid out along the mesh worker axes.

    Every worker keeps a public replica ``h`` of its own value and one
    running replica per neighbor tap; each round it stochastically
    quantizes ``m - h`` onto a per-worker uniform grid (``bits`` bits),
    sends only the uint8 level plane plus two grid scalars, and combines
    ``m <- P_ii m + sum_k P_ik hnbr_k`` — the self term stays exact, the
    delta magnitude (hence injected noise) decays with consensus.  Given
    the same per-round uniform draws this reproduces ``gossip_quantized``
    exactly; the rounds budget is scaled by the caller ((32/bits)x per
    T_c).  Requires a PRNG ``key``.
    """

    name = "gossip_q"

    def __init__(self, n: int, rounds: int, bits: int = 8,
                 graph: str = "ring", lazy: float = 0.5,
                 torus_shape: Optional[tuple] = None,
                 active: Optional[Sequence[bool]] = None,
                 relayout: bool = True, mesh=None):
        super().__init__(n, rounds, graph, lazy, torus_shape, active,
                         relayout, mesh)
        if bits not in (4, 8):
            raise ValueError("bits must be 4 or 8 (uint8 wire container)")
        self.bits = int(bits)
        self.name = f"gossip_q{bits}"

    def wire_bytes_per_round(self, d: int) -> int:
        # uint8 level container; 4-bit packs two levels per byte (the
        # per-tap payload actually put on the wire by _pack/_unpack), plus
        # the two f32 grid scalars per neighbor message.
        k = self.taps.k if self.taps is not None else self.n
        per_msg = (-(-d // 2) if self.bits == 4 else d) + 8
        return per_msg * (k - 1)

    def _pack(self, lvl: Array) -> Array:
        """4-bit wire format: two levels per byte (lossless)."""
        if self.bits != 4:
            return lvl
        n, d = lvl.shape
        if d % 2:
            lvl = jnp.pad(lvl, ((0, 0), (0, 1)))
        return lvl[:, ::2] | (lvl[:, 1::2] << 4)

    def _unpack(self, packed: Array, d: int) -> Array:
        if self.bits != 4:
            return packed
        both = jnp.stack([packed & 0xF, packed >> 4], axis=-1)
        return both.reshape(both.shape[0], -1)[:, :d]

    def combine(self, msg: Array, key: Optional[Array] = None) -> Array:
        if key is None:
            raise ValueError("QuantizedGossipConsensus needs a PRNG key")
        m = msg.astype(jnp.float32)
        if self.n < 2 or self.rounds < 1 or self.identity:
            return m
        # the fused path needs the self tap first (w[0] multiplies m)
        if self.taps is None or any(self.taps.offsets[0]):
            from ..core.extensions import gossip_quantized
            return gossip_quantized(m, jnp.asarray(self.p, jnp.float32),
                                    self.rounds, self.bits, key)
        taps = self.taps
        levels = float(2 ** self.bits - 1)
        d = m.shape[1]
        w = jnp.asarray(taps.weights)
        km1 = taps.k - 1

        def one_round(k_round, carry):
            cur, h, hnbr = carry
            # -- send half: stochastic-quantize the delta, update replica
            diff = cur - h
            lo = diff.min(axis=-1, keepdims=True)
            hi = diff.max(axis=-1, keepdims=True)
            scale = jnp.maximum(hi - lo, 1e-12) / levels
            # partitionable threefry: the rounding plane is drawn shard-
            # locally; the sequential impl's u32 resharding costs more
            # wire bytes per round than the u8 level planes themselves
            # (must match core.extensions.quantize_unbiased's draws)
            with jax.threefry_partitionable(True):
                rnd = jax.random.uniform(jax.random.fold_in(key, k_round),
                                         cur.shape)
            lvl, h_new = kops.stochastic_quantize(cur, h, rnd, lo, scale,
                                                  levels, sharding=self.rows)
            # -- the wire: rolled (nibble-packed) level planes + scalars.
            # The barriers pin the collective-permute to the uint8 plane:
            # without them XLA hoists the u8->f32 dequant (and the 4-bit
            # unpack) across the roll, putting fp32 on the interconnect
            # and defeating the (32/bits)x byte saving (see the
            # multipod_2x16x16 section of BENCH_dist.json).
            wire = jax.lax.optimization_barrier(self._pack(lvl))
            lvl_r = jnp.stack([
                self._unpack(
                    jax.lax.optimization_barrier(taps.take(wire, j)), d)
                for j in range(1, taps.k)])
            lo_r = jnp.stack([taps.take(lo, j) for j in range(1, taps.k)])
            sc_r = jnp.stack([taps.take(scale, j)
                              for j in range(1, taps.k)])
            # -- receive half: fused dequantize + replica update + combine
            out, hnbr_new = kops.quantized_combine(
                cur, hnbr, lvl_r, lo_r, sc_r, w, sharding=self.rows)
            return out, h_new, hnbr_new

        h0 = jnp.zeros_like(m)
        hnbr0 = jnp.zeros((km1,) + m.shape, jnp.float32)
        out, _, _ = jax.lax.fori_loop(0, self.rounds, one_round,
                                      (m, h0, hnbr0))
        # survivor relayout: restore inactive workers' original rows —
        # their replicas only ever accumulate the taps' zero fill, and
        # no active row reads them, so the select is exact
        return _mask_rows(out, m, getattr(self.taps, "active", None))


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

def _default_torus(n: int) -> tuple:
    rows = int(np.sqrt(n))
    while n % rows:
        rows -= 1
    return rows, n // rows


def torus_shape_for_mesh(mesh) -> Optional[tuple]:
    """The physical worker-axis extents as the torus (rows, cols).

    A ("pod", "data", "model") mesh gossips over pod x data, so the
    natural torus is (pod_extent, data_extent) — each roll then permutes
    along exactly one physical mesh axis.  Single-worker-axis meshes fall
    back to the most-square factorization.
    """
    waxes = worker_axes(mesh)
    if len(waxes) == 2:
        return int(mesh.shape[waxes[0]]), int(mesh.shape[waxes[1]])
    return None


CONSENSUS_CHOICES = ("exact", "gossip", "gossip_q8", "gossip_q4")


def make_strategy(name: str, n: int, *, rounds: int = 5,
                  graph: str = "ring", lazy: float = 0.5,
                  torus_shape: Optional[tuple] = None,
                  active: Optional[Sequence[bool]] = None,
                  relayout: bool = True, mesh=None) -> ConsensusStrategy:
    """Build a strategy from the AMBConfig vocabulary.

    ``name`` in {"exact", "gossip", "gossip_q8", "gossip_q4"}.  Quantized
    strategies get (32/bits)x the rounds — same T_c byte budget.  An
    ``active`` worker mask (elastic membership) rebuilds the gossip
    operator: ring/torus fleets relayout the survivors onto a smaller
    ring/torus whose taps stay on the collective-permute fast path
    (:func:`survivor_taps`; ``relayout=False`` forces the legacy dense
    :func:`masked_metropolis` operator), non-circulant graphs take the
    dense induced-subgraph operator.  Exact consensus needs no rebuild —
    a departed worker's zero-weighted message (b_i = 0) already drops
    out of the eq.-6 average.  ``mesh``: the mesh whose worker axes hold
    the message rows; the gossip kernels run per device on it.
    """
    if name == "exact":
        return ExactConsensus(n)
    if name == "gossip":
        return GossipConsensus(n, rounds, graph, lazy, torus_shape, active,
                               relayout, mesh)
    if name in ("gossip_q8", "gossip_q4"):
        bits = int(name[-1])
        return QuantizedGossipConsensus(n, rounds * 32 // bits, bits,
                                        graph, lazy, torus_shape, active,
                                        relayout, mesh)
    raise ValueError(f"unknown consensus strategy {name!r}; "
                     f"choose from {CONSENSUS_CHOICES}")
