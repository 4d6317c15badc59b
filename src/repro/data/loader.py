"""The session's data plane: pluggable input sources + background prefetch.

AMB's contract is that the compute budget T — not input availability —
bounds per-epoch work (paper §3: a worker computes gradients of "as many
samples as possible" in fixed time).  When the host builds the batch
*inside* the step loop, the input path inflates the measured step time,
the :class:`repro.api.clock.MeasuredClock` EMA absorbs it, and b_i(t)
shrinks — the training system becomes its own straggler.  This module
moves the host input path off the step-critical path:

  * :class:`InputSource` — the pluggable source contract: one method,
    ``batch(epoch) -> pytree`` with leading dim ``global_batch`` laid out
    in ``n_workers`` contiguous per-worker blocks (the layout
    :func:`repro.dist.amb.seq_weights_from_b` masks).  Sources
    **over-provision**: b_i(t) is decided only at step time (the clock
    draws the epoch's per-gradient times against the deadline T), so a
    source always yields the per-worker *cap* and the eq.-3 sequence
    weights slice each worker's first b_i samples in-graph.  Variable
    minibatches therefore cost the data plane nothing.
  * :class:`StreamSource` — wraps the deterministic synthetic streams
    (:class:`~repro.data.pipeline.LinRegStream` /
    :class:`~repro.data.pipeline.LogRegStream` /
    :class:`~repro.data.pipeline.LMTokenStream`), drawing worker i's
    block from stream node index i — distinct per-worker shards, i.i.d.
    across the fleet (the pre-loader drivers fed every worker node 0's
    stream: all workers trained on identical data).
  * :class:`SyntheticSource` — an on-device source: uniform tokens
    generated by a jitted sampler directly into the mesh layout, so the
    prefetch ``device_put`` is an identity.  The zero-host-cost baseline
    the dataplane benchmarks compare against.
  * :func:`put_batch` — one ``jax.device_put`` call over the whole batch
    tree (transfers are batched by the runtime), replacing the old
    per-leaf put loop in ``shard_batch``.
  * :class:`Prefetcher` — a background thread that keeps ``depth``
    (default 2: double buffering) batches device-resident ahead of the
    consumer: build on the host, ``put_batch`` into the session's
    ``NamedSharding`` layout, park in a bounded queue.  The consumer
    (``AMBSession.run``) overlaps epoch t's device step with the host
    build + transfer of epoch t+1's batch.

Nothing here imports the session; :class:`repro.api.AMBSession` wires a
``Prefetcher`` around any source via ``session.run(...)``.
"""
from __future__ import annotations

import itertools
import queue
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

Array = jax.Array

# profiler span names (stable: the benchmark's per-layer metrics read them)
SPAN_WAIT = "amb.data.wait"
SPAN_BUILD = "amb.data.build"
SPAN_PUT = "amb.data.put"


# ---------------------------------------------------------------------------
# Sharded placement (the single put)
# ---------------------------------------------------------------------------

def batch_sharding(batch, mesh, batch_axes=("data",)):
    """Per-leaf ``NamedSharding``: leading dim over the worker axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sh(x):
        return NamedSharding(mesh, P(batch_axes, *([None] * (x.ndim - 1))))

    return jax.tree.map(sh, batch)


def put_batch(batch, mesh, batch_axes=("data",)):
    """Place a (host) batch onto the mesh in one ``device_put`` call.

    The whole tree goes through a single :func:`jax.device_put` (the
    runtime batches the per-leaf transfers), not one put per leaf.  A
    batch that is already committed to the target sharding — e.g. one a
    :class:`Prefetcher` or :class:`SyntheticSource` produced — passes
    through without a copy, so ``AMBSession.step`` can call this
    unconditionally.
    """
    return jax.device_put(batch, batch_sharding(batch, mesh, batch_axes))


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------

class InputSource:
    """Contract for the session's input side (see module docstring).

    ``batch(epoch)`` returns the epoch's *over-provisioned* global batch:
    leading dim ``n_workers * per_worker`` in worker-contiguous blocks,
    worker i's block drawn from its own shard of the underlying data.
    Must be deterministic in ``epoch`` (AMB restores resume the stream by
    the session's step counter) and safe to call from a background
    thread.
    """

    n_workers: int = 1
    per_worker: int = 1

    @property
    def global_batch(self) -> int:
        return self.n_workers * self.per_worker

    def batch(self, epoch: int):
        raise NotImplementedError


class StreamSource(InputSource):
    """Per-worker shards of one deterministic stream.

    Worker i's block is ``stream.batch(node=i, epoch, per_worker)`` —
    the streams are deterministic in (seed, node, epoch), so distinct
    node indices give each worker its own i.i.d. shard (paper §3's
    independent-samples assumption), and a restored session re-draws the
    exact remaining stream.

    The whole multi-worker build is **compiled once** (one jit over all
    workers' draws + the concatenate, with ``epoch`` traced): the
    eager op-by-op build costs hundreds of tiny dispatches per epoch —
    on a CPU host it measured ~500x the compiled build for the LM
    stream, several times the training step itself — which would make
    the data plane the training system's own straggler.  Token draws
    are bit-identical to the eager path; the float streams may differ
    from an eager ``stream.batch`` in the last ulp (fusion reorders
    the adds), but the source is self-consistent run to run.

    An ``assignment`` (:class:`repro.dist.redundancy.CodedAssignment`)
    switches to coded placement: each *group* of ``rho`` workers shares
    one distinct data block (drawn from the group's stream node), and
    member m's shard is that block **rolled** by its rotation offset —
    so local slot s holds block slot ``(s + shift) % per``, matching
    the decode weights' index map exactly.  One block draw per group,
    ``rho`` cheap rolls; the redundancy costs the stream nothing extra.
    """

    def __init__(self, stream, n_workers: int, per_worker: int,
                 assignment=None):
        self.stream = stream
        self.n_workers = int(n_workers)
        self.per_worker = int(per_worker)
        self.assignment = assignment
        if assignment is not None and assignment.n != self.n_workers:
            raise ValueError(f"assignment covers {assignment.n} workers, "
                             f"source has {self.n_workers}")

        def build(epoch):
            if assignment is None or assignment.rho <= 1:
                shards = [stream.batch(i, epoch, self.per_worker)
                          for i in range(self.n_workers)]
            else:
                nodes = assignment.data_nodes()
                shifts = assignment.shifts(self.per_worker)
                blocks = {g: stream.batch(int(g), epoch, self.per_worker)
                          for g in sorted({int(x) for x in nodes})}
                shards = [jax.tree.map(
                    lambda x, s=int(shifts[i]): jnp.roll(x, -s, axis=0),
                    blocks[int(nodes[i])]) for i in range(self.n_workers)]
            return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                *shards)

        self._build = jax.jit(build)

    def batch(self, epoch: int):
        return self._build(epoch)


class SyntheticSource(InputSource):
    """On-device uniform-token source (LM batches), already sharded.

    The jitted sampler writes straight into the mesh layout, so the
    prefetcher's ``put_batch`` is an identity — the zero-host-cost
    reference point for the dataplane benchmarks, and a stand-in for a
    fully device-resident ingestion pipeline.
    """

    def __init__(self, vocab_size: int, seq_len: int, n_workers: int,
                 per_worker: int, mesh, batch_axes=("data",), seed: int = 0):
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.n_workers = int(n_workers)
        self.per_worker = int(per_worker)
        self.seed = int(seed)
        gb = self.global_batch
        shardings = batch_sharding(
            {"tokens": jax.ShapeDtypeStruct((gb, seq_len), jnp.int32),
             "labels": jax.ShapeDtypeStruct((gb, seq_len), jnp.int32)},
            mesh, batch_axes)

        def sample(epoch):
            key = jax.random.fold_in(jax.random.PRNGKey(self.seed), epoch)
            toks = jax.random.randint(key, (gb, seq_len), 0, vocab_size)
            labels = jnp.concatenate(
                [toks[:, 1:], jnp.full((gb, 1), -1, toks.dtype)], axis=1)
            return {"tokens": toks, "labels": labels}

        self._sample = jax.jit(sample, out_shardings=shardings)

    def batch(self, epoch: int):
        return self._sample(jnp.int32(epoch))


class CostedSource(InputSource):
    """Wrap a source with a fixed per-batch host cost (benchmarks/tests).

    ``cost_s`` seconds of ``time.sleep`` model an I/O-bound host input
    path (disk / network reads / decompression waiting on something that
    is not the accelerator).  Sleeping releases the GIL, so a prefetcher
    genuinely overlaps it with the device step — exactly the overlap the
    dataplane exists to buy.
    """

    def __init__(self, inner: InputSource, cost_s: float):
        self.inner = inner
        self.cost_s = float(cost_s)
        self.n_workers = inner.n_workers
        self.per_worker = inner.per_worker

    def batch(self, epoch: int):
        import time
        b = self.inner.batch(epoch)
        jax.block_until_ready(b)     # the build itself, not just dispatch
        if self.cost_s > 0.0:
            time.sleep(self.cost_s)
        return b


def make_source(kind: str, *, n_workers: int, per_worker: int, **kw
                ) -> InputSource:
    """Source registry: ``lm`` / ``linreg`` / ``logreg`` stream shards,
    or the on-device ``synthetic`` source (needs ``mesh`` et al.)."""
    if kind == "synthetic":
        return SyntheticSource(n_workers=n_workers, per_worker=per_worker,
                               **kw)
    from .pipeline import make_stream
    return StreamSource(make_stream(kind, **kw), n_workers, per_worker)


# ---------------------------------------------------------------------------
# The prefetcher
# ---------------------------------------------------------------------------

class Prefetcher:
    """Background double-buffered feeder: host build + device put ahead
    of the consumer.

    A daemon thread walks epochs from ``start_epoch``, calls
    ``source.batch(epoch)``, commits the result to the mesh layout with
    :func:`put_batch`, and parks it in a queue of ``depth`` slots (2 =
    classic double buffering: one batch being consumed, one in flight).
    The bounded queue is the backpressure — the thread blocks once it is
    ``depth`` epochs ahead, so at most ``depth`` batches of device memory
    are ever pinned by the data plane.

    Iterate to consume (``next(pf)``), ``close()`` when done (also
    stops the thread mid-``put`` via the queue's timeout poll).  A
    source exception is re-raised in the consumer on the corresponding
    ``next`` call.

    Profiler spans, each with the stat ``epoch``: ``amb.data.build``
    and ``amb.data.put`` on the thread, ``amb.data.wait`` for the
    consumer blocked on the queue.
    """

    _STOP = object()

    def __init__(self, source: InputSource, mesh, batch_axes=("data",), *,
                 depth: int = 2, start_epoch: int = 0,
                 steps: Optional[int] = None,
                 put: Optional[Callable] = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.source = source
        self.depth = int(depth)
        self._put = put if put is not None else (
            lambda b: put_batch(b, mesh, batch_axes))
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._epochs = itertools.count(start_epoch) if steps is None \
            else iter(range(start_epoch, start_epoch + steps))
        self._next_epoch = start_epoch    # of the consumer's next batch
        self._thread = threading.Thread(target=self._fill, daemon=True,
                                        name="amb-prefetcher")
        self._thread.start()

    def _enqueue(self, item) -> None:
        """Park an item, honouring ``close()`` even on a full queue.

        Every producer-side put — batches, the error sentinel, the end
        sentinel — goes through this timeout-poll loop.  A plain
        blocking ``put`` here can strand the thread forever: if the
        consumer dies without draining (e.g. ``session.run`` unwinding
        on an unrelated error) the queue stays full and ``close()``'s
        ``join`` would time out, leaking the thread.
        """
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def _fill(self) -> None:
        try:
            for epoch in self._epochs:
                if self._stop.is_set():
                    return
                with TraceAnnotation(SPAN_BUILD, epoch=epoch):
                    batch = self.source.batch(epoch)
                with TraceAnnotation(SPAN_PUT, epoch=epoch):
                    batch = self._put(batch)
                self._enqueue((epoch, batch))
        except BaseException as e:                  # re-raised in next()
            self._enqueue((None, e))
            return
        self._enqueue((None, self._STOP))

    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        with TraceAnnotation(SPAN_WAIT, epoch=self._next_epoch):
            epoch, item = self._q.get()
        if epoch is None:
            if item is self._STOP:
                raise StopIteration
            raise item
        self._next_epoch = epoch + 1
        return item

    def close(self) -> None:
        """Stop the thread and drop any parked batches (idempotent)."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
