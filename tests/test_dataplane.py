"""The data plane (repro.data.loader) + the step-critical-path contracts.

Covers the sharded single-put (``put_batch`` and its deprecated
``shard_batch`` alias), the per-worker stream shards (worker i draws
stream node i — the pre-loader drivers fed every worker node 0), the
background :class:`~repro.data.Prefetcher` (ordering, backpressure,
error propagation, shutdown), TrainState donation through every epoch
driver (the pre-step state's buffers must actually be freed, with no
duplicated live buffers), the kernel router (compiled-Pallas-on-TPU /
jnp-ref-on-CPU decision, env + programmatic overrides), and — slow
marked — the prefetch-overlap win against an artificially costed source.
"""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import (CostedSource, InputSource, LMTokenStream,
                        Prefetcher, StreamSource, SyntheticSource,
                        make_source, put_batch, shard_batch)
from repro.kernels import router

from test_api import _tiny_session
from repro.api import ConsensusSpec
from repro.launch.mesh import make_mesh


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# put_batch / shard_batch
# ---------------------------------------------------------------------------

def test_put_batch_places_leading_dim_on_data_axis():
    mesh = _mesh11()
    batch = {"tokens": np.arange(32, dtype=np.int32).reshape(4, 8),
             "labels": np.arange(32, dtype=np.int32).reshape(4, 8)}
    dev = put_batch(batch, mesh)
    for leaf in jax.tree.leaves(dev):
        assert isinstance(leaf.sharding, jax.sharding.NamedSharding)
        # PartitionSpec normalises the one-axis tuple ("data",) to "data"
        assert leaf.sharding.spec[0] == "data"
    np.testing.assert_array_equal(np.asarray(dev["tokens"]),
                                  batch["tokens"])


def test_put_batch_is_idempotent_no_copy():
    """An already-committed batch passes through without a new buffer —
    what lets session.step call put_batch unconditionally on prefetched
    (already device-resident) batches."""
    mesh = _mesh11()
    batch = {"tokens": np.zeros((4, 8), np.int32)}
    once = put_batch(batch, mesh)
    twice = put_batch(once, mesh)
    assert twice["tokens"] is once["tokens"]


def test_shard_batch_is_a_put_batch_alias():
    mesh = _mesh11()
    batch = {"x": np.ones((2, 4), np.float32)}
    a = shard_batch(batch, mesh)
    b = put_batch(batch, mesh)
    np.testing.assert_array_equal(np.asarray(a["x"]), np.asarray(b["x"]))
    assert a["x"].sharding == b["x"].sharding


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------

def test_stream_source_draws_distinct_per_worker_shards():
    """Worker i's block must come from stream node i: distinct i.i.d.
    shards per worker (the old drivers fed node 0 to everyone, so the
    whole fleet trained on identical data)."""
    stream = LMTokenStream(vocab_size=97, seq_len=8, seed=3)
    src = StreamSource(stream, n_workers=4, per_worker=2)
    got = src.batch(5)
    assert jax.tree.leaves(got)[0].shape[0] == src.global_batch == 8
    blocks = [jax.tree.map(lambda x: np.asarray(x)[2 * i:2 * i + 2], got)
              for i in range(4)]
    for i, blk in enumerate(blocks):
        want = stream.batch(i, 5, 2)        # eager reference draw
        np.testing.assert_array_equal(blk["tokens"],
                                      np.asarray(want["tokens"]))
    # and the shards genuinely differ across workers
    assert not np.array_equal(blocks[0]["tokens"], blocks[1]["tokens"])


def test_stream_source_deterministic_in_epoch():
    src = StreamSource(LMTokenStream(vocab_size=31, seq_len=4, seed=0),
                       n_workers=2, per_worker=3)
    a = src.batch(7)
    b = src.batch(7)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    c = src.batch(8)
    assert not np.array_equal(np.asarray(a["tokens"]),
                              np.asarray(c["tokens"]))


def test_synthetic_source_lands_on_device_presharded():
    mesh = _mesh11()
    src = SyntheticSource(vocab_size=64, seq_len=8, n_workers=1,
                          per_worker=4, mesh=mesh)
    b = src.batch(0)
    assert b["tokens"].shape == (4, 8)
    assert isinstance(b["tokens"].sharding, jax.sharding.NamedSharding)
    # put_batch on it is the no-copy identity (sharding already matches)
    assert put_batch(b, mesh)["tokens"] is b["tokens"]
    # labels are the next-token shift with a -1 tail
    toks, labels = np.asarray(b["tokens"]), np.asarray(b["labels"])
    np.testing.assert_array_equal(labels[:, :-1], toks[:, 1:])
    assert (labels[:, -1] == -1).all()


def test_make_source_registry():
    mesh = _mesh11()
    s1 = make_source("lm", n_workers=2, per_worker=2, vocab_size=17,
                     seq_len=4)
    assert isinstance(s1, StreamSource)
    assert s1.global_batch == 4
    s2 = make_source("synthetic", n_workers=1, per_worker=2, vocab_size=17,
                     seq_len=4, mesh=mesh)
    assert isinstance(s2, SyntheticSource)
    with pytest.raises(KeyError):
        make_source("nope", n_workers=1, per_worker=1)


# ---------------------------------------------------------------------------
# Prefetcher
# ---------------------------------------------------------------------------

class _CountingSource(InputSource):
    n_workers, per_worker = 1, 1

    def __init__(self):
        self.built = []

    def batch(self, epoch):
        self.built.append(epoch)
        return {"e": np.asarray([epoch])}


def test_prefetcher_yields_epochs_in_order_and_stops():
    src = _CountingSource()
    pf = Prefetcher(src, _mesh11(), steps=5, start_epoch=3,
                    put=lambda b: b)
    got = [int(item["e"][0]) for item in pf]
    assert got == [3, 4, 5, 6, 7]
    pf.close()
    pf.close()                              # idempotent


def test_prefetcher_backpressure_bounds_lead():
    """The bounded queue is the backpressure: the thread never builds
    more than depth + 1 epochs ahead of the consumer (depth parked in
    the queue, one in the blocked put)."""
    src = _CountingSource()
    depth = 2
    pf = Prefetcher(src, _mesh11(), steps=10, depth=depth,
                    put=lambda b: b)
    consumed = 0
    max_lead = 0
    for item in pf:
        consumed += 1
        time.sleep(0.02)                    # slow consumer
        max_lead = max(max_lead, len(src.built) - consumed)
    pf.close()
    assert consumed == 10
    assert max_lead <= depth + 1, max_lead


def test_prefetcher_propagates_source_errors():
    class Boom(InputSource):
        n_workers, per_worker = 1, 1

        def batch(self, epoch):
            if epoch == 2:
                raise RuntimeError("bad shard")
            return {"e": np.asarray([epoch])}

    pf = Prefetcher(Boom(), _mesh11(), steps=5, put=lambda b: b)
    assert int(next(pf)["e"][0]) == 0
    assert int(next(pf)["e"][0]) == 1
    with pytest.raises(RuntimeError, match="bad shard"):
        next(pf)
    pf.close()


def test_prefetcher_close_unblocks_producer():
    src = _CountingSource()
    pf = Prefetcher(src, _mesh11(), steps=100, depth=1, put=lambda b: b)
    next(pf)
    pf.close()                              # thread mid-put must exit
    assert not pf._thread.is_alive()


def test_prefetcher_error_sentinel_honors_close_on_full_queue():
    """A source error with the queue already full must not strand the
    producer: the error sentinel's put goes through the same
    stop-polling loop as batches, so close() still reaps the thread
    even when the consumer never drains the error."""
    class BoomAfterFill(InputSource):
        n_workers, per_worker = 1, 1

        def batch(self, epoch):
            if epoch >= 1:                  # epoch 0 fills the depth-1 queue
                raise RuntimeError("late boom")
            return {"e": np.asarray([epoch])}

    pf = Prefetcher(BoomAfterFill(), _mesh11(), steps=5, depth=1,
                    put=lambda b: b)
    # wait for the producer to park epoch 0 and hit the error while the
    # queue is full — its sentinel put is now blocked on the consumer
    deadline = time.monotonic() + 5.0
    while pf._q.empty() and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)
    pf.close()                              # never consumed anything
    pf._thread.join(timeout=5.0)
    assert not pf._thread.is_alive()


def test_prefetcher_puts_batches_on_device():
    mesh = _mesh11()
    src = _CountingSource()
    pf = Prefetcher(src, mesh, steps=2)     # default put = put_batch
    item = next(pf)
    assert isinstance(item["e"].sharding, jax.sharding.NamedSharding)
    pf.close()


# ---------------------------------------------------------------------------
# Session integration: run(), donation, restore data order
# ---------------------------------------------------------------------------

def test_session_run_matches_manual_step_loop():
    """run() through the prefetched plane reproduces the manual
    step-by-step loop exactly (token draws are bit-identical)."""
    sA, _ = _tiny_session()
    sB, _ = _tiny_session()
    losses_run = []
    sA.run(3, on_step=lambda s, m: losses_run.append(m["loss"]))
    src = sB.batch_source()
    losses_manual = [sB.step(src.batch(e))["loss"] for e in range(3)]
    assert losses_run == losses_manual
    assert sA.steps_done == sB.steps_done == 3


def test_session_run_zero_steps_is_noop():
    s, _ = _tiny_session()
    assert s.run(0) is None
    assert s.steps_done == 0


def test_session_run_sync_path_matches_prefetched():
    sA, _ = _tiny_session()
    sB, _ = _tiny_session()
    mA = sA.run(2, prefetch=2)
    mB = sB.run(2, prefetch=0)
    assert mA["loss"] == mB["loss"]


def test_session_run_surfaces_source_error_and_stays_usable():
    """A source raising mid-run must surface from session.run itself —
    not hang, not vanish into the prefetch thread — and leave the
    session flushable and steppable, with the producer thread reaped."""
    import threading

    s, _ = _tiny_session(ConsensusSpec(consensus="gossip", graph="ring",
                                       async_epochs=True, staleness=2))
    inner = s.batch_source()

    class Flaky(InputSource):
        n_workers = inner.n_workers
        per_worker = inner.per_worker

        def batch(self, epoch):
            if epoch == 2:
                raise RuntimeError("shard fetch failed")
            return inner.batch(epoch)

    threads_before = threading.active_count()
    with pytest.raises(RuntimeError, match="shard fetch failed"):
        s.run(5, source=Flaky())
    # run's finally closed the prefetcher: no leaked producer thread
    deadline = time.monotonic() + 5.0
    while threading.active_count() > threads_before \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= threads_before
    assert s.steps_done == 2                # the epochs that completed
    s.flush()                               # drains in-flight consensus
    m = s.step(inner.batch(2))              # and the session still steps
    assert np.isfinite(m["loss"])
    s.close()


@pytest.mark.parametrize("consensus", [
    ConsensusSpec(),
    ConsensusSpec(consensus="gossip", graph="ring"),
    ConsensusSpec(consensus="gossip", graph="ring", pipeline=True),
    ConsensusSpec(consensus="gossip", graph="ring", async_epochs=True,
                  staleness=2),
], ids=["exact", "gossip", "pipelined", "async_D2"])
def test_donated_state_is_freed_every_protocol(consensus):
    """donate_argnums must hold through every epoch driver: after a
    step, every leaf of the pre-step TrainState is deleted (its buffer
    was reused in place, not shadowed by a second allocation), and the
    process-wide live-buffer count stays flat step over step."""
    s, _ = _tiny_session(consensus)
    src = s.batch_source()
    s.step(src.batch(0))                    # compile outside the count
    old = s.state
    live_before = len(jax.live_arrays())
    s.step(src.batch(1))
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old))
    s.step(src.batch(2))
    assert len(jax.live_arrays()) <= live_before
    # flush donates too; the session stays usable afterwards
    pre_flush = s.state
    s.flush()
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(pre_flush))
    _ = s.params


def test_donation_survives_staleness_retune():
    """_apply_staleness reassembles the state from pieces of the old
    one; the rebuilt state must still be donation-clean (no leaf object
    appearing twice)."""
    s, _ = _tiny_session(ConsensusSpec(consensus="gossip", graph="ring",
                                       async_epochs=True, staleness=2))
    src = s.batch_source()
    s.step(src.batch(0))
    s._apply_staleness(3)
    old = s.state
    s.step(src.batch(1))                    # would raise on double-donate
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old))


def test_restored_session_continues_data_order(tmp_path):
    """A save/restore must not rewind or skip stream epochs: restored
    run(n) consumes exactly the epochs an uninterrupted run would."""
    sA, _ = _tiny_session()
    sA.run(4)
    ref_loss = sA.run(1)["loss"]            # epoch 4 in one long run

    sB, _ = _tiny_session()
    sB.run(4)
    sB.save(tmp_path / "ck")
    from repro.api import AMBSession
    from repro.models.common import ArchConfig
    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=64, q_chunk=16, kv_chunk=16,
                     mxu_f32_accum=False)
    restored = AMBSession.restore(tmp_path / "ck", mesh=_mesh11(), cfg=cfg)
    assert restored.steps_done == 4
    assert restored.run(1)["loss"] == ref_loss


# ---------------------------------------------------------------------------
# Kernel router
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _reset_router():
    yield
    router.set_mode(None)
    os.environ.pop("REPRO_KERNELS", None)


def test_router_auto_routes_ref_on_cpu():
    if jax.default_backend() not in ("tpu", "gpu"):
        assert router.resolve() == "ref"
    else:
        assert router.resolve() == "pallas"
    # the hot path must never silently run the grid-emulation oracle
    assert router.resolve() != "pallas_interpret"


def test_router_env_and_set_mode_overrides():
    os.environ["REPRO_KERNELS"] = "pallas_interpret"
    assert router.mode() == "pallas_interpret"
    assert router.resolve() == "pallas_interpret"
    router.set_mode("ref")                  # programmatic beats env
    assert router.resolve() == "ref"
    router.set_mode(None)                   # back to env
    assert router.resolve() == "pallas_interpret"
    os.environ["REPRO_KERNELS"] = "bogus"
    with pytest.raises(ValueError, match="REPRO_KERNELS"):
        router.mode()


def test_router_force_and_validation():
    assert router.resolve(force="pallas_interpret") == "pallas_interpret"
    assert router.resolve(force="ref") == "ref"
    with pytest.raises(ValueError):
        router.resolve(force="auto")        # force must be concrete
    with pytest.raises(ValueError):
        router.set_mode("bogus")


def test_ops_dispatch_follows_router():
    """ops.gossip_combine under set_mode('ref') equals the forced
    interpret oracle — same math, routed implementation."""
    from repro.kernels import ops
    msgs = jax.random.normal(jax.random.PRNGKey(0), (3, 256), jnp.float32)
    w = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
    router.set_mode("ref")
    got = ops.gossip_combine(msgs, w)
    want = ops.gossip_combine(msgs, w, force="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)


def test_trainspec_kernels_flag_pins_router():
    s, _ = _tiny_session()                  # default: auto, leaves router
    from repro.api import TrainSpec
    import argparse
    ap = argparse.ArgumentParser()
    TrainSpec.add_cli_args(ap)
    args = ap.parse_args(["--kernels", "ref"])
    assert TrainSpec.from_args(args).kernels == "ref"
    with pytest.raises(SystemExit):
        ap.parse_args(["--kernels", "bogus"])


# ---------------------------------------------------------------------------
# Overlap (slow): the prefetched plane must beat the sync loop
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_prefetch_overlap_beats_sync_with_costed_source():
    """With an I/O-bound host cost ~ the step time, the prefetched data
    plane must hide the host path behind the device step.  The margin
    asserted (1.15x) is deliberately below the benchmarked ~1.4x to
    keep the test robust on loaded CI hosts.

    Needs a step large enough to dominate the queue/thread overhead
    (the 1x1 smoke step is ~1 ms — nothing to hide a cost behind), so
    this builds a wider model than ``_tiny_session``.
    """
    from repro.api import AMBSession, ClockSpec, TrainSpec
    from repro.models.common import ArchConfig
    cfg = ArchConfig(name="t2", family="dense", num_layers=2, d_model=128,
                     num_heads=4, num_kv_heads=4, head_dim=32, d_ff=512,
                     vocab_size=256, q_chunk=32, kv_chunk=32,
                     mxu_f32_accum=False)
    s = AMBSession(TrainSpec(batch_per_worker=8, seq_len=64),
                   ClockSpec(kind="simulated"), ConsensusSpec(),
                   mesh=_mesh11(), cfg=cfg)
    src = s.batch_source()
    s.run(2, src)                           # compile + warm
    t0 = time.perf_counter()
    s.run(4, src, prefetch=0)
    step_s = (time.perf_counter() - t0) / 4

    costed = CostedSource(src, step_s)
    t0 = time.perf_counter()
    s.run(6, costed, prefetch=0)
    t_sync = (time.perf_counter() - t0) / 6
    t0 = time.perf_counter()
    s.run(6, costed, prefetch=2)
    t_pre = (time.perf_counter() - t0) / 6
    assert t_sync / t_pre > 1.15, (t_sync, t_pre)
