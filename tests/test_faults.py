"""Straggler-proof fleets: coded redundancy, survivor relayout, churn.

Covers the three robustness layers this repo adds on top of the paper's
b_i(t) = 0 wipeout tolerance:

  * :mod:`repro.dist.redundancy` — coded data placement + the
    decode-on-settle weights: unbiasedness (every covered sample totals
    weight one across its replica holders), bit-exactness of the
    uncoded path against ``seq_weights_from_b``, placement validation.
  * :mod:`repro.dist.consensus` elastic membership — operator
    properties of both the survivor-relayout taps (doubly stochastic,
    positive spectral gap, inactive rows exactly identity, combine ==
    dense matrix power) and the legacy dense ``masked_metropolis``
    fallback; the single-survivor identity and all-inactive rejection
    edge cases; dense-vs-relayout A/B agreement on the survivor mean.
  * :mod:`repro.faults` — determinism and composition of the fault
    models, injector actuation (events only on membership change,
    quorum guard, slowdown pinning), and — slow marked — dual-state
    preservation across leave -> rejoin on a real mesh, including the
    async D > 1 drain-first flush, plus the compiled-HLO check that
    churned ring steps stay on the collective-permute fast path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.dist import (CodedAssignment, SurvivorTaps, epoch_weights,
                        make_strategy, masked_metropolis, survivor_taps)
from repro.dist.amb import seq_weights_from_b
from repro.faults import (Compose, CorrelatedOutage, FailSlow, FailStop,
                          FaultInjector, PoissonChurn)

from test_dist import run_sub


# ---------------------------------------------------------------------------
# Coded redundancy: placement + decode weights
# ---------------------------------------------------------------------------

def test_coded_assignment_validation():
    with pytest.raises(ValueError):
        CodedAssignment(8, 3)                # rho must divide n
    with pytest.raises(ValueError):
        CodedAssignment(8, 0)                # rho >= 1
    a = CodedAssignment(8, 2)
    assert a.groups == 4
    assert [a.group(i) for i in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    # epoch_weights rejects a mismatched fleet size
    with pytest.raises(ValueError):
        epoch_weights(jnp.zeros(4, jnp.int32), 4, 2, CodedAssignment(8, 2))


def test_rotated_replicas_stagger_within_group():
    """Members of a group start their sweep at rotated offsets, so a
    half-finished group still covers the whole block (the point of the
    rotation — identical placement would re-cover the same prefix)."""
    a = CodedAssignment(8, 4)
    per = 8
    assert a.shifts(per)[:4].tolist() == [0, 2, 4, 6]
    # every worker in a group reads the group's stream node
    assert a.data_nodes()[:4].tolist() == [0, 0, 0, 0]


def test_uncoded_epoch_weights_bit_exact():
    """rho = 1 (and assignment=None) must reproduce the paper's eq.-3
    weights and effective batch bit-for-bit — coded support cannot
    perturb the uncoded fast path."""
    n, per = 4, 8
    b = jnp.asarray([0, 3, 8, 11], jnp.int32)     # incl. the per-cap case
    for a in (None, CodedAssignment(n, 1)):
        sw, bw = epoch_weights(b, n, per, a)
        ref = seq_weights_from_b(b, n * per, n).reshape(n, per)
        np.testing.assert_array_equal(np.asarray(sw), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(bw),
                                      np.minimum(np.asarray(b), per))


def test_decode_weights_unbiased_property():
    """The decode invariant: for every *covered* block sample the decode
    weights across its replica holders sum to exactly 1 (unbiased full
    gradient over the covered set); uncovered samples get weight 0."""
    rng = np.random.default_rng(0)
    for n, rho, per in [(8, 2, 4), (8, 4, 8), (6, 3, 5), (12, 2, 7)]:
        a = CodedAssignment(n, rho)
        shifts = a.shifts(per)
        for _ in range(10):
            b = rng.integers(0, per + 2, size=n)
            sw, bw = epoch_weights(jnp.asarray(b, jnp.int32), n, per, a)
            sw = np.asarray(sw)
            np.testing.assert_allclose(np.asarray(bw), sw.sum(1), rtol=1e-6)
            # scatter local weights back to block coordinates
            block_w = np.zeros((a.groups, per))
            covered = np.zeros((a.groups, per), dtype=bool)
            for i in range(n):
                g = a.group(i)
                for s in range(min(b[i], per)):
                    blk = (s + shifts[i]) % per
                    block_w[g, blk] += sw[i, s]
                    covered[g, blk] = True
            np.testing.assert_allclose(block_w[covered], 1.0, rtol=1e-6)
            assert (block_w[~covered] == 0.0).all()


def test_decode_single_survivor_recovers_full_block():
    """One full-batch survivor per group reconstructs the block alone at
    weight 1 — a dead replica holder costs no data, only redundancy."""
    n, rho, per = 8, 2, 4
    b = jnp.asarray([per, 0] * 4, jnp.int32)
    sw, bw = epoch_weights(b, n, per, CodedAssignment(n, rho))
    np.testing.assert_array_equal(np.asarray(sw)[0::2], 1.0)
    np.testing.assert_array_equal(np.asarray(sw)[1::2], 0.0)
    np.testing.assert_array_equal(np.asarray(bw), [per, 0] * 4)


def test_decode_double_coverage_halves_weights():
    n, rho, per = 4, 2, 4
    sw, bw = epoch_weights(jnp.full(4, per, jnp.int32), n, per,
                           CodedAssignment(n, rho))
    np.testing.assert_allclose(np.asarray(sw), 0.5)
    np.testing.assert_allclose(np.asarray(bw), per / 2)


# ---------------------------------------------------------------------------
# Elastic membership: survivor taps + dense fallback operator properties
# ---------------------------------------------------------------------------

def _spectral_gap(p, active):
    """1 - |second eigenvalue| of the operator restricted to survivors."""
    act = np.asarray(active)
    sub = np.asarray(p)[np.ix_(act, act)]
    ev = np.sort(np.abs(np.linalg.eigvals(sub)))[::-1]
    assert abs(ev[0] - 1.0) < 1e-6           # f32 tap weights
    return 1.0 - ev[1] if len(ev) > 1 else 1.0


@pytest.mark.parametrize("graph,n", [("ring", 8), ("torus", 12)])
def test_survivor_taps_operator_properties(graph, n):
    rng = np.random.default_rng(1)
    for _ in range(8):
        active = rng.random(n) > 0.4
        if active.sum() < 2:
            active[:2] = True
        taps = survivor_taps(tuple(active), graph)
        assert isinstance(taps, SurvivorTaps)
        p = taps.dense()
        # rows/cols sum to 1, non-negative: a doubly stochastic operator
        np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
        np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-6)
        assert (p >= -1e-12).all()
        # inactive rows/cols are exactly identity (state frozen)
        for i in np.flatnonzero(~active):
            want = np.zeros(n)
            want[i] = 1.0
            np.testing.assert_array_equal(p[i], want)
            np.testing.assert_array_equal(p[:, i], want)
        # survivors form a connected re-laid ring/torus: gap > 0
        assert _spectral_gap(p, active) > 1e-6
        # take() applies the dense operator on the survivor rows (the
        # inactive rows are restored to identity by combine's final
        # mask, not by the taps themselves)
        x = rng.standard_normal((n, 5)).astype(np.float32)
        got = sum(np.asarray(taps.weights[i]) * np.asarray(
            taps.take(jnp.asarray(x), i)) for i in range(taps.k))
        np.testing.assert_allclose(got[active], (p @ x)[active], atol=1e-5)


def test_masked_metropolis_operator_properties():
    """The dense fallback keeps the same contract on the *induced*
    subgraph: doubly stochastic, frozen inactive rows, positive gap on
    connected survivor sets, loud failure on disconnected ones."""
    from repro.core import consensus as cns
    adj = cns.build_graph("ring", 8)
    p = masked_metropolis(adj, (True, True, True, False, True,
                               True, True, True), lazy=0.5)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-12)
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-12)
    np.testing.assert_array_equal(p[3], np.eye(8)[3])
    active = np.ones(8, bool)
    active[3] = False
    assert _spectral_gap(p, active) > 1e-6
    # two non-adjacent failures disconnect a ring's induced subgraph
    with pytest.raises(ValueError, match="disconnect"):
        masked_metropolis(adj, (True, True, False, True, True,
                                False, True, True), lazy=0.5)


def test_relayout_reconnects_what_masking_disconnects():
    """The mask that kills the induced-subgraph ring is exactly where
    relayout earns its keep: survivors re-enumerate onto a fresh ring,
    gossip converges to the survivor mean anyway."""
    n = 8
    active = (True, True, False, True, True, False, True, True)
    msgs = jax.random.normal(jax.random.PRNGKey(1), (n, 16))
    g = make_strategy("gossip", n, rounds=400, graph="ring", active=active)
    assert isinstance(g.taps, SurvivorTaps)
    out = np.asarray(g.combine(msgs))
    act = np.asarray(active)
    want = np.asarray(msgs)[act].mean(0)
    np.testing.assert_allclose(out[act],
                               np.broadcast_to(want, out[act].shape),
                               atol=1e-5)
    np.testing.assert_array_equal(out[~act], np.asarray(msgs)[~act])
    # the legacy dense fallback (relayout off) refuses this mask
    with pytest.raises(ValueError, match="disconnect"):
        make_strategy("gossip", n, rounds=4, graph="ring", active=active,
                      relayout=False)


def test_relayout_and_dense_fallback_agree_on_survivor_mean():
    """A/B: on a mask both operators accept, they reach the same fixed
    point (the survivor mean) — relayout changes the mixing path, not
    the answer."""
    n = 6
    active = (True, True, True, False, True, True)
    msgs = jax.random.normal(jax.random.PRNGKey(2), (n, 8))
    fast = make_strategy("gossip", n, rounds=300, graph="ring",
                         active=active)
    dense = make_strategy("gossip", n, rounds=300, graph="ring",
                          active=active, relayout=False)
    assert isinstance(fast.taps, SurvivorTaps) and dense.taps is None
    np.testing.assert_allclose(np.asarray(fast.combine(msgs)),
                               np.asarray(dense.combine(msgs)), atol=1e-4)


def test_quantized_survivor_path_is_finite_and_identity_on_dropped():
    n = 8
    active = (True, False, True, True, True, False, True, True)
    msgs = jax.random.normal(jax.random.PRNGKey(3), (n, 32))
    g = make_strategy("gossip_q8", n, rounds=2, graph="ring",
                      active=active)
    assert isinstance(g.taps, SurvivorTaps)
    out = np.asarray(g.combine(msgs, key=jax.random.PRNGKey(0)))
    assert np.isfinite(out).all()
    act = np.asarray(active)
    np.testing.assert_array_equal(out[~act], np.asarray(msgs)[~act])


def test_single_survivor_degenerates_to_identity():
    """S1: one survivor means there is nobody to gossip with — the
    strategy must be the exact identity (no permutes, no quantization
    noise), for the fp32 and the quantized planes alike."""
    n = 4
    active = (False, False, True, False)
    msgs = jax.random.normal(jax.random.PRNGKey(4), (n, 8))
    for name in ("gossip", "gossip_q8", "gossip_q4"):
        g = make_strategy(name, n, rounds=6, graph="ring", active=active)
        assert g.identity and g.taps is None
        out = np.asarray(g.combine(msgs, key=jax.random.PRNGKey(1)))
        np.testing.assert_array_equal(out, np.asarray(msgs))


def test_all_inactive_fleet_is_rejected():
    """S1: an all-down fleet has no consensus operator — loud error,
    not a silent NaN factory."""
    for name in ("gossip", "gossip_q8"):
        with pytest.raises(ValueError, match="at least one worker"):
            make_strategy(name, 4, rounds=2, graph="ring",
                          active=(False,) * 4)


def test_survivor_taps_declines_non_circulant_cases():
    assert survivor_taps((True, False, False, False)) is None   # 1 alive
    assert survivor_taps((True, True, True), graph="star") is None


# ---------------------------------------------------------------------------
# Fault models
# ---------------------------------------------------------------------------

def test_fail_stop_window():
    m = FailStop(workers=(1, 3), at=2, until=5)
    assert m.fleet(1, 4).active.all()
    st = m.fleet(3, 4)
    np.testing.assert_array_equal(st.active, [True, False, True, False])
    assert m.fleet(5, 4).active.all()
    assert not st.healthy and m.fleet(0, 4).healthy


def test_fail_slow_multiplies_clock_draws():
    m = FailSlow(workers=(0,), factor=3.0, start=1, stop=4)
    assert m.fleet(0, 2).slow.tolist() == [1.0, 1.0]
    assert m.fleet(2, 2).slow.tolist() == [3.0, 1.0]
    assert m.fleet(2, 2).active.all()       # slow, not gone
    assert m.fleet(4, 2).healthy


def test_correlated_outage_periodicity():
    m = CorrelatedOutage(group=(0, 1), period=4, duration=2, start=2)
    downs = [not m.fleet(e, 4).active[0] for e in range(12)]
    assert downs == [False, False, True, True, False, False,
                     True, True, False, False, True, True]


def test_compose_ands_membership_and_multiplies_slowdowns():
    m = Compose((FailStop(workers=(2,), at=0),
                 FailSlow(workers=(0,), factor=2.0),
                 FailSlow(workers=(0,), factor=3.0)))
    st = m.fleet(0, 4)
    np.testing.assert_array_equal(st.active, [True, True, False, True])
    assert st.slow[0] == 6.0


def test_poisson_churn_is_pure_and_pins_quorum():
    m = PoissonChurn(leave_rate=0.5, rejoin_rate=0.5, seed=7, pin=2)
    n = 6
    traj = [m.fleet(e, n).active.copy() for e in range(40)]
    # pure in epoch: re-query gives the identical trajectory
    for e in (0, 13, 39):
        np.testing.assert_array_equal(m.fleet(e, n).active, traj[e])
    # pinned workers never leave; churned ones actually churn both ways
    assert all(t[:2].all() for t in traj)
    flat = np.stack(traj)[:, 2:]
    assert (~flat).any() and flat.any()
    transitions = (flat[1:] != flat[:-1]).sum()
    assert transitions >= 4
    # a different seed gives a different trajectory
    other = PoissonChurn(leave_rate=0.5, rejoin_rate=0.5, seed=8, pin=2)
    assert any(not np.array_equal(other.fleet(e, n).active, traj[e])
               for e in range(40))


# ---------------------------------------------------------------------------
# FaultInjector actuation
# ---------------------------------------------------------------------------

class _StubSession:
    n_workers = 4

    def __init__(self):
        self.active_calls, self.slow_calls = [], []

    def set_active(self, active):
        self.active_calls.append(np.asarray(active).copy())

    def set_slowdown(self, slow):
        self.slow_calls.append(None if slow is None
                               else np.asarray(slow).copy())


def test_injector_actuates_only_on_change():
    sess = _StubSession()
    inj = FaultInjector(FailStop(workers=(1,), at=2, until=4))
    for e in range(6):
        inj.apply(sess, e)
    # all-up at 0 is a change from "never applied"; then down at 2, up at 4
    assert len(sess.active_calls) == 3
    np.testing.assert_array_equal(sess.active_calls[1],
                                  [True, False, True, True])
    assert inj.membership_changes == 3
    assert [ev["epoch"] for ev in inj.events] == [0, 2, 4]


def test_injector_quorum_guard_keeps_worker_zero():
    sess = _StubSession()
    inj = FaultInjector(FailStop(workers=(0, 1, 2, 3), at=0))
    inj.apply(sess, 0)
    np.testing.assert_array_equal(sess.active_calls[0],
                                  [True, False, False, False])


def test_injector_slowdown_pinning():
    sess = _StubSession()
    inj = FaultInjector(FailSlow(workers=(2,), factor=4.0, start=1, stop=2))
    for e in range(3):
        inj.apply(sess, e)
    # nominal -> [1,1,4,1] -> nominal; nominal is pinned as None
    assert sess.slow_calls[0] is None
    np.testing.assert_array_equal(sess.slow_calls[1], [1, 1, 4, 1])
    assert sess.slow_calls[2] is None


def test_session_set_slowdown_validation():
    from test_api import _tiny_session
    session, _ = _tiny_session()
    with pytest.raises(ValueError):
        session.set_slowdown([1.0, 1.0])     # wrong length (n = 1)
    with pytest.raises(ValueError):
        session.set_slowdown([0.0])          # must be positive
    session.set_slowdown([2.5])
    assert session._slow is not None
    session.set_slowdown(None)
    assert session._slow is None
    session.close()


# ---------------------------------------------------------------------------
# Mesh integration (slow): state across leave -> rejoin, fast-path HLO
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_leave_rejoin_preserves_dual_state_async_drain():
    """Leave -> rejoin on a real 8-device mesh under AMB-DG staleness 2:
    set_active drains the in-flight queue first (payloads settle under
    the operator they were packed for), the departed worker's dual is
    bit-frozen while down, and it resumes from that state on rejoin."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
        from repro.data import LMTokenStream

        SEQ, BPW = 32, 2
        sess = AMBSession(
            TrainSpec(arch="qwen2-1.5b", smoke=True, seq_len=SEQ,
                      batch_per_worker=BPW, data=8),
            ClockSpec(kind="simulated"),
            ConsensusSpec(consensus="gossip", gossip_rounds=3,
                          async_epochs=True, staleness=2))
        stream = LMTokenStream(vocab_size=sess.cfg.vocab_size,
                               seq_len=SEQ, seed=0)
        for e in range(3):                    # fill the staleness queue
            sess.step(stream.batch(0, e, sess.global_batch))

        mask = [True] * 8
        mask[5] = False
        sess.set_active(mask)                 # drains in-flight payloads
        z_frozen = [np.asarray(z)[5].copy()
                    for z in jax.tree.leaves(sess.state["z"])]
        for e in range(3, 5):
            m = sess.step(stream.batch(0, e, sess.global_batch))
            assert m["b"][5] == 0
        for zf, z in zip(z_frozen, jax.tree.leaves(sess.state["z"])):
            np.testing.assert_array_equal(zf, np.asarray(z)[5])
        print("FROZEN_OK")

        sess.set_active([True] * 8)           # rejoin from the stale dual
        m = sess.step(stream.batch(0, 5, sess.global_batch))
        assert m["b"][5] > 0
        # the drain emptied the queue, so this step only ENQUEUES its
        # payload (1 in flight < D=2) — flush settles it before we
        # measure that the rejoined dual resumed moving
        sess.flush()
        moved = max(float(np.abs(np.asarray(z)[5] - zf).max())
                    for zf, z in zip(z_frozen,
                                     jax.tree.leaves(sess.state["z"])))
        assert moved > 0.0
        print("REJOIN_OK")
    """)
    assert "FROZEN_OK" in out and "REJOIN_OK" in out


@pytest.mark.slow
def test_churned_ring_combine_stays_on_permute_fast_path():
    """Acceptance check: the compiled combine for a churned ring mask
    contains collective-permutes and never materializes the worker axis
    — the survivor relayout keeps elastic membership off the dense
    ``P @ m`` fallback, which compiles to an all-gather of all n
    messages followed by a dot over the worker axis."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.dist import make_strategy
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))
        active = (True, True, False, True, True, False, True, True)
        for name in ("gossip", "gossip_q8"):
            g = make_strategy(name, 8, rounds=2, graph="ring",
                              active=active)
            sh = NamedSharding(mesh, P("data"))
            fn = jax.jit(lambda m: g.combine(m, key=jax.random.PRNGKey(0)),
                         in_shardings=sh, out_shardings=sh)
            hlo = fn.lower(
                jax.ShapeDtypeStruct((8, 256), jnp.float32)).compile()
            txt = hlo.as_text()
            assert "collective-permute" in txt, name
            assert "all-gather" not in txt, name
            print("FAST_PATH_OK", name)

        # A/B: relayout=False on a *connected* mask compiles the dense
        # operator instead — all-gather + worker-axis dot, no permutes
        g = make_strategy("gossip", 8, rounds=2, graph="ring",
                          active=(True,) * 7 + (False,), relayout=False)
        sh = NamedSharding(mesh, P("data"))
        txt = jax.jit(g.combine, in_shardings=sh, out_shardings=sh).lower(
            jax.ShapeDtypeStruct((8, 256), jnp.float32)).compile().as_text()
        assert "all-gather" in txt and "collective-permute" not in txt
        print("DENSE_FALLBACK_OK")
    """)
    assert out.count("FAST_PATH_OK") == 2 and "DENSE_FALLBACK_OK" in out


@pytest.mark.slow
def test_session_under_poisson_churn_trains_and_restores_bit_exact():
    """End to end on 8 devices: Poisson churn + coded redundancy keeps
    every loss finite, and a mid-churn save -> restore -> continue run
    reproduces the uninterrupted run bit-for-bit (fault models are pure
    in the epoch index, so the trajectory replays)."""
    out = run_sub("""
        import tempfile
        import numpy as np
        from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
        from repro.faults import FaultInjector, PoissonChurn

        train = TrainSpec(arch="qwen2-1.5b", smoke=True, seq_len=16,
                          batch_per_worker=2, data=8, redundancy=2)
        cons = ConsensusSpec(consensus="gossip", gossip_rounds=2)
        model = PoissonChurn(leave_rate=0.4, rejoin_rate=0.6, seed=5)

        def fresh():
            return AMBSession(train, ClockSpec(kind="simulated"), cons)

        ref, losses = fresh(), []
        ref.run(6, faults=FaultInjector(model), prefetch=0,
                on_step=lambda s, m: losses.append(float(m["loss"])))
        assert np.isfinite(losses).all() and len(losses) == 6
        inj = FaultInjector(model)
        sess = fresh()
        sess.run(3, faults=inj, prefetch=0)
        assert inj.membership_changes >= 1
        with tempfile.TemporaryDirectory() as d:
            sess.save(d)
            resumed = AMBSession.restore(d)
        got = []
        resumed.run(3, faults=FaultInjector(model), prefetch=0,
                    on_step=lambda s, m: got.append(float(m["loss"])))
        assert got == losses[3:], (got, losses[3:])
        print("CHURN_RESTORE_OK", losses)
    """)
    assert "CHURN_RESTORE_OK" in out
