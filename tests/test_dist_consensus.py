"""Fast in-process checks of repro.dist: mesh gossip vs core consensus,
seq-weight masking properties, and the exact step on the trivial mesh.

These run on the single real CPU device (no subprocess / forced device
count) — the cross-implementation contracts that test_dist.py then proves
on real multi-device meshes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import consensus as cns
from repro.dist.amb import (num_workers, ring_gossip, ring_p,
                            seq_weights_from_b, worker_axes)
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# ring_gossip == core.consensus.gossip (same P, same rounds)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,rounds", [(2, 1), (4, 4), (4, 25), (8, 7),
                                      (10, 12)])
def test_ring_gossip_matches_core_gossip(n, rounds):
    """The mesh-layout gossip (rolled neighbor stacks + K-way weighted
    combine) and the dense P @ m reference are the same operator."""
    msgs = jax.random.normal(jax.random.PRNGKey(n * 100 + rounds), (n, 33))
    p = jnp.asarray(ring_p(n), jnp.float32)
    want = cns.gossip(msgs, p, rounds)
    got = ring_gossip(msgs, rounds)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_ring_gossip_preserves_mean_and_contracts():
    n = 6
    msgs = jax.random.normal(jax.random.PRNGKey(3), (n, 17))
    out = ring_gossip(msgs, 30)
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(msgs.mean(0)), rtol=1e-5,
                               atol=1e-5)
    assert float(cns.consensus_error(out)) < 0.1 * float(
        cns.consensus_error(msgs))


def test_ring_gossip_single_worker_identity():
    msgs = jnp.ones((1, 5)) * 3.0
    np.testing.assert_array_equal(np.asarray(ring_gossip(msgs, 10)),
                                  np.asarray(msgs))


def test_ring_p_doubly_stochastic():
    for n in (2, 3, 4, 16):
        p = ring_p(n)
        assert np.allclose(p.sum(0), 1.0) and np.allclose(p.sum(1), 1.0)


# ---------------------------------------------------------------------------
# seq_weights_from_b properties (paper eq. 3 masking)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(1, 8), st.integers(1, 8), st.integers(0, 2 ** 30))
def test_seq_weights_properties(n, per, seed):
    """sum(w) == sum(min(b_i, per)); each worker block is a 0/1 prefix."""
    rng = np.random.default_rng(seed)
    b = rng.integers(0, per + 3, size=n)          # may exceed capacity
    gb = n * per
    w = np.asarray(seq_weights_from_b(jnp.asarray(b, jnp.int32), gb, n))
    assert w.shape == (gb,)
    assert set(np.unique(w)) <= {0.0, 1.0}
    assert w.sum() == np.minimum(b, per).sum()
    blocks = w.reshape(n, per)
    for i in range(n):
        k = int(blocks[i].sum())
        assert (blocks[i][:k] == 1.0).all() and (blocks[i][k:] == 0.0).all()
        assert k == min(int(b[i]), per)


def test_seq_weights_rejects_indivisible():
    with pytest.raises(ValueError):
        seq_weights_from_b(jnp.zeros((3,), jnp.int32), 10, 3)


# ---------------------------------------------------------------------------
# worker accounting on meshes (real single-device + fake shapes)
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_num_workers_spans_non_model_axes():
    assert num_workers(FakeMesh({"data": 4, "model": 2})) == 4
    assert num_workers(FakeMesh({"pod": 2, "data": 2, "model": 2})) == 4
    assert num_workers(FakeMesh({"model": 8})) == 1
    assert worker_axes(FakeMesh({"pod": 2, "data": 2, "model": 2})) == \
        ("pod", "data")


def test_exact_step_trivial_mesh_descends():
    """make_train_step on the 1x1 mesh (single real device): the full AMB
    masking/metrics path without any parallelism."""
    from repro.dist import use_sharding
    from repro.dist.amb import AMBConfig, make_train_step
    from repro.data import LMTokenStream
    from repro.models import init_params
    from repro.models.common import ArchConfig
    from repro.optim import make_optimizer

    cfg = ArchConfig(name="t", family="dense", num_layers=2, d_model=64,
                     num_heads=2, num_kv_heads=2, head_dim=32, d_ff=128,
                     vocab_size=128, q_chunk=32, kv_chunk=32,
                     mxu_f32_accum=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=16, seed=0)
    opt = make_optimizer("adamw", lr=1e-2)
    with use_sharding(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        state = opt.init(params)
        step = jax.jit(make_train_step(cfg, opt, mesh, AMBConfig()))
        losses = []
        for i in range(8):
            batch = stream.batch(0, i, 4)
            params, state, m = step(params, state, batch,
                                    jnp.array([3], jnp.int32))
            losses.append(float(m["loss"]))
        assert m["global_batch"] == 3
        assert losses[-1] < losses[0]


def test_gossip_step_zero_batch_preserves_duals():
    """A straggler-wiped epoch (every b_i(t) = 0) must leave the gossip dual
    state unchanged — the exact-consensus path sees a zero gradient there,
    and the decentralized path has to agree, not reset z to 0."""
    from repro.dist import use_sharding
    from repro.dist.amb import AMBConfig, make_gossip_train_step
    from repro.core.dual_averaging import BetaSchedule
    from repro.data import LMTokenStream
    from repro.models import init_params
    from repro.models.common import ArchConfig

    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=64, q_chunk=16, kv_chunk=16,
                     mxu_f32_accum=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    amb = AMBConfig(consensus="gossip", gossip_rounds=2,
                    beta=BetaSchedule(k=5.0, mu=1.0, scale=10.0))
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=8, seed=0)
    with use_sharding(mesh):
        init_state, gstep = make_gossip_train_step(cfg, mesh, amb)
        state = init_state(init_params(jax.random.PRNGKey(0), cfg))
        batch = stream.batch(0, 0, 2)
        state, _ = gstep(state, batch, jnp.array([2], jnp.int32))
        znorm = sum(float(jnp.abs(z).sum()) for z in
                    jax.tree.leaves(state["z"]))
        assert znorm > 0
        state2, m = gstep(state, batch, jnp.array([0], jnp.int32))
        for a, bz in zip(jax.tree.leaves(state["z"]),
                         jax.tree.leaves(state2["z"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bz))
        assert float(m["global_batch"]) == 0.0
