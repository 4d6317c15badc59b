"""The repro.dist.consensus strategy layer + the pipelined epoch.

Cross-implementation contracts, in-process on the single real CPU device:
tap-decomposed ring/torus gossip vs the dense ``core.consensus.gossip``
operator, quantized gossip vs ``core.extensions.gossip_quantized``
(including bias/variance behavior), and the staleness-1 pipelined step's
flush equivalence to the sequential gossip step.  The mesh-heavy
(subprocess, forced-device) variants live at the bottom, marked slow.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import consensus as cns
from repro.core.extensions import gossip_quantized
from repro.dist.consensus import (ExactConsensus, GossipConsensus,
                                  QuantizedGossipConsensus, group_taps,
                                  make_strategy)
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# Tap decomposition
# ---------------------------------------------------------------------------

def test_group_taps_ring_and_torus_reconstruct_p():
    for p, shape in [
        (cns.metropolis_weights(cns.ring_graph(6)), (6,)),
        (cns.metropolis_weights(cns.torus_graph(3, 4)), (3, 4)),
        (cns.metropolis_weights(cns.torus_graph(2, 16)), (2, 16)),
    ]:
        taps = group_taps(p, shape)
        assert taps is not None
        assert not any(taps.offsets[0])          # self tap first
        assert abs(float(taps.weights.sum()) - 1.0) < 1e-6

def test_group_taps_rejects_non_circulant():
    # star graph: hub degree != spoke degree -> P not group-circulant
    p = cns.metropolis_weights(cns.star_graph(6))
    assert group_taps(p, (6,)) is None
    # the paper's ring-plus-chords graph is not vertex transitive either
    p = cns.metropolis_weights(cns.build_graph("paper", 10), lazy=0.3)
    assert group_taps(p, (10,)) is None


# ---------------------------------------------------------------------------
# GossipConsensus == core.consensus.gossip (same P, same rounds)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,cols,rounds", [(2, 2, 3), (2, 3, 7),
                                              (3, 4, 12), (2, 16, 5)])
def test_torus_gossip_matches_core_gossip(rows, cols, rounds):
    """Torus strategy == dense gossip with the torus_graph Metropolis P."""
    n = rows * cols
    msgs = jax.random.normal(jax.random.PRNGKey(n + rounds), (n, 33))
    p = cns.metropolis_weights(cns.torus_graph(rows, cols), lazy=0.5)
    want = cns.gossip(msgs, jnp.asarray(p, jnp.float32), rounds)
    got = GossipConsensus(n, rounds, "torus",
                          torus_shape=(rows, cols)).combine(msgs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_dense_fallback_matches_core_gossip():
    """Non-circulant graphs run the dense operator — same result."""
    g = GossipConsensus(10, 6, "paper", lazy=0.3)
    assert g.taps is None
    msgs = jax.random.normal(jax.random.PRNGKey(3), (10, 21))
    want = cns.gossip(msgs, jnp.asarray(g.p, jnp.float32), 6)
    np.testing.assert_allclose(np.asarray(g.combine(msgs)),
                               np.asarray(want), rtol=1e-6)


def test_exact_strategy_is_global_mean():
    msgs = jax.random.normal(jax.random.PRNGKey(0), (5, 13))
    out = ExactConsensus(5).combine(msgs)
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(np.asarray(msgs.mean(0)),
                                               msgs.shape), rtol=1e-6)


# ---------------------------------------------------------------------------
# QuantizedGossipConsensus == core.extensions.gossip_quantized
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("graph,shape,bits", [("ring", None, 8),
                                              ("ring", None, 4),
                                              ("torus", (2, 3), 8),
                                              ("torus", (2, 3), 4)])
def test_quantized_strategy_matches_core(graph, shape, bits):
    """Same per-round uniform draws -> the tap-decomposed quantized gossip
    reproduces the dense CHOCO reference within float tolerance.

    The atol covers stochastic-rounding boundary flips: the two
    separately-compiled programs reduce the per-row grid (lo/scale) in
    different orders, so a draw within an ulp of a rounding threshold can
    flip — bounded by one (decayed) delta quantum."""
    n, rounds = 6, 8
    key = jax.random.PRNGKey(11)
    msgs = jax.random.normal(jax.random.fold_in(key, 1), (n, 64)) * 3.0
    q = QuantizedGossipConsensus(n, rounds, bits, graph, torus_shape=shape)
    want = gossip_quantized(msgs, jnp.asarray(q.p, jnp.float32), rounds,
                            bits, key)
    got = q.combine(msgs, key)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-3)


def test_quantized_bias_and_variance_bounds():
    """E_key[quantized gossip] ~ fp32 gossip (unbiased stochastic rounding),
    spread decays with more bits, and the consensus error tracks the core
    implementation's."""
    n, rounds, d = 6, 6, 96
    key = jax.random.PRNGKey(5)
    msgs = jax.random.normal(key, (n, d)) * 4.0
    exact = GossipConsensus(n, rounds, "ring").combine(msgs)

    def runs(bits, reps=24):
        q = QuantizedGossipConsensus(n, rounds, bits, "ring")
        return jnp.stack([q.combine(msgs, jax.random.fold_in(key, i))
                          for i in range(reps)])

    out8, out4 = runs(8), runs(4)
    spread = float(msgs.max() - msgs.min())
    # bias: the empirical mean stays well inside the dynamic range noise
    bias8 = float(jnp.abs(out8.mean(0) - exact).max())
    assert bias8 < 0.02 * spread
    # variance: 4-bit levels are 17x coarser -> strictly noisier than 8-bit
    var8 = float(out8.var(axis=0).mean())
    var4 = float(out4.var(axis=0).mean())
    assert var8 < var4
    # consensus error comparable to the core reference at equal rounds
    q8 = QuantizedGossipConsensus(n, rounds, 8, "ring")
    err_mesh = float(cns.consensus_error(q8.combine(msgs, key)))
    err_core = float(cns.consensus_error(gossip_quantized(
        msgs, jnp.asarray(q8.p, jnp.float32), rounds, 8, key)))
    assert err_mesh < 2.0 * err_core + 1e-3


def test_quantized_wire_bytes_accounting():
    d = 1 << 20
    fp = GossipConsensus(8, 1, "ring")
    q8 = QuantizedGossipConsensus(8, 1, 8, "ring")
    q4 = QuantizedGossipConsensus(8, 1, 4, "ring")
    assert fp.wire_bytes_per_round(d) == 4 * d * 2        # 2 ring neighbors
    assert q8.wire_bytes_per_round(d) < fp.wire_bytes_per_round(d) / 3.9
    assert q4.wire_bytes_per_round(d) < fp.wire_bytes_per_round(d) / 7.9


def test_factory_round_scaling_and_names():
    assert make_strategy("exact", 4).name == "exact"
    assert make_strategy("gossip", 4, rounds=5).rounds == 5
    assert make_strategy("gossip_q8", 4, rounds=5).rounds == 20
    assert make_strategy("gossip_q4", 4, rounds=5).rounds == 40
    with pytest.raises(ValueError):
        make_strategy("psum", 4)


# ---------------------------------------------------------------------------
# Pipelined epoch: flush equivalence (single-device mesh, in process)
# ---------------------------------------------------------------------------

def _tiny_setup():
    from repro.core.dual_averaging import BetaSchedule
    from repro.data import LMTokenStream
    from repro.models import init_params
    from repro.models.common import ArchConfig

    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=64, q_chunk=16, kv_chunk=16,
                     mxu_f32_accum=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    beta = BetaSchedule(k=5.0, mu=1.0, scale=10.0)
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=8, seed=0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, mesh, beta, stream, params


def test_pipelined_step_flush_matches_sequential_trivial_mesh():
    """One pipelined step + flush == one sequential gossip step, exactly:
    the same message settles through the same operator, one step later."""
    from repro.dist import use_sharding
    from repro.dist.amb import AMBConfig, make_gossip_train_step
    from repro.dist.pipeline import make_pipelined_gossip_train_step

    cfg, mesh, beta, stream, params = _tiny_setup()
    amb = AMBConfig(consensus="gossip", gossip_rounds=3, beta=beta)
    with use_sharding(mesh):
        batch = stream.batch(0, 0, 2)
        b = jnp.array([2], jnp.int32)
        init_s, gstep = make_gossip_train_step(cfg, mesh, amb)
        s_seq, m_seq = jax.jit(gstep)(init_s(params), batch, b)
        init_p, pstep, flush = make_pipelined_gossip_train_step(
            cfg, mesh, amb)
        s_pipe, m_pipe = jax.jit(pstep)(init_p(params), batch, b)
        s_pipe = jax.jit(flush)(s_pipe)
    assert float(m_pipe["global_batch"]) == float(m_seq["global_batch"])
    for a, bz in zip(jax.tree.leaves(s_seq["z"]),
                     jax.tree.leaves(s_pipe["z"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bz))


def test_pipelined_first_step_leaves_dual_untouched():
    """Epoch 1 has nothing in flight: the zero pending message's zero
    normaliser must hit the empty-neighborhood guard, not zero the dual."""
    from repro.dist import use_sharding
    from repro.dist.amb import AMBConfig
    from repro.dist.pipeline import make_pipelined_gossip_train_step

    cfg, mesh, beta, stream, params = _tiny_setup()
    amb = AMBConfig(consensus="gossip", gossip_rounds=2, beta=beta)
    with use_sharding(mesh):
        init_p, pstep, _ = make_pipelined_gossip_train_step(cfg, mesh, amb)
        s0 = init_p(params)
        s1, _ = jax.jit(pstep)(s0, stream.batch(0, 0, 2),
                               jnp.array([2], jnp.int32))
    for a, bz in zip(jax.tree.leaves(s0["z"]), jax.tree.leaves(s1["z"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bz))
    assert float(jnp.abs(s1["pending"]).sum()) > 0     # message enqueued


# ---------------------------------------------------------------------------
# Mesh-heavy variants (subprocess, forced host devices) — slow
# ---------------------------------------------------------------------------

from test_dist import run_sub as _run_sub      # the canonical forced-
# device subprocess runner (see tests/test_dist.py)


@pytest.mark.slow
def test_pipelined_flush_equivalence_on_mesh():
    """Flush equivalence + staleness-1 on a real 4x2 mesh, for the ring,
    torus, and quantized strategies."""
    out = _run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.dist import use_sharding
        from repro.dist.amb import AMBConfig, make_gossip_train_step
        from repro.dist.pipeline import make_pipelined_gossip_train_step
        from repro.data import LMTokenStream, shard_batch
        from repro.models import init_params
        from repro.core.dual_averaging import BetaSchedule
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = smoke_config("qwen2-1.5b")
        beta = BetaSchedule(k=20.0, mu=1.0, scale=50.0)
        stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
        b = jnp.array([2, 1, 2, 2], jnp.int32)
        for consensus, graph in [("gossip", "ring"), ("gossip", "torus"),
                                 ("gossip_q8", "torus")]:
            amb = AMBConfig(consensus=consensus, gossip_rounds=4,
                            graph=graph, beta=beta)
            with use_sharding(mesh):
                params = init_params(jax.random.PRNGKey(0), cfg)
                batch = shard_batch(stream.batch(0, 0, 8), mesh)
                init_s, gstep = make_gossip_train_step(cfg, mesh, amb)
                s_seq, _ = jax.jit(gstep)(init_s(params), batch, b)
                init_p, pstep, flush = make_pipelined_gossip_train_step(
                    cfg, mesh, amb)
                s_pipe, _ = jax.jit(pstep)(init_p(params), batch, b)
                s_flush = jax.jit(flush)(s_pipe)
                err = max(float(jnp.abs(a - bb).max()) for a, bb in
                          zip(jax.tree.leaves(s_seq["z"]),
                              jax.tree.leaves(s_flush["z"])))
                assert err == 0.0, (consensus, graph, err)
                # staleness-1: a second pipelined step's dual (settles the
                # first message) also equals the sequential first step
                s_pipe2, _ = jax.jit(pstep)(s_pipe, batch, b)
                err2 = max(float(jnp.abs(a - bb).max()) for a, bb in
                           zip(jax.tree.leaves(s_seq["z"]),
                               jax.tree.leaves(s_pipe2["z"])))
                assert err2 == 0.0, (consensus, graph, err2)
                print("OK", consensus, graph)
    """)
    assert out.count("OK") == 3


@pytest.mark.slow
def test_torus_gossip_step_trains_on_mesh():
    """--consensus gossip --graph torus end-to-end on the forced-host
    mesh: the acceptance path, minus the CLI."""
    out = _run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.dist import use_sharding
        from repro.dist.amb import AMBConfig, make_gossip_train_step
        from repro.dist.consensus import torus_shape_for_mesh
        from repro.data import LMTokenStream, shard_batch
        from repro.models import init_params
        from repro.core.dual_averaging import BetaSchedule
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert torus_shape_for_mesh(mesh) == (2, 2)
        cfg = smoke_config("qwen2-1.5b")
        beta = BetaSchedule(k=20.0, mu=1.0, scale=50.0)
        amb = AMBConfig(consensus="gossip", gossip_rounds=40,
                        graph="torus", beta=beta)
        init_state, step = make_gossip_train_step(cfg, mesh, amb)
        stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
        with use_sharding(mesh):
            params = init_params(jax.random.PRNGKey(0), cfg)
            state = init_state(params)
            b = jnp.array([2, 1, 2, 0], jnp.int32)
            batch = shard_batch(stream.batch(0, 0, 8), mesh)
            state, m = jax.jit(step)(state, batch, b)
        assert float(m["global_batch"]) == 5.0
        assert jnp.isfinite(m["loss"])
        # 40 rounds over the 2x2 torus -> near-consensus across pods
        spread = max(float(jnp.std(z.astype(jnp.float32), axis=0).max())
                     for z in jax.tree.leaves(state["z"]))
        print("spread", spread)
        assert spread < 1e-5
    """)
    assert "spread" in out
