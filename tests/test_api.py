"""The repro.api Session surface: specs, clocks, protocol, elasticity.

Fast in-process tests cover the spec round-trips (JSON + argparse), the
tri-state compute_time contract (an explicit 0.0 is honoured), the
zero-step no-op session, and the masked-subgraph consensus operator.
The golden-parity suite at the bottom (slow, forced-host-device
subprocess) asserts that an AMBSession-driven run reproduces the
pre-redesign ``launch/train.py`` wiring bit-for-bit in every consensus
mode, and that ``set_active`` is exactly the b_i(t) = 0 path.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AMBSession, ClockSpec, ConsensusSpec, MeasuredClock,
                       SimulatedClock, TrainSpec, build_protocol, make_clock)
from repro.core.stragglers import amb_batch_sizes
from repro.launch.mesh import make_mesh

from test_dist import run_sub      # canonical forced-device subprocess


# ---------------------------------------------------------------------------
# Specs: JSON + argparse round-trips
# ---------------------------------------------------------------------------

def test_spec_json_roundtrip():
    specs = [
        TrainSpec(arch="rwkv6-3b", smoke=True, data=4, model=2, pod=2,
                  optimizer="adamw", mode="fmb", seed=7),
        ClockSpec(kind="simulated", compute_time=0.0, comm_time=1.5,
                  straggler="deterministic"),
        ConsensusSpec(consensus="gossip_q4", graph="torus",
                      torus_shape=(2, 4), pipeline=True, gossip_rounds=9,
                      beta_mu=16.0),
    ]
    for spec in specs:
        s = spec.to_json()
        back = type(spec).from_json(s)
        assert back == spec, (spec, back)
        assert back.to_json() == s        # stable fixed point
    # tuples survive the JSON list round-trip
    cs = ConsensusSpec.from_json(
        ConsensusSpec(torus_shape=(2, 4)).to_json())
    assert cs.torus_shape == (2, 4)


def test_spec_argparse_roundtrip():
    ap = argparse.ArgumentParser()
    TrainSpec.add_cli_args(ap)
    ClockSpec.add_cli_args(ap)
    ConsensusSpec.add_cli_args(ap)

    # defaults parse to the default specs
    args = ap.parse_args([])
    assert TrainSpec.from_args(args) == TrainSpec()
    assert ClockSpec.from_args(args) == ClockSpec()
    assert ConsensusSpec.from_args(args) == ConsensusSpec()

    # a full CLI line reconstructs the exact spec triple
    args = ap.parse_args([
        "--arch", "qwen2-1.5b", "--smoke", "--data", "4", "--model", "2",
        "--batch-per-worker", "2", "--seq-len", "32", "--seed", "3",
        "--sim-clock", "--compute-time", "0.0", "--comm-time", "2.0",
        "--consensus", "gossip", "--graph", "torus",
        "--gossip-rounds", "7", "--pipeline"])
    train = TrainSpec.from_args(args)
    assert train == TrainSpec(arch="qwen2-1.5b", smoke=True, data=4,
                              model=2, batch_per_worker=2, seq_len=32,
                              seed=3)
    clock = ClockSpec.from_args(args)
    assert clock.kind == "simulated"       # --sim-clock alias
    assert clock.compute_time == 0.0       # explicit zero survives
    assert clock.comm_time == 2.0
    cons = ConsensusSpec.from_args(args)
    assert cons == ConsensusSpec(consensus="gossip", graph="torus",
                                 gossip_rounds=7, pipeline=True)
    # CLI -> spec -> JSON -> spec closes the loop
    assert TrainSpec.from_json(train.to_json()) == train


# ---------------------------------------------------------------------------
# Clock: tri-state compute_time (the falsy-zero fix)
# ---------------------------------------------------------------------------

def test_explicit_zero_compute_time_is_honoured():
    key = jax.random.PRNGKey(0)
    for kind in ("simulated", "measured"):
        clk = make_clock(ClockSpec(kind=kind, compute_time=0.0), n=4,
                         batch_per_worker=8)
        times, budget = clk.epoch(key)
        assert budget == 0.0, (kind, budget)
        # T = 0 means nobody finishes a gradient — the b_i(t) = 0 epoch
        assert int(amb_batch_sizes(times, budget).sum()) == 0
    # and resolve_budget is the canonical tri-state helper
    assert ClockSpec(compute_time=0.0).resolve_budget(3.5) == 0.0
    assert ClockSpec(compute_time=None).resolve_budget(3.5) == 3.5


def test_measured_clock_budget_tracks_updates():
    clk = make_clock(ClockSpec(kind="measured"), n=4, batch_per_worker=8)
    assert isinstance(clk, MeasuredClock)
    _, b0 = clk.epoch(jax.random.PRNGKey(0))
    assert b0 > 0.0                     # pre-measurement boot (model unit)
    clk.update(step_seconds=32.0, global_b=32.0)   # 1 s per gradient
    _, b1 = clk.epoch(jax.random.PRNGKey(1))
    # Lemma-6 budget in measured units: (1 + n/b) * sec_per_grad * bpw
    assert b1 == pytest.approx((1.0 + 4 / 32) * 1.0 * 8.0)
    sim = make_clock(ClockSpec(kind="simulated"), n=4, batch_per_worker=8)
    assert isinstance(sim, SimulatedClock)
    sim.update(1.0, 1.0)                # no-op by contract
    _, bs = sim.epoch(jax.random.PRNGKey(0))
    assert bs == sim.budget_t


# ---------------------------------------------------------------------------
# Session basics on a trivial in-process mesh
# ---------------------------------------------------------------------------

def _tiny_session(consensus=ConsensusSpec(), clock=None, seed=0):
    from repro.models.common import ArchConfig
    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=64, q_chunk=16, kv_chunk=16,
                     mxu_f32_accum=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    train = TrainSpec(batch_per_worker=2, seq_len=8, seed=seed)
    return AMBSession(train, clock or ClockSpec(kind="simulated"),
                      consensus, mesh=mesh, cfg=cfg), cfg


def test_zero_step_session_is_a_noop(tmp_path):
    """No step ever runs: params are the init, flush/save still work."""
    from repro.models import init_params
    session, cfg = _tiny_session()
    p0 = jax.tree.map(np.asarray, init_params(jax.random.PRNGKey(0), cfg))
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(session.params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    session.flush()                        # no in-flight consensus: no-op
    session.save(tmp_path)                 # checkpoint at step 0
    assert (tmp_path / "step_00000000").exists()
    assert session.steps_done == 0


def test_on_step_reports_zero_based_epoch_just_run():
    """``on_step(epoch, metrics)`` passes the 0-based index of the epoch
    that just finished, with ``steps_done`` already advanced past it."""
    session, _ = _tiny_session()
    seen = []
    session.run(3, on_step=lambda e, m: seen.append((e, session.steps_done)))
    assert seen == [(0, 1), (1, 2), (2, 3)]
    session.close()


def test_zero_step_train_driver_returns_none(tmp_path):
    """launch.train with --steps 0 returns None instead of raising
    UnboundLocalError (the pre-redesign bug)."""
    from repro.launch.train import main
    out = main(["--smoke", "--steps", "0", "--seq-len", "8",
                "--batch-per-worker", "1", "--sim-clock",
                "--metrics", str(tmp_path / "m.jsonl")])
    assert out is None


def test_session_modes_agree_on_single_worker():
    """n = 1: every consensus mode degenerates to the same local update,
    so one step must produce the identical loss across all of them."""
    losses = {}
    from repro.data import LMTokenStream
    for name, cons in [
        ("exact", ConsensusSpec()),
        ("gossip", ConsensusSpec(consensus="gossip", gossip_rounds=3)),
        ("gossip_q8", ConsensusSpec(consensus="gossip_q8",
                                    gossip_rounds=2)),
        ("pipelined", ConsensusSpec(consensus="gossip", pipeline=True)),
    ]:
        session, cfg = _tiny_session(cons)
        stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=8,
                               seed=0)
        m = session.step(stream.batch(0, 0, session.global_batch))
        session.flush()
        losses[name] = m["loss"]
    assert len(set(losses.values())) == 1, losses


def test_gossip_rejects_non_dual_averaging():
    with pytest.raises(ValueError):
        AMBSession(TrainSpec(optimizer="adamw"),
                   ClockSpec(kind="simulated"),
                   ConsensusSpec(consensus="gossip"),
                   mesh=make_mesh((1, 1), ("data", "model")))
    from repro.dist.amb import AMBConfig
    from repro.optim import AdamW
    with pytest.raises(ValueError):
        build_protocol(None, None, AMBConfig(consensus="gossip"),
                       optimizer=AdamW())


# ---------------------------------------------------------------------------
# Elastic membership: the masked consensus operator
# ---------------------------------------------------------------------------

def test_masked_metropolis_properties():
    from repro.core import consensus as cns
    from repro.dist import masked_metropolis
    adj = cns.ring_graph(6)
    active = np.array([1, 1, 0, 1, 1, 1], bool)
    p = masked_metropolis(adj, active, lazy=0.5)
    # doubly stochastic, inactive node is an identity row/column
    assert np.allclose(p.sum(0), 1.0) and np.allclose(p.sum(1), 1.0)
    assert p[2, 2] == 1.0 and np.count_nonzero(p[2]) == 1
    assert np.count_nonzero(p[:, 2]) == 1
    # active workers re-weight only surviving neighbors
    assert p[1, 2] == 0.0 and p[3, 2] == 0.0
    # a partitioned active set is rejected
    with pytest.raises(ValueError):
        masked_metropolis(adj, np.array([0, 1, 1, 0, 1, 1], bool), 0.5)


def test_masked_strategy_converges_to_active_mean():
    from repro.dist import make_strategy
    n = 6
    active = (True, True, False, True, True, True)
    msgs = jax.random.normal(jax.random.PRNGKey(0), (n, 16))
    g = make_strategy("gossip", n, rounds=300, graph="ring", active=active)
    # survivors re-lay onto a smaller ring: the masked operator stays on
    # the tap fast path instead of falling back to a dense P @ m
    from repro.dist import SurvivorTaps
    assert isinstance(g.taps, SurvivorTaps)
    out = np.asarray(g.combine(msgs))
    act = np.asarray(active)
    want = np.asarray(msgs)[act].mean(0)
    np.testing.assert_allclose(out[act],
                               np.broadcast_to(want, out[act].shape),
                               atol=1e-5)
    # the dropped worker keeps its own message verbatim
    np.testing.assert_allclose(out[2], np.asarray(msgs)[2], rtol=1e-6)


def test_set_active_masks_b_and_rebuilds():
    from repro.data import LMTokenStream
    session, cfg = _tiny_session()
    with pytest.raises(ValueError):
        session.set_active([False])          # someone must stay
    with pytest.raises(ValueError):
        session.set_active([True, True])     # wrong length
    session.set_active([True])               # all-active: no mask kept
    assert session._active is None
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=8, seed=0)
    m = session.step(stream.batch(0, 0, session.global_batch))
    assert m["b"].shape == (1,)


# ---------------------------------------------------------------------------
# Golden parity: AMBSession == the pre-redesign launch/train.py wiring
# (slow, forced-host-device subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_session_matches_pre_redesign_driver_bit_for_bit():
    """For each consensus mode, 3 AMBSession steps reproduce the exact
    per-step losses of the pre-redesign driver loop (the old main()'s
    hand wiring, replicated here against the dist primitives): same
    straggler draws, same key folding, same clock, same steps."""
    out = run_sub("""
        import time
        import jax, jax.numpy as jnp
        from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
        from repro.api.clock import MeasuredClock
        from repro.configs import smoke_config
        from repro.core.dual_averaging import BetaSchedule
        from repro.core.stragglers import ShiftedExponential, amb_batch_sizes
        from repro.data import LMTokenStream, shard_batch
        from repro.dist import use_sharding
        from repro.dist.amb import (AMBConfig, make_gossip_train_step,
                                    make_train_step, num_workers)
        from repro.dist.params import tree_shardings
        from repro.dist.pipeline import make_pipelined_gossip_train_step
        from repro.launch.mesh import make_host_mesh
        from repro.models import init_params
        from repro.optim import make_optimizer

        STEPS, BPW, SEQ, SEED = 3, 2, 32, 0

        def old_driver(consensus, pipeline):
            '''The pre-redesign launch/train.py main(), verbatim wiring.'''
            cfg = smoke_config("qwen2-1.5b")
            mesh = make_host_mesh(4, 2)
            n = num_workers(mesh)
            gb = n * BPW
            key = jax.random.PRNGKey(SEED)
            straggler = ShiftedExponential(lam=2.0 / 3.0, zeta=1.0,
                                           b_ref=BPW)
            clock = MeasuredClock(straggler, n, BPW)
            beta = BetaSchedule(k=50.0, mu=float(gb), scale=200.0)
            stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=SEQ,
                                   seed=SEED)
            gossip = consensus != "exact" or pipeline
            amb = AMBConfig(consensus=consensus, gossip_rounds=5,
                            graph="ring", beta=beta, seed=SEED)
            losses = []
            with use_sharding(mesh):
                params = init_params(key, cfg)
                params = jax.tree.map(
                    lambda p, sh: jax.device_put(p, sh), params,
                    tree_shardings(params, mesh))
                if gossip:
                    if pipeline:
                        init_s, gstep, flush = \
                            make_pipelined_gossip_train_step(cfg, mesh, amb)
                    else:
                        init_s, gstep = make_gossip_train_step(cfg, mesh,
                                                               amb)
                    state = init_s(params)
                    step_fn = jax.jit(gstep)
                else:
                    opt = make_optimizer("dual_averaging", beta=beta)
                    opt_state = opt.init(params)
                    step_fn = jax.jit(make_train_step(cfg, opt, mesh, amb))
                for step in range(STEPS):
                    skey = jax.random.fold_in(key, 10_000 + step)
                    times = clock.times(skey)
                    budget = clock.budget()
                    b = amb_batch_sizes(times, budget)
                    batch = shard_batch(stream.batch(0, step, gb), mesh,
                                        ("data",))
                    t0 = time.time()
                    if gossip:
                        state, m = step_fn(state, batch, b)
                    else:
                        params, opt_state, m = step_fn(params, opt_state,
                                                       batch, b)
                    losses.append(float(m["loss"]))
                    clock.update(time.time() - t0,
                                 float(m["global_batch"]))
            return losses

        def session_driver(consensus, pipeline):
            train = TrainSpec(arch="qwen2-1.5b", smoke=True, seq_len=SEQ,
                              batch_per_worker=BPW, data=4, model=2,
                              seed=SEED)
            session = AMBSession(train, ClockSpec(),
                                 ConsensusSpec(consensus=consensus,
                                               pipeline=pipeline))
            stream = LMTokenStream(vocab_size=session.cfg.vocab_size,
                                   seq_len=SEQ, seed=SEED)
            losses = [session.step(stream.batch(0, s,
                                                session.global_batch)
                                   )["loss"] for s in range(STEPS)]
            session.flush()
            return losses

        for consensus, pipeline in [("exact", False), ("gossip", False),
                                    ("gossip_q8", False),
                                    ("gossip", True)]:
            want = old_driver(consensus, pipeline)
            got = session_driver(consensus, pipeline)
            assert want == got, (consensus, pipeline, want, got)
            print("PARITY", consensus, "pipelined" if pipeline else "seq",
                  got)
    """)
    assert out.count("PARITY") == 4


@pytest.mark.slow
def test_set_active_equals_b_zero_on_mesh():
    """Elastic membership on a real 4x2 mesh: a dropped worker produces
    exactly the state a b_i(t) = 0 epoch would (exact consensus), and
    under gossip the dropped worker's dual replica is frozen while the
    active set keeps mixing."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
        from repro.data import LMTokenStream

        SEQ, BPW = 32, 2
        train = TrainSpec(arch="qwen2-1.5b", smoke=True, seq_len=SEQ,
                          batch_per_worker=BPW, data=4, model=2)
        clock = ClockSpec(kind="simulated")

        def fresh(consensus):
            return AMBSession(train, clock, ConsensusSpec(
                consensus=consensus, gossip_rounds=4))

        stream = LMTokenStream(vocab_size=fresh("exact").cfg.vocab_size,
                               seq_len=SEQ, seed=0)
        mask = [True, True, False, True]

        # exact consensus: set_active == forcing b_i(t) = 0 by hand
        sA = fresh("exact"); sA.set_active(mask)
        batch = stream.batch(0, 0, sA.global_batch)
        mA = sA.step(batch)
        assert mA["b"][2] == 0 and mA["b"].sum() > 0
        sB = fresh("exact")
        mB = sB.step(batch, b=jnp.asarray(mA["b"]))
        assert mA["loss"] == mB["loss"], (mA["loss"], mB["loss"])
        err = max(float(jnp.abs(a - b).max()) for a, b in
                  zip(jax.tree.leaves(sA.params),
                      jax.tree.leaves(sB.params)))
        assert err == 0.0, err
        print("EXACT_OK", mA["b"].tolist())

        # gossip: dropped worker is cut from the graph AND contributes 0
        sG = fresh("gossip"); sG.set_active(mask)
        z_before = [np.asarray(z)[2].copy()
                    for z in jax.tree.leaves(sG.state["z"])]
        mG = sG.step(batch)
        assert mG["b"][2] == 0
        z_after = [np.asarray(z)[2] for z in jax.tree.leaves(sG.state["z"])]
        for zb, za in zip(z_before, z_after):
            np.testing.assert_array_equal(zb, za)   # frozen while dropped
        # active workers did update
        moved = max(float(np.abs(np.asarray(z)[0]).max())
                    for z in jax.tree.leaves(sG.state["z"]))
        assert moved > 0.0
        # global batch only counts active workers
        assert mG["global_batch"] == float(mG["b"].sum())

        # the primal excludes the dropped worker's frozen dual: replacing
        # it with garbage must not move session.params at all
        p1 = [np.asarray(p) for p in jax.tree.leaves(sG.params)]
        sG.state["z"] = jax.tree.map(lambda z: z.at[2].set(1e3),
                                     sG.state["z"])
        p2 = [np.asarray(p) for p in jax.tree.leaves(sG.params)]
        for a, b in zip(p1, p2):
            np.testing.assert_array_equal(a, b)

        # rejoin: worker 2 participates again next step
        sG.set_active([True] * 4)
        mR = sG.step(stream.batch(0, 1, sG.global_batch))
        assert mR["b"][2] > 0
        print("GOSSIP_OK")
    """)
    assert "EXACT_OK" in out and "GOSSIP_OK" in out
