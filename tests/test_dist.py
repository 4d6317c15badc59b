"""Distributed AMB: mesh train steps, gossip consensus, param specs.

Multi-device cases run in a subprocess with forced host devices so the main
pytest process keeps the single real device (the dry-run contract).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.params import param_spec
from jax.sharding import PartitionSpec as P


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_param_spec_rules():
    mesh = FakeMesh({"data": 16, "model": 16})
    assert param_spec("embed", (256000, 12288), mesh) == P("model", "data")
    assert param_spec("unembed", (12288, 256000), mesh) == P("data", "model")
    assert param_spec("blocks/attn/wq", (64, 12288, 12288), mesh) == \
        P(None, "data", "model")
    assert param_spec("blocks/attn/wo", (64, 12288, 12288), mesh) == \
        P(None, "model", "data")
    assert param_spec("blocks/moe/w_gate", (48, 128, 2048, 768), mesh) == \
        P(None, "model", "data", None)
    assert param_spec("blocks/ln1", (64, 12288), mesh) == P()


def test_param_spec_divisibility_dropped():
    mesh = FakeMesh({"data": 16, "model": 16})
    # whisper vocab 51865 not divisible by 16 -> vocab axis dropped
    spec = param_spec("embed", (51865, 512), mesh)
    assert spec == P(None, "data")


def test_seq_weights_from_b():
    from repro.dist.amb import seq_weights_from_b
    w = seq_weights_from_b(jnp.array([2, 0, 3, 1]), 16, 4)
    want = [1, 1, 0, 0, 0, 0, 0, 0, 1, 1, 1, 0, 1, 0, 0, 0]
    np.testing.assert_array_equal(np.asarray(w), want)


@pytest.mark.slow
def test_exact_train_step_descends_on_mesh():
    """Distributed-step machinery: variable-b masking, sharding, descent.

    Descent is asserted on a FIXED held-out batch (online per-step loss is
    dominated by batch noise) with AdamW; dual-averaging *convergence* is
    covered by core/engine tests on the paper's convex problems, so here we
    only assert the exact-consensus DA path runs and accumulates duals.
    """
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.dist import use_sharding
        from repro.dist.amb import AMBConfig, make_train_step
        from repro.dist.params import tree_shardings
        from repro.data import LMTokenStream, shard_batch
        from repro.models import init_params, lm_loss
        from repro.optim import make_optimizer
        from repro.core.dual_averaging import BetaSchedule
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = smoke_config("qwen2-1.5b")
        opt = make_optimizer("adamw", lr=3e-3)
        stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
        eval_batch = stream.batch(999, 0, 32)
        with use_sharding(mesh):
            params = init_params(jax.random.PRNGKey(0), cfg)
            params = jax.tree.map(jax.device_put, params,
                                  tree_shardings(params, mesh))
            state = opt.init(params)
            step = jax.jit(make_train_step(cfg, opt, mesh, AMBConfig()))
            eval_loss = jax.jit(lambda p: lm_loss(p, cfg, eval_batch)[0])
            e0 = float(eval_loss(params))
            for i in range(30):
                batch = shard_batch(stream.batch(0, i, 8), mesh)
                b = jnp.array([2, 1, 2, 2], jnp.int32)   # variable minibatch
                params, state, m = step(params, state, batch, b)
            e1 = float(eval_loss(params))
        assert m["global_batch"] == 7
        print("E0", e0, "E1", e1)
        assert e1 < e0 - 0.05

        # dual-averaging exact path: runs on mesh, z accumulates, loss finite
        da = make_optimizer("dual_averaging",
                            beta=BetaSchedule(k=20.0, mu=1.0, scale=50.0))
        with use_sharding(mesh):
            params = init_params(jax.random.PRNGKey(0), cfg)
            params = jax.tree.map(jax.device_put, params,
                                  tree_shardings(params, mesh))
            state = da.init(params)
            step = jax.jit(make_train_step(cfg, da, mesh, AMBConfig()))
            for i in range(3):
                batch = shard_batch(stream.batch(0, i, 8), mesh)
                b = jnp.array([2, 1, 2, 2], jnp.int32)
                params, state, m = step(params, state, batch, b)
        assert jnp.isfinite(m["loss"])
        znorm = sum(float(jnp.linalg.norm(z.astype(jnp.float32)))
                    for z in jax.tree.leaves(state["z"]))
        print("ZN", znorm)
        assert znorm > 0
    """)
    assert "E0" in out and "ZN" in out


@pytest.mark.slow
def test_gossip_train_step_on_mesh():
    """Decentralized gossip path correctness on a mesh:

    1. finite rounds (r=4): runs, weighted global-batch accounting is right,
       and per-worker replicas genuinely differ (eps > 0, Lemma 1 regime);
    2. many rounds (r=60): per-worker duals collapse to consensus (spread
       ~ 0) AND match the exact-consensus (eps = 0) path's dual after one
       step — the paper's eq. (4) weighted average, two implementations.
    """
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.dist import use_sharding
        from repro.dist.amb import (AMBConfig, make_gossip_train_step,
                                    make_train_step)
        from repro.dist.params import tree_shardings
        from repro.data import LMTokenStream, shard_batch
        from repro.models import init_params
        from repro.optim import make_optimizer
        from repro.core.dual_averaging import BetaSchedule
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = smoke_config("qwen2-1.5b")
        beta = BetaSchedule(k=20.0, mu=1.0, scale=50.0)
        stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
        batch0 = stream.batch(0, 0, 8)
        b = jnp.array([2, 1, 2, 2], jnp.int32)

        with use_sharding(mesh):
            params = init_params(jax.random.PRNGKey(0), cfg)
            params = jax.tree.map(jax.device_put, params,
                                  tree_shardings(params, mesh))

            # exact-consensus reference: dual after one step
            opt = make_optimizer("dual_averaging", beta=beta)
            step = jax.jit(make_train_step(cfg, opt, mesh, AMBConfig()))
            _, st_e, m_e = step(params, opt.init(params),
                                shard_batch(batch0, mesh), b)

            # (1) finite rounds
            amb4 = AMBConfig(consensus="gossip", gossip_rounds=4, beta=beta)
            init_state, gstep = make_gossip_train_step(cfg, mesh, amb4)
            gs, m = jax.jit(gstep)(init_state(params),
                                   shard_batch(batch0, mesh), b)
            assert float(m["global_batch"]) == 7.0
            assert jnp.isfinite(m["loss"])
            spread4 = max(float(jnp.std(z.astype(jnp.float32), axis=0).max())
                          for z in jax.tree.leaves(gs["z"]))
            print("spread4", spread4)
            assert spread4 > 1e-7   # finite-round error is real

            # (2) many rounds -> consensus == exact path
            amb60 = AMBConfig(consensus="gossip", gossip_rounds=60, beta=beta)
            init_state, gstep = make_gossip_train_step(cfg, mesh, amb60)
            gs, _ = jax.jit(gstep)(init_state(params),
                                   shard_batch(batch0, mesh), b)
            spread60 = max(float(jnp.std(z.astype(jnp.float32), axis=0).max())
                           for z in jax.tree.leaves(gs["z"]))
            print("spread60", spread60)
            assert spread60 < 1e-6
            err = max(float(jnp.max(jnp.abs(ze - zg[0])))
                      for ze, zg in zip(jax.tree.leaves(st_e["z"]),
                                        jax.tree.leaves(gs["z"])))
            print("err", err)
            assert err < 2e-3   # bf16 grads + reduction-order differences
    """)
    assert "spread60" in out and "err" in out


@pytest.mark.slow
def test_dryrun_small_mesh_subprocess():
    """run_one end-to-end on a reduced mesh: proves the dry-run machinery."""
    out = run_sub("""
        import os
        os.environ["REPRO_DRYRUN_XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_DRYRUN_MESH"] = "4,2"
        from pathlib import Path
        from repro.launch.dryrun import run_one
        rec = run_one("whisper-base", "train_4k", False,
                      Path("/tmp/dryrun_test"))
        assert rec["hlo_flops"] > 0
        assert rec["collectives"]["traffic_bytes"] >= 0
        assert rec["dominant_term"] in ("compute", "memory", "collective")
        print("OK", rec["dominant_term"], rec["depth_extrapolated"])
    """, devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_gossip_train_step_multi_pod():
    """3-axis mesh (pod, data, model): gossip consensus spans pod x data
    jointly — the multi-pod worker set — and batch accounting is global."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import smoke_config
        from repro.dist import use_sharding
        from repro.dist.amb import (AMBConfig, make_gossip_train_step,
                                    num_workers)
        from repro.data import LMTokenStream, shard_batch
        from repro.models import init_params
        from repro.core.dual_averaging import BetaSchedule
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = smoke_config("qwen2-1.5b")
        assert num_workers(mesh) == 4
        beta = BetaSchedule(k=20.0, mu=1.0, scale=50.0)
        amb = AMBConfig(consensus="gossip", gossip_rounds=40, beta=beta)
        init_state, step = make_gossip_train_step(cfg, mesh, amb)
        stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
        with use_sharding(mesh):
            params = init_params(jax.random.PRNGKey(0), cfg)
            state = init_state(params)
            b = jnp.array([2, 1, 2, 0], jnp.int32)   # one idle worker
            batch = shard_batch(stream.batch(0, 0, 8), mesh)
            state, m = jax.jit(step)(state, batch, b)
        assert float(m["global_batch"]) == 5.0
        assert jnp.isfinite(m["loss"])
        # 40 rounds over a 4-worker ring -> near-consensus across pods
        spread = max(float(jnp.std(z.astype(jnp.float32), axis=0).max())
                     for z in jax.tree.leaves(state["z"]))
        print("spread", spread)
        assert spread < 1e-5
    """)
    assert "spread" in out
