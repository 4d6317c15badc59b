"""End-to-end driver: train a ~100M-parameter LM under AMB for a few hundred
steps on simulated devices (deliverable (b) end-to-end example).

The "demo-100m" config is a 12L/512d/32k-vocab decoder (~84M params).  Each
step draws straggler compute times, fixes the AMB budget T (Lemma 6), masks
each worker's unfinished sequences, and applies weighted consensus + dual
averaging — the full production path (pjit, FSDP x TP sharding) at CPU scale.

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --steps 20 --tiny   # CI-sized
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse          # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.dual_averaging import BetaSchedule           # noqa: E402
from repro.core.stragglers import (ShiftedExponential,       # noqa: E402
                                   amb_batch_sizes)
from repro.data import LMTokenStream, shard_batch            # noqa: E402
from repro.dist import use_sharding                          # noqa: E402
from repro.dist.amb import AMBConfig, make_train_step, num_workers  # noqa: E402
from repro.dist.params import tree_shardings                 # noqa: E402
from repro.launch.mesh import make_mesh                      # noqa: E402
from repro.metrics import MetricsLogger                      # noqa: E402
from repro.models import init_params, param_count            # noqa: E402
from repro.models.common import ArchConfig                   # noqa: E402
from repro.optim import make_optimizer                       # noqa: E402

DEMO_100M = ArchConfig(
    name="demo-100m", family="dense", num_layers=12, d_model=512,
    num_heads=8, num_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32768,
    qk_norm=True, q_chunk=128, kv_chunk=128,
    mxu_f32_accum=False)   # executes on CPU (no BF16xBF16=F32 dot thunk)

DEMO_TINY = ArchConfig(
    name="demo-tiny", family="dense", num_layers=2, d_model=128,
    num_heads=4, num_kv_heads=2, head_dim=32, d_ff=512, vocab_size=2048,
    q_chunk=64, kv_chunk=64, mxu_f32_accum=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch-per-worker", type=int, default=2)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = DEMO_TINY if args.tiny else DEMO_100M
    ndev = len(jax.devices())
    data = 4 if ndev >= 8 else max(1, ndev)
    model = 2 if ndev >= 8 else 1
    mesh = make_mesh((data, model), ("data", "model"))
    n = num_workers(mesh)
    gb = n * args.batch_per_worker

    key = jax.random.PRNGKey(args.seed)
    straggler = ShiftedExponential(lam=2 / 3, zeta=1.0,
                                   b_ref=args.batch_per_worker)
    t_budget = (1.0 + n / gb) * straggler.mean_batch_time()   # Lemma 6
    opt = make_optimizer("dual_averaging",
                         beta=BetaSchedule(k=30.0, mu=1.0, scale=60.0))
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                           seed=args.seed)
    logger = MetricsLogger("artifacts/train_lm_demo.jsonl")

    with use_sharding(mesh):
        params = init_params(key, cfg)
        print(f"model: {cfg.name}  params={param_count(params):,}  "
              f"mesh=({data}x{model})  workers={n}  global_batch={gb}")
        params = jax.tree.map(jax.device_put, params,
                              tree_shardings(params, mesh))
        opt_state = opt.init(params)
        step_fn = jax.jit(make_train_step(cfg, opt, mesh, AMBConfig()))

        wall = 0.0
        for step in range(args.steps):
            times = straggler.per_gradient_times(
                jax.random.fold_in(key, 7000 + step), n,
                args.batch_per_worker)
            b = amb_batch_sizes(times, t_budget)
            wall += t_budget + 0.3 * t_budget
            batch = shard_batch(stream.batch(0, step, gb), mesh)
            t0 = time.time()
            params, opt_state, m = step_fn(params, opt_state, batch, b)
            loss = float(m["loss"])
            logger.log(step, loss=loss, b=float(m["global_batch"]),
                       sim_wall=wall, step_s=time.time() - t0)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d}  loss {loss:.4f}  "
                      f"b(t)={int(m['global_batch'])}/{gb}  "
                      f"({time.time() - t0:.1f}s/step)")
    logger.close()
    print("done — metrics in artifacts/train_lm_demo.jsonl")


if __name__ == "__main__":
    main()
