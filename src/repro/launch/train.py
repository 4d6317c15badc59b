"""AMB training driver: a thin CLI adapter over :class:`repro.api.AMBSession`.

Every flag maps onto one of the four session specs
(:class:`repro.api.TrainSpec` / :class:`repro.api.ClockSpec` /
:class:`repro.api.ConsensusSpec` / :class:`repro.api.ControllerSpec`);
the session owns the mesh, the clock (measured by default, ``--sim-clock``
restores the paper-evaluation simulated clock — see
:mod:`repro.api.clock`), the consensus strategy, the epoch driver, and —
under ``--controller`` — the online self-tuning loop over budget,
staleness, and batch target.  This driver only selects the input source
and checkpoints; batches flow through the session's prefetched data
plane (``session.run`` — per-worker stream shards, background host
build + device put, ``--prefetch`` buffers deep), and per-epoch metrics
(and controller decisions) are written by the session itself via
``metrics_path``.

Example (8 simulated devices, reduced qwen2, async torus gossip with two
in-flight consensus payloads, self-tuning on):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 50 --data 4 --model 2 --consensus gossip --graph torus \
      --async --staleness 2 --controller
(``--pipeline`` is the staleness-1 special case; ``--restore DIR``
resumes a saved session, controller state included.)

Fault tolerance: ``--churn RATE`` drives the run through a
:class:`repro.faults.PoissonChurn` model (workers leave at RATE per
epoch, rejoin at ``--churn-rejoin``; worker 0 is pinned up) — membership
changes flow through the session's elastic ``set_active`` path, so
consensus re-lays onto the survivors' ring/torus.  Pair with
``--redundancy RHO`` to keep the gradient estimate unbiased while
replica holders are down.
"""
from __future__ import annotations

import argparse

from ..api import (AMBSession, ClockSpec, ConsensusSpec, ControllerSpec,
                   TrainSpec)
from .cache import use_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    TrainSpec.add_cli_args(ap)
    ClockSpec.add_cli_args(ap)
    ConsensusSpec.add_cli_args(ap)
    ControllerSpec.add_cli_args(ap)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--prefetch", type=int, default=2,
                    help="data-plane prefetch depth (batches built + "
                         "device-put ahead of the step; 0 = synchronous)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--restore", default=None, metavar="DIR",
                    help="resume from an AMBSession.save directory "
                         "(params, opt/dual state, and step counter; the "
                         "saved specs override the spec flags)")
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--churn", type=float, default=0.0, metavar="RATE",
                    help="Poisson churn: per-epoch leave rate for each "
                         "unpinned worker (0 = off); membership changes "
                         "rebuild consensus over the survivors")
    ap.add_argument("--churn-rejoin", type=float, default=0.5,
                    help="per-epoch rejoin rate for downed workers")
    ap.add_argument("--churn-seed", type=int, default=0,
                    help="fault-trajectory seed (independent of --seed)")
    args = ap.parse_args(argv)
    use_compile_cache()

    faults = None
    if args.churn > 0.0:
        from ..faults import PoissonChurn
        faults = PoissonChurn(leave_rate=args.churn,
                              rejoin_rate=args.churn_rejoin,
                              seed=args.churn_seed)

    metrics_path = args.metrics
    try:
        if args.restore:
            session = AMBSession.restore(args.restore,
                                         metrics_path=metrics_path)
            if session.metrics is None:     # keep the arch-derived default
                from ..metrics import MetricsLogger
                session.metrics = MetricsLogger(
                    f"artifacts/train_{session.train.arch}_"
                    f"{session.train.mode}.jsonl")
        else:
            train = TrainSpec.from_args(args)
            session = AMBSession(
                train, ClockSpec.from_args(args),
                ConsensusSpec.from_args(args),
                ControllerSpec.from_args(args),
                metrics_path=metrics_path
                or f"artifacts/train_{train.arch}_{train.mode}.jsonl")
    except ValueError as e:
        raise SystemExit(str(e))
    # session.run draws epochs at the session's own absolute counter, so
    # a restored run continues both the data order and the logged step
    # axis where the saved one stopped instead of re-emitting steps 0..N
    last = session.steps_done + args.steps - 1

    def on_step(step, m):
        if "action" in m:
            print(f"step {step:4d} controller: {m['action']['reason']}")
        if step % 10 == 0 or step == last:
            print(f"step {step:4d} loss {m['loss']:.4f} "
                  f"b(t)={m['global_batch']:.0f} "
                  f"T={m['budget_s']:.3f}s "
                  f"sim_wall={m['sim_wall_s']:.1f}s")

    # the prefetched data plane: per-worker shards of the arch's LM
    # stream (worker i draws stream node i), host build + device put
    # overlapped with the previous epoch's step
    m = session.run(args.steps, prefetch=args.prefetch, on_step=on_step,
                    faults=faults)
    loss = None if m is None else m["loss"]   # zero-step run: no-op
    session.flush()      # settle in-flight gossip (pipelined mode)
    if args.ckpt_dir:
        session.save(args.ckpt_dir)
        print(f"checkpoint saved to {args.ckpt_dir}")
    session.close()
    return loss


if __name__ == "__main__":
    main()
