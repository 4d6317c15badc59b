"""Benchmark the repro.dist train steps: exact-psum vs gossip consensus.

All steps are built through the Session API's
:func:`repro.api.protocol.build_protocol` — the same uniform
TrainState/epoch-driver surface the launchers use.  Times, on a
host-device mesh (forced device count, CPU-friendly smoke config):

  * the exact-consensus protocol step (dual averaging),
  * the gossip protocol step at several round counts r,
  * the ``gossip_combine`` K-way weighted combine through the
    :mod:`repro.kernels.router` hot path (compiled Pallas on TPU/GPU,
    jnp reference on CPU) vs the interpret-mode oracle, at model-sized
    message widths,
  * the ``dist_dataplane`` section: (a) steps/s of the synchronous
    build-put-step loop vs the prefetched data plane
    (:class:`repro.data.Prefetcher`) at several host-batch costs
    (0/0.5/1/2x the measured step time, modeled by
    :class:`repro.data.CostedSource`); (b) TrainState donation
    accounting — live-buffer counts stay flat across steps and the
    pre-step state's buffers are actually freed, for all four epoch
    drivers; (c) the kernel routing decision and its delta vs the
    interpret oracle,
  * the ``dist_pipelined`` section: (a) the staleness-1 pipelined step vs
    the sequential gossip protocol — "sequential" meaning the paper's two
    distinct windows, a compute-phase dispatch followed by a
    consensus-phase dispatch, which is exactly the structure pipelining
    absorbs (the fused one-program sequential step is reported too, for
    transparency; on CPU hosts the two phases share the same cores, so
    the measurable win is the eliminated message materialization +
    dispatch, while on TPU the ICI rounds hide under the backward pass);
    (b) the 2x16x16 dry-run mesh cost model — lower+compile FLOPs and
    cross-pod collective-permute bytes per gossip round for each
    consensus strategy vs the exact all-reduce step (subprocess with 512
    forced host devices; compile only, never executed),
  * the ``dist_async`` section: simulated epoch wall time vs staleness D
    for the AMB-DG async driver against the sequential and pipelined
    schedules, under the paper's straggler clock with a long consensus
    window (T_c > T) — the regime bounded staleness reclaims,
  * the ``dist_controller`` section: the online self-tuning controller
    (``--controller``; :mod:`repro.control`) vs static (D, budget)
    settings under a *shifting* straggler clock — the per-gradient rate
    jumps 3x mid-run, the statics keep their launch tuning, the
    controller re-solves Lemma 6 and retunes D from telemetry,
  * the ``dist_churn`` section: graceful degradation under Poisson
    worker churn (:mod:`repro.faults`) — loss trajectory and epoch wall
    for coded (``--redundancy``; :mod:`repro.dist.redundancy`) vs
    uncoded fleets against the no-churn baselines, plus the
    survivor-relayout fast-path check (churned ring combines compile to
    collective-permutes, never the dense ``P @ m`` fallback) and the
    relayout-vs-dense combine timing,
  * the ``dist_serve`` section: continuous batching
    (:mod:`repro.serve`) vs static rebatching on one staggered-arrival
    workload, with background AMB fine-tune epochs absorbed into the
    round budget — per-op costs are *measured* on the live engine, then
    both lanes replay deterministically on a
    :class:`repro.serve.SyntheticClock` so the comparison isolates the
    scheduling policy; reports TTFT/TPOT p50/p99, tokens/s, and the
    fine-tune loss trajectory in one run.

Writes ``artifacts/bench/BENCH_dist.json`` and prints the
``name,us_per_call,derived`` CSV rows (benchmarks/run.py conventions).

    PYTHONPATH=src python -m benchmarks.dist_step --steps 10
"""
from __future__ import annotations

import os
import subprocess
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

from repro.api.protocol import build_protocol               # noqa: E402
from repro.configs import smoke_config                      # noqa: E402
from repro.core.dual_averaging import BetaSchedule          # noqa: E402
from repro.data import LMTokenStream, put_batch             # noqa: E402
from repro.dist import use_sharding                         # noqa: E402
from repro.dist.amb import AMBConfig, num_workers           # noqa: E402
from repro.dist.params import tree_shardings                # noqa: E402
from repro.kernels import ref                               # noqa: E402
from repro.kernels.gossip_combine import gossip_combine_pallas  # noqa: E402
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.models import init_params                        # noqa: E402
from repro.optim import make_optimizer                      # noqa: E402


def _time_it(fn, *args, iters: int = 5) -> float:
    """Median-free simple timing: best of ``iters`` after one warmup."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def bench_train_steps(arch: str, steps: int, seq_len: int) -> dict:
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = smoke_config(arch)
    n = num_workers(mesh)
    beta = BetaSchedule(k=20.0, mu=1.0, scale=50.0)
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=seq_len,
                           seed=0)
    b = jnp.array([2, 1, 2, 2], jnp.int32)
    out: dict = {"arch": arch, "mesh": "4x2", "workers": n,
                 "seq_len": seq_len, "steps_timed": steps}

    with use_sharding(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.tree.map(jax.device_put, params,
                              tree_shardings(params, mesh))
        batch = put_batch(stream.batch(0, 0, 2 * n), mesh)

        opt = make_optimizer("dual_averaging", beta=beta)
        proto = build_protocol(cfg, mesh, AMBConfig(), optimizer=opt)
        step = jax.jit(proto.step)
        st = proto.init(params)
        t = _time_it(lambda: step(st, batch, b), iters=steps)
        out["exact_step_s"] = t

        for r in (4, 16, 60):
            amb = AMBConfig(consensus="gossip", gossip_rounds=r, beta=beta)
            gproto = build_protocol(cfg, mesh, amb)
            gs = gproto.init(params)
            gstep_j = jax.jit(gproto.step)
            out[f"gossip_r{r}_step_s"] = _time_it(
                lambda: gstep_j(gs, batch, b), iters=steps)

    out["gossip_r4_overhead"] = out["gossip_r4_step_s"] / out["exact_step_s"]
    return out


def bench_gossip_combine(widths=(1 << 16, 1 << 20)) -> dict:
    """K-way weighted combine: the routed hot path vs the interpret oracle.

    ``routed_s`` is the headline — what :func:`repro.kernels.ops.
    gossip_combine` actually executes after :mod:`repro.kernels.router`
    picks an implementation (compiled Pallas on TPU/GPU, the compiled
    jnp reference on CPU).  The interpret-mode Pallas timing is kept as
    a diagnostic only: it emulates the TPU grid step by step and must
    never be a production path.
    """
    from repro.kernels import ops as kops
    from repro.kernels import router
    routed = router.resolve()
    out: dict = {"k": 3, "backend": jax.default_backend(),
                 "routed_impl": routed,
                 "note": "routed_s = the ops.gossip_combine hot path "
                         "(router decision above); pallas_interpret_s "
                         "is the grid-emulation oracle, diagnostic only"}
    for nmsg in widths:
        key = jax.random.PRNGKey(0)
        msgs = jax.random.normal(key, (3, nmsg), jnp.float32)
        w = jnp.asarray([0.5, 0.25, 0.25], jnp.float32)
        routed_j = jax.jit(kops.gossip_combine)
        t_routed = _time_it(routed_j, msgs, w)
        ref_j = jax.jit(ref.gossip_combine_ref)
        t_ref = _time_it(ref_j, msgs, w)
        t_pal = _time_it(
            lambda: gossip_combine_pallas(msgs, w, interpret=True))
        got = gossip_combine_pallas(msgs, w, interpret=True)
        want = routed_j(msgs, w)
        err = float(jnp.max(jnp.abs(got - want)))
        out[f"n{nmsg}"] = {"routed_s": t_routed, "jnp_ref_s": t_ref,
                           "pallas_interpret_s": t_pal,
                           "interpret_slowdown_vs_routed": t_pal / t_routed,
                           "max_abs_err": err}
    return out


def bench_dataplane(arch: str, steps: int, seq_len: int,
                    cost_factors=(0.0, 0.5, 1.0, 2.0)) -> dict:
    """The step-time critical path: prefetch overlap, donation, routing.

    (a) **Prefetch overlap** — steps/s of the synchronous loop (build
    the host batch, ``put_batch``, then step — the pre-dataplane
    behavior, ``session.run(prefetch=0)``) vs the prefetched data plane
    (``prefetch=2``: a background thread double-buffers host build +
    device put ahead of the consumer), at host-batch costs of
    0/0.5/1/2x the measured bare step time.  The cost is modeled by
    :class:`repro.data.CostedSource` as a GIL-releasing sleep (an
    I/O-bound input path), so the overlap measured here is the overlap
    the thread actually achieves.  At cost ~ step time the sync loop
    pays build + step serially while the prefetched loop hides the
    build entirely — the acceptance regime.

    (b) **Donation accounting** — for each of the four epoch drivers:
    step twice, then check the process-wide live-buffer count stays
    flat across further steps and every leaf of the pre-step TrainState
    was actually freed (``donate_argnums=0`` aliasing in effect — the
    old iterate's buffers are reused, not shadowed).

    (c) **Kernel routing** — the router's decision for this backend
    (the hot path never runs interpret-mode Pallas on CPU).
    """
    from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
    from repro.data import CostedSource
    from repro.kernels import router

    train = TrainSpec(arch=arch, smoke=True, seq_len=seq_len,
                      batch_per_worker=2, data=4, model=2)
    out: dict = {"arch": arch, "mesh": "4x2", "seq_len": seq_len,
                 "steps_timed": steps, "prefetch_depth": 2}

    session = AMBSession(train, ClockSpec(kind="simulated"),
                         ConsensusSpec())
    source = session.batch_source()
    session.run(2, source)                     # compile + warm the plane
    t0 = time.perf_counter()
    session.run(steps, source, prefetch=0)
    bare_step_s = (time.perf_counter() - t0) / steps
    out["bare_step_s"] = bare_step_s

    sweep = {}
    for f in cost_factors:
        costed = CostedSource(source, f * bare_step_s)
        t0 = time.perf_counter()
        session.run(steps, costed, prefetch=0)
        t_sync = (time.perf_counter() - t0) / steps
        t0 = time.perf_counter()
        session.run(steps, costed, prefetch=2)
        t_pre = (time.perf_counter() - t0) / steps
        sweep[f"cost_{f:g}x"] = {
            "host_batch_cost_s": f * bare_step_s,
            "sync_steps_per_s": 1.0 / t_sync,
            "prefetched_steps_per_s": 1.0 / t_pre,
            "speedup": t_sync / t_pre,
        }
    out["overlap"] = sweep

    donation = {}
    for label, kw in (("exact", {}),
                      ("gossip", dict(consensus="gossip", graph="ring")),
                      ("pipelined", dict(consensus="gossip", graph="ring",
                                         pipeline=True)),
                      ("async_D2", dict(consensus="gossip", graph="ring",
                                        async_epochs=True, staleness=2))):
        s = AMBSession(train, ClockSpec(kind="simulated"),
                       ConsensusSpec(**kw))
        src = s.batch_source()
        s.run(2, src)                          # compile outside the count
        live_before = len(jax.live_arrays())
        old = s.state
        s.run(2, src)
        live_after = len(jax.live_arrays())
        freed = all(leaf.is_deleted()
                    for leaf in jax.tree.leaves(old))
        donation[label] = {
            "live_arrays_before": live_before,
            "live_arrays_after": live_after,
            "live_arrays_flat": bool(live_after <= live_before),
            "old_state_freed": bool(freed),
        }
        del old, s, src
    out["donation"] = donation

    out["kernel_routing"] = {
        "backend": jax.default_backend(),
        "mode": router.mode(),
        "resolved": router.resolve(),
        "interpret_on_hot_path": bool(router.resolve()
                                      == "pallas_interpret"),
    }
    return out


def bench_pipelined(arch: str, steps: int, seq_len: int,
                    rounds=(16, 60)) -> dict:
    """Pipelined step vs the sequential (two-window) gossip protocol.

    The sequential baseline runs the paper's epoch as its two distinct
    windows — a compute-phase program (masked grads -> packed message)
    then a consensus-phase program (gossip -> dual update) — which is how
    an unpipelined system executes T followed by T_c.  The pipelined step
    runs the same consensus *inside* the compute program, against the
    previous epoch's message (staleness 1).
    """
    from repro.dist.amb import (_local_grads, pack_messages,
                                seq_weights_from_b, strategy_from_config,
                                unpack_duals)

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = smoke_config(arch)
    n = num_workers(mesh)
    per = 2
    beta = BetaSchedule(k=20.0, mu=1.0, scale=50.0)
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=seq_len,
                           seed=0)
    b = jnp.array([2, 1, 2, 2], jnp.int32)
    out: dict = {"arch": arch, "mesh": "4x2", "workers": n,
                 "seq_len": seq_len,
                 "note": "sequential = compute-phase dispatch + "
                         "consensus-phase dispatch (the protocol's two "
                         "windows); fused = one-program sequential step"}

    with use_sharding(mesh):
        params = init_params(jax.random.PRNGKey(0), cfg)
        params = jax.tree.map(jax.device_put, params,
                              tree_shardings(params, mesh))
        batch = put_batch(stream.batch(0, 0, per * n), mesh)
        for r in rounds:
            amb = AMBConfig(consensus="gossip", gossip_rounds=r, beta=beta)
            strategy = strategy_from_config(amb, mesh)
            gproto = build_protocol(cfg, mesh, amb)
            gs = gproto.init(params)

            def compute_phase(state, batch, b):
                beta_t = amb.beta(state["t"].astype(jnp.float32) + 1.0)
                sw = seq_weights_from_b(b, n * per, n).reshape(n, per)
                grads, _ = _local_grads(cfg, state, batch, sw, beta_t,
                                        None, n, per)
                bw = jnp.minimum(b, per).astype(jnp.float32)
                return pack_messages(state["z"], grads, n * bw, n)

            def consensus_phase(state, msg):
                return unpack_duals(strategy.combine(msg), state["z"], n)

            cp, sp = jax.jit(compute_phase), jax.jit(consensus_phase)
            msg = cp(gs, batch, b)
            jax.block_until_ready(msg)

            def split_epoch():
                return sp(gs, cp(gs, batch, b))

            t_split = _time_it(split_epoch, iters=steps)
            gj = jax.jit(gproto.step)
            t_fused = _time_it(lambda: gj(gs, batch, b), iters=steps)

            pproto = build_protocol(cfg, mesh, amb, pipeline=True)
            pj = jax.jit(pproto.step)
            ps, _ = pj(pproto.init(params), batch, b)  # warm: in flight
            t_pipe = _time_it(lambda: pj(ps, batch, b), iters=steps)

            out[f"r{r}"] = {
                "sequential_step_s": t_split,
                "sequential_fused_step_s": t_fused,
                "pipelined_step_s": t_pipe,
                "overlap_ratio": t_pipe / t_split,
                "overlap_demonstrated": bool(t_pipe < t_split),
            }
    return out


def bench_async(arch: str, steps: int, seq_len: int,
                stalenesses=(1, 2, 4), comm_time: float = 8.0) -> dict:
    """Epoch wall time vs staleness under the paper's straggler clock.

    Drives an :class:`repro.api.AMBSession` per epoch driver — the
    sequential gossip protocol (two windows: T then T_c), the staleness-1
    pipeline, and the AMB-DG async driver at several staleness values D —
    all under the simulated straggler clock with a deliberately *long*
    consensus window (T_c > T, the regime the paper's fixed windows
    handle worst).  The simulated per-epoch wall time follows the
    protocol schedule: ``T + T_c`` sequential, ``max(T, T_c)`` pipelined,
    ``max(T, T_c / D)`` async — bounded staleness lets one consensus
    spread over D compute windows, so the epoch rate returns to
    compute-bound once ``D >= T_c / T``.  The host-measured step time and
    final loss are reported alongside (same gossip operator and rounds
    everywhere; only the schedule differs).
    """
    from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec

    if steps < 1:
        raise ValueError("bench_async needs --steps >= 1")
    train = TrainSpec(arch=arch, smoke=True, seq_len=seq_len,
                      batch_per_worker=2, data=4, model=2)
    clock = ClockSpec(kind="simulated", comm_time=comm_time)
    out: dict = {"arch": arch, "mesh": "4x2", "seq_len": seq_len,
                 "steps": steps, "comm_time_s": comm_time,
                 "note": "sim_epoch_wall_s: sequential T+T_c, pipelined "
                         "max(T,T_c), async max(T,T_c/D); straggler "
                         "clock draws identical across drivers"}

    def drive(label: str, **spec_kw):
        session = AMBSession(train, clock, ConsensusSpec(
            consensus="gossip", gossip_rounds=4, **spec_kw))
        stream = LMTokenStream(vocab_size=session.cfg.vocab_size,
                               seq_len=seq_len, seed=0)
        best = float("inf")
        for i in range(steps):
            m = session.step(stream.batch(0, i, session.global_batch))
            if i > 0 or steps == 1:        # skip the compile step when
                best = min(best, m["step_s"])   # there is a later one
        session.flush()
        out[label] = {"sim_epoch_wall_s": session.sim_wall / steps,
                      "budget_T_s": m["budget_s"],
                      "host_step_s": best,
                      "final_loss": m["loss"]}

    drive("sequential")
    drive("pipelined", pipeline=True)
    for d in stalenesses:
        drive(f"async_D{d}", async_epochs=True, staleness=d)
    dmax = max(stalenesses)
    out["wall_speedup_async_vs_sequential"] = (
        out["sequential"]["sim_epoch_wall_s"]
        / out[f"async_D{dmax}"]["sim_epoch_wall_s"])
    return out


def bench_controller(arch: str, steps: int, seq_len: int,
                     comm_time: float = 4.0,
                     static_ds=(1, 2, 4)) -> dict:
    """Self-tuning controller vs static (D, budget) under a shifting clock.

    The scenario static tuning cannot win: the cluster's per-gradient
    rate *changes mid-run* (epoch ``switch``: every worker gets ~3x
    faster — a contention burst ending, a thermal cap lifting).  Every
    run uses the async driver and the same deliberately long consensus
    window T_c; the static baselines keep the budget T0 (the Lemma-6
    solve for the *initial* rate) and a fixed staleness D for the whole
    run, while the controller starts from exactly (T0, D=1) and retunes
    from telemetry: after the shift it cuts T toward the new Lemma-6
    solve (rate-limited, so over a few decisions) and raises D as the
    measured ``T_c / T`` ratio climbs — keeping epochs compute-bound at
    the *new* rate.  Per-epoch simulated wall is ``max(T, T_c / D)``
    (see :func:`bench_async`), so a static run pays ``T0`` forever while
    the controller converges to ``~max(T_new, T_c / D_new)``.

    Reports total simulated wall and final loss per config, plus the
    two acceptance booleans: controller wall <= best static wall, and
    controller loss no worse (5% tolerance) than that best-wall static
    run's.
    """
    from repro.api import (AMBSession, ClockSpec, ConsensusSpec,
                           ControllerSpec, TrainSpec)
    from repro.api.clock import SimulatedClock
    from repro.core.stragglers import ShiftedExponential

    epochs = max(3 * steps, 12)
    switch = epochs // 3            # shift early: 2/3 of the run is "after"
    train = TrainSpec(arch=arch, smoke=True, seq_len=seq_len,
                      batch_per_worker=2, data=4, model=2)
    n, bpw = 4, train.batch_per_worker
    slow = ShiftedExponential(lam=2.0 / 3.0, zeta=1.0, b_ref=bpw)
    fast = ShiftedExponential(lam=2.0, zeta=1.0 / 3.0, b_ref=bpw)  # 3x
    t0_budget = (1.0 + n / (n * bpw)) * slow.mean_batch_time()  # Lemma 6

    class _ShiftingClock(SimulatedClock):
        """Simulated clock whose straggler model swaps mid-run."""

        def __init__(self):
            SimulatedClock.__init__(self, slow, n, bpw,
                                    compute_time=t0_budget)
            self._epoch = 0

        def epoch(self, key):
            self.model = slow if self._epoch < switch else fast
            self._epoch += 1
            return (self.model.per_gradient_times(key, self.n, self.bpw),
                    self.budget_t)

    clock_spec = ClockSpec(kind="simulated", comm_time=comm_time,
                           compute_time=t0_budget)
    out: dict = {"arch": arch, "mesh": "4x2", "epochs": epochs,
                 "switch_epoch": switch, "comm_time_s": comm_time,
                 "budget_T0_s": t0_budget,
                 "note": "per-gradient rate shifts 3x faster at "
                         "switch_epoch; statics keep (T0, D) throughout, "
                         "controller retunes from telemetry"}

    def drive(label: str, staleness: int, controller: bool):
        ctl = ControllerSpec(enabled=True, interval=2, warmup=2) \
            if controller else None
        session = AMBSession(
            train, clock_spec,
            ConsensusSpec(consensus="gossip", gossip_rounds=4,
                          async_epochs=True, staleness=staleness),
            ctl)
        session.clock = _ShiftingClock()     # same draws for every config
        stream = LMTokenStream(vocab_size=session.cfg.vocab_size,
                               seq_len=seq_len, seed=0)
        decisions = []
        for i in range(epochs):
            m = session.step(stream.batch(0, i, session.global_batch))
            if "action" in m:
                decisions.append({"epoch": i, **{
                    k: m["action"][k] for k in ("budget", "staleness",
                                                "reason")
                    if m["action"][k] is not None}})
        session.flush()
        out[label] = {"sim_wall_total_s": session.sim_wall,
                      "sim_wall_per_epoch_s": session.sim_wall / epochs,
                      "final_budget_T_s": m["budget_s"],
                      "final_staleness": m["staleness"],
                      "final_loss": m["loss"]}
        if controller:
            out[label]["decisions"] = decisions

    for d in static_ds:
        drive(f"static_D{d}", staleness=d, controller=False)
    drive("controller", staleness=1, controller=True)

    best = min((f"static_D{d}" for d in static_ds),
               key=lambda k: out[k]["sim_wall_total_s"])
    out["best_static"] = best
    out["controller_beats_best_static_wall"] = bool(
        out["controller"]["sim_wall_total_s"]
        <= out[best]["sim_wall_total_s"] * 1.001)
    out["loss_no_worse"] = bool(
        out["controller"]["final_loss"]
        <= out[best]["final_loss"] * 1.05)
    return out


_MULTIPOD_VARIANTS = (("gossip", "torus"), ("gossip_q8", "torus"),
                      ("gossip_q4", "torus"), ("gossip", "ring"))


def multipod_probe(arch: str, seq_len: int) -> dict:
    """(subprocess body) 2x16x16 lower+compile cost model, JSON to stdout.

    Per consensus strategy: compiled cost-analysis FLOPs and the
    collective-permute footprint of one gossip round (the fori_loop body
    appears once in HLO, so the parsed permute bytes *are* per-round),
    vs the exact-consensus all-reduce step.  The analytic per-worker wire
    bytes from ``ConsensusStrategy.wire_bytes_per_round`` are reported
    alongside, and ``permute_bytes_by_dtype`` breaks the permutes down by
    element type — the quantized strategies' planes must show up as u8
    (the optimization barriers in ``QuantizedGossipConsensus`` pin the
    wire; the rounding draws are partitionable-threefry, i.e. shard-local,
    so no u32 RNG resharding rides the interconnect either).
    """
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import InputShape
    from repro.core.dual_averaging import BetaSchedule as BS
    from repro.dist.amb import strategy_from_config
    from repro.launch import specs as S
    from repro.launch.dryrun import _costs
    from repro.launch.mesh import make_production_mesh
    from repro.optim import DualAveragingOpt

    mesh = make_production_mesh(multi_pod=True)
    cfg = smoke_config(arch)
    n = num_workers(mesh)
    beta = BS(k=20.0, mu=1.0, scale=50.0)
    params_sds = S.abstract_params(cfg)
    pspecs = tree_shardings(params_sds, mesh)
    as_in = lambda sds, sh: jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                                 sharding=sh)
    zsh = NamedSharding(mesh, P(("pod", "data")))

    def protocol_state_in(proto, **spec_overrides):
        """Abstract TrainState inputs: structure from the protocol's own
        init (the single source of truth), shardings assigned per key."""
        state_sds = jax.eval_shape(proto.init, params_sds)
        specs = {"t": NamedSharding(mesh, P())}
        for key, sub in state_sds.items():
            if key == "t":
                continue
            specs[key] = spec_overrides.get(
                key, jax.tree.map(lambda s: zsh, sub))
        return jax.tree.map(as_in, state_sds, specs)

    shape = InputShape(name="probe", kind="train", global_batch=n,
                       seq_len=seq_len)
    batch_in = S.train_input_specs(cfg, shape, mesh)
    b_in = S.worker_batch_spec(mesh)
    d_msg = 1 + sum(int(np.prod(p.shape)) for p in
                    jax.tree.leaves(params_sds))

    out: dict = {"mesh": "2x16x16", "chips": 512, "workers": n,
                 "arch": arch, "seq_len": seq_len}
    import time as _t
    for consensus, graph in _MULTIPOD_VARIANTS:
        amb = AMBConfig(consensus=consensus, gossip_rounds=1, graph=graph,
                        beta=beta)
        with use_sharding(mesh):
            gproto = build_protocol(cfg, mesh, amb)
            state_in = protocol_state_in(gproto, w0=pspecs)
            t0 = _t.time()
            lowered = jax.jit(gproto.step).lower(state_in, batch_in, b_in)
            t1 = _t.time()
            c = _costs(lowered.compile())
            t2 = _t.time()
            strategy = strategy_from_config(amb, mesh)
        permute = c["collectives"]["collective-permute"]
        out[f"{consensus}_{graph}"] = {
            "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "hlo_flops": c["flops"],
            "permute_per_round": {"count": permute["count"],
                                  "bytes": permute["bytes"]},
            "permute_bytes_by_dtype": permute["by_dtype"],
            "all_reduce": c["collectives"]["all-reduce"],
            "wire_bytes_per_round_per_worker":
                strategy.wire_bytes_per_round(d_msg),
        }

    opt = DualAveragingOpt()
    with use_sharding(mesh):
        proto = build_protocol(cfg, mesh, AMBConfig(), optimizer=opt)
        opt_specs = tree_shardings(jax.eval_shape(opt.init, params_sds),
                                   mesh)
        exact_state_in = protocol_state_in(proto, params=pspecs,
                                           opt=opt_specs)
        t0 = _t.time()
        lowered = jax.jit(proto.step).lower(exact_state_in, batch_in, b_in)
        t1 = _t.time()
        c = _costs(lowered.compile())
        t2 = _t.time()
    out["exact_allreduce"] = {
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "hlo_flops": c["flops"],
        "permute": c["collectives"]["collective-permute"],
        "all_reduce": c["collectives"]["all-reduce"],
    }
    return out


def bench_churn(arch: str, steps: int, seq_len: int,
                leave_rate: float = 0.35, rejoin_rate: float = 0.5,
                redundancy: int = 2) -> dict:
    """Graceful degradation under Poisson churn: coded vs uncoded.

    Four runs on the 8-way host mesh sharing the same model seed, data
    stream, and straggler draws — {no churn, Poisson churn} x {uncoded,
    coded rho=2} — driven through ``session.run(faults=...)``, i.e. the
    same :class:`repro.faults.FaultInjector` path a launcher uses.  The
    interesting comparison is the *loss trajectory*: the uncoded fleet
    loses every downed worker's shard outright (smaller, noisier
    effective batch), while coded placement lets the surviving replica
    holders re-cover the block with decode weights that keep the
    gradient unbiased — so the coded churned trajectory should track
    the no-churn baseline and the uncoded churned one should trail it.

    Also reports (a) the survivor-relayout fast-path check — the
    compiled combine for a churned ring mask must contain
    collective-permutes and no dense dot, i.e. elastic membership never
    falls back to ``P @ m`` on circulant graphs — and (b) the measured
    combine time of the relayout taps vs the dense masked operator
    (``relayout=False``) on the same survivor mask.
    """
    from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
    from repro.dist import SurvivorTaps, make_strategy
    from repro.faults import FaultInjector, PoissonChurn
    from jax.sharding import NamedSharding, PartitionSpec as P

    epochs = max(steps, 12)
    clock = ClockSpec(kind="simulated")
    model = PoissonChurn(leave_rate=leave_rate, rejoin_rate=rejoin_rate,
                         seed=11)
    out: dict = {"arch": arch, "mesh": "8", "seq_len": seq_len,
                 "epochs": epochs, "leave_rate": leave_rate,
                 "rejoin_rate": rejoin_rate, "redundancy": redundancy,
                 "note": "same seed/stream/straggler draws across runs; "
                         "loss_tail = mean loss over the last half of "
                         "the trajectory"}

    def drive(label: str, rho: int, churn: bool):
        session = AMBSession(
            TrainSpec(arch=arch, smoke=True, seq_len=seq_len,
                      batch_per_worker=2, data=8, redundancy=rho),
            clock, ConsensusSpec(consensus="gossip", gossip_rounds=3))
        injector = FaultInjector(model) if churn else None
        losses: list = []
        session.run(epochs, prefetch=0, faults=injector,
                    on_step=lambda s, m: losses.append(float(m["loss"])))
        out[label] = {
            "losses": losses,
            "loss_tail": sum(losses[epochs // 2:]) / (epochs - epochs // 2),
            "sim_epoch_wall_s": session.sim_wall / epochs,
            "membership_changes": (0 if injector is None
                                   else injector.membership_changes)}
        session.close()

    drive("nochurn_uncoded", 1, churn=False)
    drive("nochurn_coded", redundancy, churn=False)
    drive("churn_uncoded", 1, churn=True)
    drive("churn_coded", redundancy, churn=True)

    # paired trajectory divergence: churned vs no-churn runs share the
    # seed, stream, and straggler draws, so the per-step loss delta is
    # the churn effect with batch-composition noise cancelled
    for coding in ("uncoded", "coded"):
        pairs = zip(out[f"churn_{coding}"]["losses"],
                    out[f"nochurn_{coding}"]["losses"])
        out[f"{coding}_trajectory_divergence"] = (
            sum(abs(a - b) for a, b in pairs) / epochs)
    out["coded_churn_excess"] = (out["churn_coded"]["loss_tail"]
                                 - out["nochurn_coded"]["loss_tail"])
    out["uncoded_churn_excess"] = (out["churn_uncoded"]["loss_tail"]
                                   - out["nochurn_uncoded"]["loss_tail"])

    # estimator fidelity over the same churn trajectory: the gradient
    # estimate is the weight-w_s average of per-sample gradients, so its
    # bias is exactly the deviation of the realized per-sample weights
    # from the ideal all-ones coverage.  Uncoded, a downed worker's
    # block samples get weight 0 (dropped data -> biased estimate);
    # coded, any surviving replica holder re-covers them at weight 1.
    from repro.dist import CodedAssignment, epoch_weights
    n, per = 8, 2
    asg = CodedAssignment(n, redundancy)
    shifts, nodes = asg.shifts(per), asg.data_nodes()
    cov = {"uncoded": [], "coded": []}
    bias = {"uncoded": [], "coded": []}
    for e in range(epochs):
        active = model.fleet(e, n).active.copy()
        if not active.any():
            active[0] = True
        b = jnp.asarray(np.where(active, per, 0), jnp.int32)
        for coding, a in (("uncoded", None), ("coded", asg)):
            sw = np.asarray(epoch_weights(b, n, per, a)[0])
            groups = asg.groups if a is not None else n
            block_w = np.zeros((groups, per))
            for i in range(n):
                g = int(nodes[i]) if a is not None else i
                s0 = int(shifts[i]) if a is not None else 0
                for s in range(per):
                    block_w[g, (s + s0) % per] += sw[i, s]
            cov[coding].append(float((block_w > 0).mean()))
            bias[coding].append(float(np.sqrt(((block_w - 1) ** 2).mean())))
    out["estimator_fidelity"] = {
        "note": "per-sample weight coverage/bias of the decoded "
                "gradient estimate under the churn masks (b_i = per "
                "for survivors); ideal = every sample weighted 1",
        "uncoded_coverage": sum(cov["uncoded"]) / epochs,
        "coded_coverage": sum(cov["coded"]) / epochs,
        "uncoded_weight_rmse": sum(bias["uncoded"]) / epochs,
        "coded_weight_rmse": sum(bias["coded"]) / epochs,
    }
    fid = out["estimator_fidelity"]
    out["coded_holds_estimate"] = bool(
        fid["coded_coverage"] >= fid["uncoded_coverage"]
        and fid["coded_weight_rmse"] <= fid["uncoded_weight_rmse"] + 1e-9)

    # fast-path check + relayout-vs-dense combine timing on one
    # representative churned mask (non-adjacent failures: the mask the
    # dense induced-subgraph operator cannot even express on a ring)
    mask = (True, True, False, True, True, False, True, True)
    mesh = make_mesh((8,), ("data",))
    sh = NamedSharding(mesh, P("data"))
    msgs = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (8, 1 << 16)), sh)
    fast = make_strategy("gossip", 8, rounds=3, graph="ring", active=mask)
    assert isinstance(fast.taps, SurvivorTaps)
    txt = jax.jit(fast.combine, in_shardings=sh, out_shardings=sh).lower(
        jax.ShapeDtypeStruct((8, 1 << 16), jnp.float32)).compile().as_text()
    # the dense P @ m fallback compiles to an all-gather of the full
    # worker axis followed by a dot over it (no permutes); the tap fast
    # path compiles to per-tap collective-permutes with no all-gather —
    # its only dot contracts the K tap weights, not the worker axis
    out["survivor_fast_path"] = {
        "collective_permute_in_hlo": "collective-permute" in txt,
        "dense_gather_in_hlo": "all-gather" in txt,
        "taps_per_round": fast.taps.k,
        "relayout_combine_s": _time_it(
            jax.jit(fast.combine, in_shardings=sh, out_shardings=sh), msgs),
    }
    # the dense fallback needs a connected induced subgraph to exist
    dense = make_strategy("gossip", 8, rounds=3, graph="ring",
                          active=(True,) * 7 + (False,), relayout=False)
    out["survivor_fast_path"]["dense_fallback_combine_s"] = _time_it(
        jax.jit(dense.combine, in_shardings=sh, out_shardings=sh), msgs)
    return out


def bench_serve(arch: str, seq_len: int, n_requests: int = 12,
                slots: int = 4, cache_len: int = 64) -> dict:
    """Continuous batching vs static rebatching, fine-tune interleaved.

    One staggered workload (heterogeneous prompt lengths AND generation
    lengths) served twice: through the :class:`repro.serve.SlotEngine`
    + :class:`repro.serve.ServeScheduler` (continuous admission, slot
    reuse, background AMB fine-tune epochs absorbing idle round budget)
    and through :func:`repro.serve.serve_static` (groups of ``slots``
    barrier on their last arrival, pad to the group max, decode until
    the slowest member finishes).

    Timing protocol: prefill-per-token, decode-round, and train-epoch
    costs are *measured* on the live engine/session first, then both
    lanes replay on a :class:`repro.serve.SyntheticClock` configured
    with those costs — so jit compilation never pollutes TTFT, the
    lanes see identical op prices, and the reported deltas are purely
    the scheduling policy (the same reason the paper reports fixed-time
    epochs, not wall-clock luck).
    """
    import random as _random

    from repro.api import AMBSession, ClockSpec, ConsensusSpec, TrainSpec
    from repro.serve import (AdmissionPolicy, Request, RequestQueue,
                             ServeMetrics, ServeScheduler, SlotEngine,
                             SyntheticClock, serve_static)

    train = TrainSpec(arch=arch, smoke=True, seq_len=seq_len,
                      batch_per_worker=2, data=4, model=2,
                      optimizer="adamw")
    session = AMBSession(train, ClockSpec(kind="simulated"),
                         ConsensusSpec())
    cfg, mesh = session.cfg, session.mesh
    if cfg.family not in ("dense", "vlm"):
        session.close()
        return {"skipped": f"static baseline needs dense/vlm, got "
                           f"{cfg.family}"}

    # -- measure the op costs on the live engine/session ------------------
    probe = SlotEngine(session.params, cfg, slots=slots,
                       cache_len=cache_len, mesh=mesh)
    prefill16 = probe._prefill_fn(16)
    toks16 = jnp.zeros((1, 16), jnp.int32)
    prefill_tok_s = _time_it(
        lambda: prefill16(probe.params, toks16, jnp.int32(15))) / 16.0
    probe.insert(Request(rid=-1, prompt=[1] * 16,
                         max_new_tokens=cache_len - 16))
    probe.decode_round()                       # compile outside the timing
    t0 = time.perf_counter()
    for _ in range(5):
        probe.decode_round()
    decode_round_s = (time.perf_counter() - t0) / 5
    src = session.batch_source()
    session.run(1, src)                        # compile the train step
    t0 = time.perf_counter()
    session.run(1, src, prefetch=0)
    train_epoch_s = time.perf_counter() - t0
    del probe

    costs = dict(prefill_tok_s=prefill_tok_s, decode_round_s=decode_round_s,
                 train_epoch_s=train_epoch_s)
    arrival_gap_s = 10 * decode_round_s
    round_budget_s = max(30 * decode_round_s, 2.5 * train_epoch_s)

    # -- one workload, replayed per lane -----------------------------------
    rng = _random.Random(7)
    prompts = [[rng.randrange(cfg.vocab_size)
                for _ in range(rng.randint(8, 24))]
               for _ in range(n_requests)]
    new_toks = [rng.randint(6, 18) for _ in range(n_requests)]

    def workload():
        return [Request(rid=i, prompt=list(prompts[i]),
                        max_new_tokens=new_toks[i],
                        arrival_s=i * arrival_gap_s)
                for i in range(n_requests)]

    out: dict = {"arch": arch, "mesh": "4x2", "slots": slots,
                 "cache_len": cache_len, "n_requests": n_requests,
                 "measured_costs": costs,
                 "arrival_gap_s": arrival_gap_s,
                 "round_budget_s": round_budget_s,
                 "note": "both lanes replay the same workload on a "
                         "SyntheticClock priced with the measured costs; "
                         "deltas are scheduling policy, not host noise"}

    # fine-tune progress is judged on a *fixed* held-out batch (per-epoch
    # train losses are each on a different minibatch, so their noise —
    # ~0.1 nats here — buries the few-epoch learning signal; the eval
    # batch isolates the parameter movement itself)
    from repro.dist import use_sharding
    from repro.models import lm_loss
    eval_batch = src.batch(10_000)             # off-stream, deterministic
    eval_fn = jax.jit(lambda p, b: lm_loss(p, cfg, b)[0])

    def eval_loss() -> float:
        with use_sharding(mesh):
            return float(eval_fn(session.params, eval_batch))

    out["finetune_eval_loss_before"] = eval_loss()

    # static rebatching lane (initial params; greedy, so the schedule —
    # and therefore every SLO — is independent of the iterate)
    static_reqs = workload()
    static_rep = serve_static(
        session.params, cfg, static_reqs, batch=slots, cache_len=cache_len,
        clock=SyntheticClock(**costs), metrics=ServeMetrics(), mesh=mesh)
    out["static"] = static_rep.summary

    # continuous lane, background fine-tune absorbed into idle budget
    cont_reqs = workload()
    queue = RequestQueue(AdmissionPolicy(cache_len=cache_len))
    for r in cont_reqs:
        queue.push(r)
    engine = SlotEngine(session.params, cfg, slots=slots,
                        cache_len=cache_len, mesh=mesh)
    sched = ServeScheduler(engine, queue, round_budget_s=round_budget_s,
                           clock=SyntheticClock(**costs), session=session,
                           train_epochs=8)
    cont_rep = sched.run()
    out["continuous"] = cont_rep.summary
    out["train_losses"] = sched.metrics.train_losses
    out["finetune_eval_loss_after"] = eval_loss()
    session.close()

    cont, stat = out["continuous"], out["static"]
    out["continuous_beats_static_tokens_per_s"] = bool(
        cont["tokens_per_s"] > stat["tokens_per_s"])
    out["continuous_beats_static_ttft_p99"] = bool(
        cont["ttft_p99_s"] < stat["ttft_p99_s"])
    out["finetune_loss_decreased"] = bool(
        cont_rep.train_epochs >= 1
        and out["finetune_eval_loss_after"]
        < out["finetune_eval_loss_before"])
    return out


def bench_multipod(arch: str, seq_len: int) -> dict:
    """Run :func:`multipod_probe` in a clean 512-device subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.dist_step", "--multipod-probe",
         "--arch", arch, "--seq-len", str(seq_len)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=1800)
    if proc.returncode != 0:
        return {"error": proc.stderr[-2000:]}
    return json.loads(proc.stdout.splitlines()[-1])


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--out", default="artifacts/bench")
    ap.add_argument("--skip-multipod", action="store_true",
                    help="skip the 512-device lower+compile subprocess")
    ap.add_argument("--multipod-probe", action="store_true",
                    help=argparse.SUPPRESS)   # internal subprocess mode
    args = ap.parse_args(argv)

    if args.multipod_probe:
        print(json.dumps(multipod_probe(args.arch, args.seq_len)))
        return {}

    rec = {
        "name": "dist_step",
        "devices": len(jax.devices()),
        "train_steps": bench_train_steps(args.arch, args.steps,
                                         args.seq_len),
        "gossip_combine": bench_gossip_combine(),
        "dist_dataplane": bench_dataplane(args.arch, args.steps,
                                          args.seq_len),
        "dist_pipelined": {
            "overlap": bench_pipelined(args.arch, args.steps,
                                       args.seq_len),
        },
        "dist_async": bench_async(args.arch, args.steps, args.seq_len),
        "dist_controller": bench_controller(args.arch, args.steps,
                                            args.seq_len),
        "dist_churn": bench_churn(args.arch, args.steps, args.seq_len),
        "dist_serve": bench_serve(args.arch, args.seq_len),
    }
    if not args.skip_multipod:
        rec["dist_pipelined"]["multipod_2x16x16"] = bench_multipod(
            args.arch, args.seq_len)
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    (outdir / "BENCH_dist.json").write_text(json.dumps(rec, indent=2))

    ts = rec["train_steps"]
    print("name,us_per_call,derived")
    print(f"dist_exact_step,{ts['exact_step_s'] * 1e6:.0f},1.0")
    for r in (4, 16, 60):
        print(f"dist_gossip_r{r}_step,{ts[f'gossip_r{r}_step_s'] * 1e6:.0f},"
              f"{ts[f'gossip_r{r}_step_s'] / ts['exact_step_s']:.2f}")
    dp = rec["dist_dataplane"]
    for label, row in dp["overlap"].items():
        print(f"dist_dataplane_{label},"
              f"{1e6 / row['prefetched_steps_per_s']:.0f},"
              f"{row['speedup']:.3f}")
    for r, row in rec["dist_pipelined"]["overlap"].items():
        if not isinstance(row, dict):
            continue
        print(f"dist_pipelined_{r}_step,{row['pipelined_step_s'] * 1e6:.0f},"
              f"{row['overlap_ratio']:.3f}")
    seq_wall = rec["dist_async"]["sequential"]["sim_epoch_wall_s"]
    for label, row in rec["dist_async"].items():
        if not (isinstance(row, dict) and "sim_epoch_wall_s" in row):
            continue
        print(f"dist_async_{label},{row['sim_epoch_wall_s'] * 1e6:.0f},"
              f"{seq_wall / row['sim_epoch_wall_s']:.3f}")
    ctl = rec["dist_controller"]
    best_wall = ctl[ctl["best_static"]]["sim_wall_per_epoch_s"]
    for label, row in ctl.items():
        if not (isinstance(row, dict) and "sim_wall_per_epoch_s" in row):
            continue
        print(f"dist_controller_{label},"
              f"{row['sim_wall_per_epoch_s'] * 1e6:.0f},"
              f"{best_wall / row['sim_wall_per_epoch_s']:.3f}")
    ch = rec["dist_churn"]
    for label in ("nochurn_uncoded", "nochurn_coded", "churn_uncoded",
                  "churn_coded"):
        row = ch[label]
        print(f"dist_churn_{label},{row['sim_epoch_wall_s'] * 1e6:.0f},"
              f"{row['loss_tail']:.4f}")
    fid = ch["estimator_fidelity"]
    for coding in ("uncoded", "coded"):
        print(f"dist_churn_{coding}_coverage,0,"
              f"{fid[f'{coding}_coverage']:.4f}")
    fp = ch["survivor_fast_path"]
    print(f"dist_churn_relayout_combine,{fp['relayout_combine_s'] * 1e6:.0f},"
          f"{fp['dense_fallback_combine_s'] / fp['relayout_combine_s']:.3f}")
    sv = rec["dist_serve"]
    if "skipped" not in sv:
        for lane in ("continuous", "static"):
            row = sv[lane]
            print(f"dist_serve_{lane},{row['span_s'] * 1e6:.0f},"
                  f"{row['tokens_per_s']:.1f}")
        print(f"dist_serve_ttft_p99,{sv['continuous']['ttft_p99_s'] * 1e6:.0f},"
              f"{sv['static']['ttft_p99_s'] / sv['continuous']['ttft_p99_s']:.3f}")
        print(f"dist_serve_finetune_epochs,{len(sv['train_losses'])},"
              f"{1.0 if sv['finetune_loss_decreased'] else 0.0}")
    print(f"[ok] wrote {outdir / 'BENCH_dist.json'}")
    return rec


if __name__ == "__main__":
    main()
