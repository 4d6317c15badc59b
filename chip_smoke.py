#!/usr/bin/env python3
"""Bring-up check on a TPU: AMB training and slot serving at qwen2-1.5b widths.

One process, every phase, no fallback:

  python chip_smoke.py                # one chip: train + dual-update check
                                      # + serve, published widths, cut depth
  python chip_smoke.py --four-chips   # four chips: exact vs ring-gossip step
                                      # on a 4x1 (data, model) mesh

Without a TPU (or with ``REPRO_KERNELS`` forcing a non-compiled kernel
route) it exits non-zero before any phase runs.  The last line of a passing
run is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
each phase prints its numbers on the lines before it.  Data comes from
``--seed`` through the session's token stream; nothing is read from disk.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.api import (AMBSession, ClockSpec, ConsensusSpec,  # noqa: E402
                       TrainSpec)
from repro.configs import get_config                         # noqa: E402
from repro.dist import use_sharding                          # noqa: E402
from repro.kernels import ops as kops, router                # noqa: E402
from repro.launch.cache import use_compile_cache             # noqa: E402
from repro.launch.mesh import make_mesh                      # noqa: E402
from repro.serve import SlotEngine, static_generate, synthetic_requests  # noqa: E402,E501

ARCH = "qwen2-1.5b"
# Deepest cut of the published 28 layers whose train step fits one v5e's
# 15.75 GiB with headroom: the step compiled for a described v5e needs
# ~10.5 GiB at 8 layers and ~12.2 GiB at 12 (bf16 weights, f32 dual and
# anchor, gradients, activations at 8 x 256 tokens).
LAYERS = 12
# The four-chip phase runs the published widths at one layer (514M
# parameters): the ring-gossip step holds each worker's f32 dual, its
# message, the two neighbour views and the combined output, ~11.7 GiB per
# chip as compiled for a described v5e:2x2.
FOUR_CHIP_LAYERS = 1
STEPS = 5
SEQ_LEN = 256
BATCH_PER_WORKER = 8
# |pallas - ref| / (1 + |ref|) for the f32 prox: a few f32 ulps.
DUAL_UPDATE_TOL = 1e-6
# the stepped bf16 parameters against the f32 reference prox: one bf16 ulp.
PARAM_TOL = 2.0 ** -7
# node-averaged gossip primal vs the exact-consensus primal, relative to the
# parameter scale: one bf16 ulp of the update path.
PRIMAL_TOL = 2.0 ** -7
# every worker's dual after a gossip epoch on the routed kernels vs the jnp
# reference route, |z - z_ref| / (1 + |z_ref|): f32 rounding over the
# rounds' multiply-adds and the eq.-6 normalisation.
GOSSIP_TOL = 1e-5


class CompileTimer:
    """Seconds JAX spent tracing, lowering and compiling since creation."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration


def _log(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


@contextlib.contextmanager
def _kernels(mode: str):
    """Route the kernels traced inside through ``mode``."""
    prev = router.mode()
    router.set_mode(mode)
    try:
        yield
    finally:
        router.set_mode(prev)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def train_phase(cfg, *, steps: int = STEPS, seq_len: int = SEQ_LEN,
                batch_per_worker: int = BATCH_PER_WORKER,
                seed: int = 0) -> tuple[AMBSession, dict]:
    """``steps`` exact-consensus dual-averaging epochs through
    ``AMBSession.run`` on a 1x1 mesh, measured clock.  Returns the session
    and per-epoch lists: ``loss``, ``global_batch`` (the b(t) the clock
    granted) and ``step_s`` (host clock around ``block_until_ready`` of
    the whole TrainState; the first includes the compile)."""
    session = AMBSession(
        TrainSpec(arch=ARCH, data=1, model=1, optimizer="dual_averaging",
                  seq_len=seq_len, batch_per_worker=batch_per_worker,
                  seed=seed),
        ClockSpec(kind="measured"), ConsensusSpec(consensus="exact"),
        cfg=cfg)
    out = {"loss": [], "global_batch": [], "step_s": []}
    jax.block_until_ready(session.state)
    last = time.perf_counter()

    def on_step(epoch, metrics):
        nonlocal last
        jax.block_until_ready(session.state)
        now = time.perf_counter()
        out["loss"].append(metrics["loss"])
        out["global_batch"].append(metrics["global_batch"])
        out["step_s"].append(now - last)
        last = now

    session.run(steps, on_step=on_step)
    return session, out


def batch_seconds(session: AMBSession) -> float:
    """Seconds of one compiled build of an epoch's global batch (the token
    stream runs on the device, beside the step)."""
    source = session.batch_source()
    jax.block_until_ready(source.batch(session.steps_done))
    t0 = time.perf_counter()
    jax.block_until_ready(source.batch(session.steps_done + 1))
    return time.perf_counter() - t0


def dual_update_check(session: AMBSession, *, force: str = "pallas") -> dict:
    """Recompute the last epoch's prox from the session's state with the
    ``force`` kernel route and with the jnp reference, leaf by leaf.

    Returns ``kernel_vs_ref``: max |kernel - ref| / (1 + |ref|) of the f32
    prox, and ``params_vs_ref``: the same for the parameters the stepped
    session holds (cast to their dtype) against the reference.
    """
    state = session.state
    opt = session.protocol.optimizer
    beta = opt.beta(state["opt"]["t"].astype(jnp.float32) + 1.0)

    @jax.jit
    def leaf_diffs(z, w0, p, beta):
        ker = kops.dual_update(z, w0, beta, force=force)
        ref = kops.dual_update(z, w0, beta, force="ref")
        scale = 1.0 + jnp.abs(ref)
        d_ker = jnp.max(jnp.abs(ker - ref) / scale)
        d_par = jnp.max(jnp.abs(p.astype(jnp.float32)
                                - ref.astype(p.dtype).astype(jnp.float32))
                        / scale)
        return d_ker, d_par

    worst = {"kernel_vs_ref": 0.0, "params_vs_ref": 0.0}
    with use_sharding(session.mesh):
        for z, w0, p in zip(jax.tree.leaves(state["opt"]["z"]),
                            jax.tree.leaves(state["opt"]["w0"]),
                            jax.tree.leaves(state["params"])):
            d_ker, d_par = leaf_diffs(z, w0, p, beta)
            worst["kernel_vs_ref"] = max(worst["kernel_vs_ref"],
                                         float(d_ker))
            worst["params_vs_ref"] = max(worst["params_vs_ref"],
                                         float(d_par))
    return worst


def serve_phase(params, cfg, *, mesh=None, requests: int = 4,
                prompt_len: int = 16, new_tokens: int = 8,
                seed: int = 0) -> dict:
    """Greedy requests through a :class:`SlotEngine`, compared token for
    token with :func:`static_generate` on the same parameters."""
    def make():
        return synthetic_requests(requests, vocab_size=cfg.vocab_size,
                                  prompt_len=prompt_len,
                                  max_new_tokens=new_tokens, seed=seed + 1)

    cache_len = prompt_len + new_tokens
    reqs = make()
    engine = SlotEngine(params, cfg, slots=requests, cache_len=cache_len,
                        mesh=mesh)
    t0 = time.perf_counter()
    for r in reqs:
        engine.insert(r)
    while engine.active_count:
        engine.decode_round()
    serve_s = time.perf_counter() - t0
    ref = static_generate(params, cfg, make(), cache_len=cache_len,
                          mesh=mesh)
    return {"tokens": [r.out_tokens for r in reqs],
            "reference": [r.out_tokens for r in ref],
            "serve_s": serve_s}


def _worker_rows(leaf) -> dict:
    """device -> the worker rows of a (n, ...) leaf that device holds."""
    return {s.device: (s.index[0].start, s.index[0].stop)
            for s in leaf.addressable_shards}


def _one_epoch(cfg, mesh, train, consensus, batch, b):
    """An :class:`AMBSession` on ``mesh`` after one epoch on ``batch``."""
    session = AMBSession(train, ClockSpec(kind="simulated"), consensus,
                         mesh=mesh, cfg=cfg)
    return session, session.step(batch, b)


def gossip_vs_ref(cfg, mesh, train, consensus, batch, b):
    """One gossip epoch from the shared init through the routed kernels,
    and again through the jnp reference route, on the same batch.

    Returns the routed session, its metrics and ``z_vs_ref``: max
    |z - z_ref| / (1 + |z_ref|) over every worker's dual replica.
    """
    with _kernels("ref"):
        ref, _ = _one_epoch(cfg, mesh, train, consensus, batch, b)
    z_ref = jax.tree.leaves(jax.device_get(ref.state["z"]))
    del ref
    session, metrics = _one_epoch(cfg, mesh, train, consensus, batch, b)
    z = jax.tree.leaves(jax.device_get(session.state["z"]))
    diff = max(float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
               for got, want in zip(z, z_ref))
    return session, metrics, {"z_vs_ref": diff}


def four_chip_phase(cfg, *, consensus: str = "gossip", seq_len: int = 128,
                    batch_per_worker: int = 2, seed: int = 0) -> dict:
    """One exact-consensus and one ring-gossip epoch (``consensus``) on the
    same batch over a 4x1 (data, model) mesh, every worker at its full
    minibatch.

    Metropolis mixing is doubly stochastic, so with equal b_i the
    node-averaged gossip dual equals the exact eq.-6 sum, and the
    node-averaged primal (``AMBSession.params``) the exact primal.  That
    holds for any doubly stochastic mixing, so each worker's dual replica
    is also compared with the same epoch on the jnp reference route.
    """
    mesh = make_mesh((4, 1), ("data", "model"))
    train = TrainSpec(arch=ARCH, data=4, model=1, seq_len=seq_len,
                      batch_per_worker=batch_per_worker, seed=seed)
    exact = AMBSession(train, ClockSpec(kind="simulated"),
                       ConsensusSpec(consensus="exact"), mesh=mesh, cfg=cfg)
    batch = exact.batch_source().batch(0)
    b = jnp.full((4,), batch_per_worker, jnp.int32)
    m_exact = exact.step(batch, b)
    w_e = jax.tree.leaves(exact.params)
    # the exact session's FSDP-sharded embedding: a quarter per device
    emb = exact.state["params"]["embed"]
    emb_shards = {s.device: s.data.shape for s in emb.addressable_shards}
    fsdp_ok = (len(emb_shards) == 4 and all(
        int(np.prod(s)) * 4 == emb.size for s in emb_shards.values()))
    del exact, emb
    gossip, m_gossip, vs_ref = gossip_vs_ref(
        cfg, mesh, train, ConsensusSpec(consensus=consensus, graph="ring"),
        batch, b)

    w_g = jax.tree.leaves(gossip.params)
    primal = max(float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                       - e.astype(jnp.float32))
                               / (1.0 + jnp.abs(e.astype(jnp.float32)))))
                 for e, g in zip(w_e, w_g))

    devices = list(mesh.devices.flat)
    # each device holds exactly its own worker's dual replica
    z_rows = [_worker_rows(z) for z in jax.tree.leaves(gossip.state["z"])]
    replicas_ok = all(
        sorted(rows.values()) == [(i, i + 1) for i in range(4)]
        and len(rows) == 4 for rows in z_rows)
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices] \
        if devices[0].memory_stats() else []
    return {"loss_exact": m_exact["loss"], "loss_gossip": m_gossip["loss"],
            "primal_diff": primal, **vs_ref, "replicas_ok": replicas_ok,
            "fsdp_ok": fsdp_ok, "bytes_in_use": in_use}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _device_checks(want_count: int) -> dict:
    devs = jax.devices()
    platform = devs[0].platform
    _check(platform == "tpu", f"no TPU: JAX found {platform!r} devices")
    _check(router.mode() in ("auto", "pallas"),
           f"REPRO_KERNELS={router.mode()!r} would bypass the compiled "
           f"kernels")
    route = router.resolve()
    _check(route == "pallas", f"kernel routing resolved to {route!r}")
    _check(len(devs) >= want_count,
           f"need {want_count} chips, JAX found {len(devs)}")
    _log(device_kind=devs[0].device_kind, count=len(devs), routing=route)
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory() -> dict:
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return {"peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in stats],
            "bytes_limit": [m.get("bytes_limit") for m in stats]}


def run_one_chip(args) -> None:
    cfg = dataclasses.replace(get_config(ARCH), num_layers=LAYERS)
    _log(phase="config", arch=ARCH, num_layers=cfg.num_layers,
         published_layers=get_config(ARCH).num_layers,
         d_model=cfg.d_model, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
         params=cfg.param_count())
    timer = CompileTimer()
    session, train = train_phase(cfg, seed=args.seed)
    losses = train["loss"]
    _log(phase="train", **train, compile_s=timer.seconds)
    _check(len(losses) == STEPS and all(np.isfinite(losses)),
           f"training losses not all finite: {losses}")
    _log(phase="batch", batch_s=batch_seconds(session))

    diffs = dual_update_check(session)
    _log(phase="dual_update", **diffs, kernel_tol=DUAL_UPDATE_TOL,
         param_tol=PARAM_TOL)
    _check(diffs["kernel_vs_ref"] <= DUAL_UPDATE_TOL,
           "Pallas dual_update differs from the jnp reference")
    _check(diffs["params_vs_ref"] <= PARAM_TOL,
           "stepped parameters differ from the reference prox")

    served = serve_phase(session.params, cfg, mesh=session.mesh,
                         seed=args.seed)
    _log(phase="serve", serve_s=served["serve_s"],
         tokens=served["tokens"])
    _check(served["tokens"] == served["reference"],
           f"slot engine tokens {served['tokens']} differ from "
           f"static_generate {served['reference']}")
    _log(phase="done", **_memory(), compile_s=timer.seconds)


def run_four_chips(args) -> None:
    cfg = dataclasses.replace(get_config(ARCH), num_layers=FOUR_CHIP_LAYERS)
    _log(phase="config", arch=ARCH, num_layers=cfg.num_layers,
         published_layers=get_config(ARCH).num_layers,
         vocab=cfg.vocab_size, params=cfg.param_count(), mesh="4x1")
    timer = CompileTimer()
    out = four_chip_phase(cfg, seed=args.seed)
    _log(phase="four_chips", **out, primal_tol=PRIMAL_TOL,
         gossip_tol=GOSSIP_TOL, compile_s=timer.seconds)
    _check(np.isfinite(out["loss_exact"]) and np.isfinite(out["loss_gossip"]),
           "four-chip losses not finite")
    _check(out["primal_diff"] <= PRIMAL_TOL,
           "gossip node-averaged primal differs from exact consensus")
    _check(out["z_vs_ref"] <= GOSSIP_TOL,
           "gossip dual replicas differ from the jnp reference route")
    _check(out["replicas_ok"], "dual replicas are not one per device")
    _check(out["fsdp_ok"], "parameters are not sharded over the devices")
    in_use = out["bytes_in_use"]
    _check(len(in_use) == 4 and min(in_use) * 2 >= max(in_use),
           f"device memory not spread over the chips: {in_use}")
    _log(phase="done", **_memory())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip exact-vs-gossip phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the token stream")
    args = ap.parse_args(argv)
    device = _device_checks(4 if args.four_chips else 1)
    use_compile_cache()
    if args.four_chips:
        run_four_chips(args)
    else:
        run_one_chip(args)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
