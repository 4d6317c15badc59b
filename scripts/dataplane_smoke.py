#!/usr/bin/env python
"""CI smoke for the data plane (fast lane of scripts/verify.sh).

End-to-end checks that the step-time critical path is actually wired,
not just importable:

  1. **Prefetched run** — a short ``AMBSession.run`` on a 1x1 mesh draws
     per-worker stream shards through a background
     :class:`repro.data.Prefetcher` and matches the synchronous
     (``prefetch=0``) loop loss-for-loss — token draws are
     deterministic, so any divergence is a data-plane ordering bug.
  2. **Donation** — after a step, every leaf of the pre-step TrainState
     must be freed (``donate_argnums=0`` aliasing held; the old iterate
     was rewritten in place, not shadowed).
  3. **Kernel routing** — on a CPU host the router must resolve the
     compiled jnp reference (never interpret-mode Pallas on the hot
     path), and the ``REPRO_KERNELS`` override must take.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                   # noqa: E402

from repro.api import (AMBSession, ClockSpec, ConsensusSpec,  # noqa: E402
                       TrainSpec)
from repro.kernels import router             # noqa: E402
from repro.launch.mesh import make_mesh     # noqa: E402
from repro.models.common import ArchConfig   # noqa: E402


def _session():
    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=64, q_chunk=16, kv_chunk=16,
                     mxu_f32_accum=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    return AMBSession(TrainSpec(batch_per_worker=2, seq_len=8),
                      ClockSpec(kind="simulated"), ConsensusSpec(),
                      mesh=mesh, cfg=cfg)


def run() -> None:
    # 1. prefetched vs sync: identical losses, identical step counters
    losses_pre, losses_sync = [], []
    sA, sB = _session(), _session()
    sA.run(3, prefetch=2, on_step=lambda s, m: losses_pre.append(m["loss"]))
    sB.run(3, prefetch=0, on_step=lambda s, m: losses_sync.append(m["loss"]))
    assert losses_pre == losses_sync, (losses_pre, losses_sync)
    assert sA.steps_done == sB.steps_done == 3

    # 2. donation: the pre-step state's buffers are actually freed
    old = sA.state
    sA.run(1)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old)), \
        "pre-step TrainState still live: donation not in effect"

    # 3. routing: never interpret on the CPU hot path; override takes
    resolved = router.resolve()
    backend = jax.default_backend()
    if backend not in ("tpu", "gpu"):
        assert resolved == "ref", (backend, resolved)
    assert resolved != "pallas_interpret"
    router.set_mode("pallas_interpret")      # explicit override wins
    assert router.resolve() == "pallas_interpret"
    router.set_mode(None)
    assert router.resolve() == resolved

    print(f"[ok] dataplane smoke: prefetched==sync over 3 steps "
          f"(loss {losses_pre[-1]:.4f}), donation freed the old state, "
          f"kernel routing {backend} -> {resolved}")


if __name__ == "__main__":
    run()
