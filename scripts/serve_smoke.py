#!/usr/bin/env python
"""CI smoke for the serving tier (fast lane of scripts/verify.sh).

End-to-end on the tiny smoke arch, deterministic synthetic clock:

  1. **Continuous batching correctness** — staggered arrivals with
     heterogeneous prompt lengths through the ``SlotEngine`` +
     ``ServeScheduler`` produce, per request, exactly the tokens the
     static rebatching reference produces (greedy, same params): the
     slot scatter, per-slot positions and bucket-padded prefill change
     the schedule, never the math.
  2. **Budget interleave** — background AMB fine-tune epochs run through
     the same ``AMBSession`` inside idle round budget; serving must
     finish every request AND at least one train epoch must land, with
     the session's loss recorded.
  3. **Metrics flush** — the SLO records (TTFT/TPOT/latency) reach the
     MetricsLogger JSONL even though no explicit close is issued before
     the check (the decode-only flush bug this PR fixes).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import (AMBSession, ClockSpec, ConsensusSpec,  # noqa: E402
                       TrainSpec)
from repro.launch.mesh import make_mesh     # noqa: E402
from repro.metrics import MetricsLogger      # noqa: E402
from repro.models.common import ArchConfig   # noqa: E402
from repro.serve import (AdmissionPolicy, Request, RequestQueue,  # noqa: E402
                         ServeMetrics, ServeScheduler, SlotEngine,
                         SyntheticClock, static_generate,
                         synthetic_requests)


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
    session = _session()
    cfg, mesh, params = session.cfg, session.mesh, session.params
    cache_len = 24
    reqs = synthetic_requests(6, vocab_size=cfg.vocab_size, prompt_len=8,
                              prompt_jitter=4, max_new_tokens=5,
                              arrival_gap_s=0.01, seed=3)
    clock_costs = dict(prefill_tok_s=0.001, decode_round_s=0.005,
                       train_epoch_s=0.02)

    # 1. parity: staggered continuous batching (no training, so params
    #    are frozen) must match the static reference token-for-token
    queue = RequestQueue(AdmissionPolicy(cache_len=cache_len))
    for r in reqs:
        queue.push(r)
    assert len(queue) == len(reqs), "smoke workload must be admissible"
    engine = SlotEngine(params, cfg, slots=2, cache_len=cache_len, mesh=mesh)
    sched = ServeScheduler(engine, queue, round_budget_s=0.06,
                           clock=SyntheticClock(**clock_costs))
    report = sched.run()
    assert report.summary["n_requests"] == len(reqs), report.summary
    assert report.summary["ttft_p99_s"] > 0 and \
        report.summary["tokens_per_s"] > 0, report.summary
    static = [Request(rid=r.rid, prompt=list(r.prompt),
                      max_new_tokens=r.max_new_tokens) for r in reqs]
    static_generate(params, cfg, static, cache_len=cache_len, mesh=mesh)
    for a, b in zip(reqs, static):
        assert a.out_tokens == b.out_tokens, (a.rid, a.out_tokens,
                                              b.out_tokens)

    # 2 + 3. fine-tune interleave on the same session (serving decodes
    #    the live primal) + SLO/train records flushed to JSONL
    reqs2 = synthetic_requests(6, vocab_size=cfg.vocab_size, prompt_len=8,
                               prompt_jitter=4, max_new_tokens=5,
                               arrival_gap_s=0.01, seed=4)
    queue2 = RequestQueue(AdmissionPolicy(cache_len=cache_len))
    for r in reqs2:
        queue2.push(r)
    path = os.path.join(tempfile.mkdtemp(prefix="serve_smoke_"),
                        "serve.jsonl")
    logger = MetricsLogger(path)
    engine2 = SlotEngine(session.params, cfg, slots=2, cache_len=cache_len,
                         mesh=mesh)
    sched2 = ServeScheduler(engine2, queue2, round_budget_s=0.06,
                            clock=SyntheticClock(**clock_costs),
                            session=session, train_epochs=3,
                            metrics=ServeMetrics(logger))
    report2 = sched2.run()
    assert report2.summary["n_requests"] == len(reqs2), report2.summary
    assert report2.train_epochs >= 1, "no fine-tune epoch absorbed"

    # the per-write flush (plus idempotent close) means records are on
    # disk now, before any close
    recs = [json.loads(line) for line in open(path)]
    kinds = {r.get("kind") for r in recs}
    assert "request" in kinds and "train" in kinds, kinds
    logger.close()
    logger.close()                            # idempotent

    session.close()
    print(f"[ok] serve smoke: {len(reqs)} staggered requests over 2 slots "
          f"== static reference token-for-token; "
          f"{report2.train_epochs} AMB epoch(s) absorbed "
          f"(loss {sched2.metrics.train_losses[-1]:.4f}); "
          f"SLO JSONL flushed ({len(recs)} records)")


if __name__ == "__main__":
    run()
