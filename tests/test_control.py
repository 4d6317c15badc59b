"""repro.control: telemetry, policies, the Controller, and session wiring.

Host-level tests cover the telemetry EMAs (including the measured-tau
preference that keeps the Lemma-6 re-solve out of its positive feedback
loop), the three policies' proposals, the Controller's cadence /
hysteresis / rate limits, and the JSON + argparse spec round-trips.  The
session-level tests drive a tiny in-process AMBSession with a mis-tuned
budget and assert the controller pulls T to the Lemma-6 solve; the slow
subprocess test (8 forced host devices) covers the acceptance criterion:
a controller-raised staleness change mid-run is bit-exactly resumable
through ``save`` / ``restore``.

Satellite coverage for :class:`repro.api.clock.MeasuredClock` lives here
too: EMA warm-up from ``sec_per_grad=None``, b_i(t) convergence under a
hardware speed step-change, and the ``ClockSpec.ema`` round-trip.
"""
import argparse
import json

import jax
import numpy as np
import pytest

from repro.api import ClockSpec, ControllerSpec, MeasuredClock, make_clock
from repro.control import (BatchDampingPolicy, BudgetPolicy, ControlAction,
                           Controller, EpochRecord, StalenessPolicy,
                           Telemetry)
from repro.core.stragglers import (ShiftedExponential, amb_batch_sizes,
                                   amb_budget_from_fmb)
from repro.launch.mesh import make_mesh

from test_dist import run_sub      # canonical forced-device subprocess


def _record(t, budget=4.0, comm=2.0, b=(8, 8, 8, 8), loss=1.0, **kw):
    return EpochRecord(t=t, budget_s=budget, comm_time_s=comm, step_s=0.01,
                       loss=loss, b=np.asarray(b),
                       global_batch=float(np.sum(b)), **kw)


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------

def test_telemetry_ema_folds():
    tel = Telemetry(ema=0.5)
    tel.update(_record(0, budget=4.0, b=(2, 4, 8, 8)))
    # fallback estimator: mean_i T / b_i
    want = np.mean(4.0 / np.array([2, 4, 8, 8.0]))
    assert tel.tau == pytest.approx(want)
    assert tel.ratio == pytest.approx(0.5)
    tel.update(_record(1, budget=4.0, b=(4, 4, 4, 4)))
    assert tel.tau == pytest.approx(0.5 * want + 0.5 * 1.0)
    assert tel.epochs_seen == 2


def test_telemetry_prefers_measured_tau():
    """When b_i saturates the data cap, T/b_i over-bills the fast nodes;
    a supplied measured tau_s must win over the fallback."""
    tel = Telemetry(ema=0.5)
    tel.update(_record(0, budget=40.0, b=(8, 8, 8, 8), tau_s=1.25))
    assert tel.tau == pytest.approx(1.25)        # not 40 / 8 = 5.0
    assert tel.ratio == pytest.approx(2.0 / 40.0)


def test_telemetry_noise_scale():
    """McCandlish form: tr(Sigma) = Dw B/(n-1), ||g||^2 debiased."""
    tel = Telemetry(ema=0.0)     # ema=0 -> last observation wins
    tel.update(_record(0, b=(8, 8, 8, 8), grad_sq_norm=2.0, grad_var=0.3))
    big_b, n = 32.0, 4
    tr = 0.3 * big_b / (n - 1)
    g2 = 2.0 - 0.3 / (n - 1)
    assert tel.trace_sigma == pytest.approx(tr)
    assert tel.grad_sq == pytest.approx(g2)
    assert tel.noise_scale == pytest.approx(tr / g2)
    # state round-trip restores every EMA exactly
    back = Telemetry.from_state(tel.to_state())
    assert back.to_state() == tel.to_state()


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------

def test_budget_policy_solve_is_lemma6():
    pol = BudgetPolicy(b_target=600)
    tau, n = 0.02, 10
    want = (1.0 + n / 600.0) * (600.0 / n) * tau
    assert pol.solve(tau, n) == pytest.approx(want)
    # per-call b_target override (the batch-damping hook)
    assert pol.solve(tau, n, b_target=1200) == pytest.approx(
        (1.0 + n / 1200.0) * (1200.0 / n) * tau)


def test_budget_policy_stationary_matches_lemma6():
    """The jit EMA form (the old AdaptiveBudget API, now re-exported from
    repro.control) converges to Lemma 6's T on a stationary cluster."""
    model = ShiftedExponential(lam=2 / 3, zeta=1.0, b_ref=60)
    n, b_global = 10, 600
    pol = BudgetPolicy(b_target=b_global, ema=0.8)
    t_lemma6 = amb_budget_from_fmb(model, n, b_global)
    state = pol.init(10.0 * t_lemma6)            # start badly mis-tuned
    key = jax.random.PRNGKey(4)
    for t in range(40):
        times = model.per_gradient_times(jax.random.fold_in(key, t), n,
                                         4 * (b_global // n))
        b = amb_batch_sizes(times, float(state["t_budget"]))
        state = pol.update(state, b)
    assert abs(float(state["t_budget"]) - t_lemma6) / t_lemma6 < 0.25


def test_adaptive_budget_is_an_alias():
    from repro.core.extensions import AdaptiveBudget
    assert AdaptiveBudget is BudgetPolicy


def test_staleness_policy_hysteresis():
    sp = StalenessPolicy(d_max=8, hysteresis=0.25)
    # ideal D = ceil(ratio) clipped to [1, d_max]
    assert [sp.target(r) for r in (0.1, 1.0, 1.5, 2.0, 4.2, 99.0)] == \
        [1, 1, 2, 2, 5, 8]
    # raises only past d_cur + hyst; lowers only past d_cur - 1 - hyst
    assert [sp.propose(2, r) for r in (0.4, 1.9, 2.1, 2.3, 4.2)] == \
        [1, 2, 2, 3, 5]
    # a boundary ratio never thrashes between adjacent values
    d = 2
    for _ in range(6):
        d = sp.propose(d, 2.0)
    assert d == 2
    assert StalenessPolicy.gamma(1) == 1.0
    assert StalenessPolicy.gamma(4) == pytest.approx(1.0 / 8.0)


def test_batch_damping_policy():
    pol = BatchDampingPolicy(b_floor=64, b_cap=512, grow=2.0, deadband=0.25)
    assert pol.propose(64, None) == 64           # no telemetry yet
    assert pol.propose(64, 1000.0) == 128        # rate-limited to 2x
    assert pol.propose(128, 1000.0) == 256
    assert pol.propose(400, 1000.0) == 512       # hard cap
    assert pol.propose(64, 70.0) == 64           # inside the deadband
    assert pol.propose(256, 1.0) == 256          # grow-only: never shrinks


# ---------------------------------------------------------------------------
# Controller: cadence, decisions, state round-trip
# ---------------------------------------------------------------------------

def _controller(async_mode=True, **spec_kw):
    kw = dict(enabled=True, interval=2, warmup=3)
    kw.update(spec_kw)
    return Controller(ControllerSpec(**kw), n_workers=4, comm_time=8.0,
                      b_target=32, b_cap=32, staleness=1,
                      async_mode=async_mode)


def test_controller_warmup_and_cadence():
    ctl = _controller()
    acts = [ctl.observe(_record(t, budget=40.0, b=(4,) * 4, tau_s=1.0))
            for t in range(8)]
    # nothing during warmup; then at most one decision per interval
    assert acts[0] is None and acts[1] is None
    fired = [i for i, a in enumerate(acts) if a is not None]
    assert fired, "controller never acted on a 10x mis-tuned budget"
    assert all(b - a >= 2 for a, b in zip(fired, fired[1:]))


def test_controller_budget_and_staleness_decisions():
    """Mis-tuned T=40 with true tau=1: budget falls (rate-limited 2x per
    decision) toward Lemma 6 ~ 9, and D rises once T_c/T demands it."""
    ctl = _controller()
    for t in range(20):
        ctl.observe(_record(t, budget=ctl.budget or 40.0, b=(4,) * 4,
                            tau_s=1.0))
    want = BudgetPolicy(b_target=32).solve(1.0, 4)
    # converges to the solve, up to the anti-thrash deadband (10%)
    assert ctl.budget == pytest.approx(want, rel=0.15)
    # T ~ 9, T_c = 8 -> ratio < 1 + hyst: D must still be 1...
    assert ctl.staleness == 1
    ctl2 = _controller()
    ctl2.comm_time = 80.0        # ...but a 10x window forces deep staleness
    for t in range(20):
        ctl2.observe(_record(t, budget=ctl2.budget or 40.0, comm=80.0,
                             b=(4,) * 4, tau_s=1.0))
    assert ctl2.staleness == 8   # d_max-clipped
    assert ctl2.decisions > 0


def test_controller_staleness_suppressed_outside_async():
    ctl = _controller(async_mode=False)
    for t in range(20):
        ctl.observe(_record(t, budget=ctl.budget or 1.0, comm=80.0,
                            b=(4,) * 4, tau_s=1.0))
    assert ctl.staleness == 1    # sequential/pipelined: D is not a knob


def test_controller_state_roundtrip_replays_identically():
    """to_state/load_state is the bit-exact-resume contract: two
    controllers fed the same tail from a shared snapshot must decide
    identically."""
    recs = [_record(t, budget=40.0, b=(3, 4, 5, 4), tau_s=1.0 + 0.01 * t)
            for t in range(12)]
    a = _controller()
    for r in recs[:6]:
        a.observe(r)
    snap = json.loads(json.dumps(a.to_state()))   # through JSON, as saved
    b = _controller()
    b.load_state(snap)
    rest_a = [None if x is None else x.to_dict()
              for x in (a.observe(r) for r in recs[6:])]
    rest_b = [None if x is None else x.to_dict()
              for x in (b.observe(r) for r in recs[6:])]
    assert rest_a == rest_b


def test_control_action_nontrivial():
    assert not ControlAction(epoch=1).nontrivial
    assert ControlAction(epoch=1, budget=2.0).nontrivial
    assert ControlAction(epoch=1, staleness=2, gamma=0.25).nontrivial


# ---------------------------------------------------------------------------
# ControllerSpec + ClockSpec.ema round-trips (satellite)
# ---------------------------------------------------------------------------

def test_controller_spec_roundtrips():
    spec = ControllerSpec(enabled=True, interval=3, warmup=7, d_max=4)
    assert ControllerSpec.from_json(spec.to_json()) == spec
    ap = argparse.ArgumentParser()
    ClockSpec.add_cli_args(ap)
    ControllerSpec.add_cli_args(ap)
    args = ap.parse_args(["--controller", "--controller-interval", "3",
                          "--controller-warmup", "7",
                          "--controller-dmax", "4", "--clock-ema", "0.55"])
    assert ControllerSpec.from_args(args) == spec
    # ClockSpec.ema round-trips through argparse and JSON
    clk = ClockSpec.from_args(args)
    assert clk.ema == 0.55
    assert ClockSpec.from_json(clk.to_json()) == clk
    # defaults parse to the default (disabled) spec
    assert ControllerSpec.from_args(ap.parse_args([])) == ControllerSpec()


# ---------------------------------------------------------------------------
# MeasuredClock (satellite): warm-up, convergence, EMA wiring
# ---------------------------------------------------------------------------

def test_measured_clock_warms_up_from_model_unit():
    clk = make_clock(ClockSpec(kind="measured", ema=0.5), n=4,
                     batch_per_worker=8)
    assert isinstance(clk, MeasuredClock)
    assert clk.sec_per_grad is None              # no measurement yet
    _, b0 = clk.epoch(jax.random.PRNGKey(0))
    assert b0 == pytest.approx((1.0 + 4 / 32) * clk.model_unit * 8)
    clk.update(step_seconds=16.0, global_b=32.0)   # 0.5 s per gradient
    assert clk.sec_per_grad == pytest.approx(0.5)  # first obs adopted


def test_measured_clock_tracks_speed_step_change():
    """Hardware gets 4x faster mid-run: the EMA converges and b_i(t) at a
    *fixed* budget grows accordingly."""
    clk = make_clock(ClockSpec(kind="measured", ema=0.5), n=4,
                     batch_per_worker=16)
    for _ in range(4):
        clk.update(step_seconds=64.0, global_b=64.0)   # 1 s / grad
    t_lemma6 = clk.budget()
    t_fixed = t_lemma6 / 4.0     # under-provisioned: b_i well below cap
    b_slow = int(amb_batch_sizes(clk.times(jax.random.PRNGKey(0)),
                                 t_fixed).sum())
    for _ in range(12):
        clk.update(step_seconds=16.0, global_b=64.0)   # 0.25 s / grad
    assert clk.sec_per_grad == pytest.approx(0.25, rel=0.01)
    b_fast = int(amb_batch_sizes(clk.times(jax.random.PRNGKey(0)),
                                 t_fixed).sum())
    assert b_fast > 2 * b_slow       # same T, ~4x the gradients (capped)
    # and the re-derived Lemma-6 budget shrank with the unit
    assert clk.budget() == pytest.approx(t_lemma6 / 4, rel=0.02)


def test_clock_set_budget_pins():
    clk = make_clock(ClockSpec(kind="measured"), n=4, batch_per_worker=8)
    clk.set_budget(2.5)
    clk.update(step_seconds=80.0, global_b=8.0)  # would re-derive T = 90
    _, budget = clk.epoch(jax.random.PRNGKey(0))
    assert budget == 2.5                         # pinned: controller owns T
    sim = make_clock(ClockSpec(kind="simulated"), n=4, batch_per_worker=8)
    sim.set_budget(1.25)
    assert sim.epoch(jax.random.PRNGKey(0))[1] == 1.25


# ---------------------------------------------------------------------------
# Session wiring (tiny in-process mesh)
# ---------------------------------------------------------------------------

def _tiny_controlled_session(clock, controller, consensus=None,
                             metrics_path=None):
    from repro.api import AMBSession, ConsensusSpec, TrainSpec
    from repro.models.common import ArchConfig
    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=32,
                     num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                     vocab_size=64, q_chunk=16, kv_chunk=16,
                     mxu_f32_accum=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    train = TrainSpec(batch_per_worker=2, seq_len=8)
    cons = consensus or ConsensusSpec(consensus="gossip", gossip_rounds=2)
    return AMBSession(train, clock, cons, controller, mesh=mesh,
                      cfg=cfg, metrics_path=metrics_path), cfg


def test_session_controller_corrects_mistuned_budget(tmp_path):
    """A 10x over-provisioned simulated budget is pulled to ~Lemma 6, and
    both the epochs and the decisions land in the metrics JSONL."""
    from repro.data import LMTokenStream
    from repro.metrics import read_metrics
    session, cfg = _tiny_controlled_session(
        ClockSpec(kind="simulated", compute_time=40.0, comm_time=0.5),
        ControllerSpec(enabled=True, interval=1, warmup=2),
        metrics_path=tmp_path / "m.jsonl")
    stream = LMTokenStream(vocab_size=cfg.vocab_size, seq_len=8, seed=0)
    budgets = []
    for i in range(10):
        m = session.step(stream.batch(0, i, session.global_batch))
        budgets.append(m["budget_s"])
    session.close()
    # Lemma 6 for this clock's model at n=1, b=2
    t_lemma6 = amb_budget_from_fmb(session.clock.model, 1, 2)
    assert budgets[0] == 40.0
    assert abs(budgets[-1] - t_lemma6) / t_lemma6 < 0.5, budgets
    recs = read_metrics(tmp_path / "m.jsonl")
    assert len(recs) == 10
    assert any("action" in r for r in recs)
    assert all("loss" in r and "budget_s" in r for r in recs)


def test_session_without_controller_unchanged(tmp_path):
    """Default sessions carry no controller and no noise-stats graph —
    the opt-in leaves the bit-parity surface untouched."""
    session, _ = _tiny_controlled_session(
        ClockSpec(kind="simulated"), None)
    assert session.controller is None
    assert session.protocol.amb.noise_stats is False


@pytest.mark.slow
def test_controller_staleness_retune_resumes_bit_exact():
    """Acceptance: the controller raises D mid-run (long T_c), and a
    save/restore through that retuned state continues bit-for-bit."""
    out = run_sub("""
        import tempfile
        import jax
        from repro.api import (AMBSession, ClockSpec, ConsensusSpec,
                               ControllerSpec, TrainSpec)
        from repro.data import LMTokenStream

        train = TrainSpec(arch="qwen2-1.5b", smoke=True, seq_len=16,
                          batch_per_worker=2, data=4, model=2)
        clock = ClockSpec(kind="simulated", comm_time=12.0)
        cons = ConsensusSpec(consensus="gossip", gossip_rounds=2,
                             async_epochs=True, staleness=1)
        ctl = ControllerSpec(enabled=True, interval=1, warmup=2)
        s = AMBSession(train, clock, cons, ctl)
        stream = LMTokenStream(vocab_size=s.cfg.vocab_size, seq_len=16,
                               seed=0)
        for i in range(6):
            m = s.step(stream.batch(0, i, s.global_batch))
        assert m["staleness"] > 1, m["staleness"]   # D was raised mid-run
        d = tempfile.mkdtemp()
        s.save(d)
        ref = [s.step(stream.batch(0, i, s.global_batch))["loss"]
               for i in range(6, 10)]
        r = AMBSession.restore(d)
        got = [r.step(stream.batch(0, i, r.global_batch))["loss"]
               for i in range(6, 10)]
        assert ref == got, (ref, got)
        print("BITEXACT D=", s.consensus_spec.staleness)
    """)
    assert "BITEXACT" in out
