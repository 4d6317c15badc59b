"""The program's profiler spans: the AMB epoch, its phases and the data plane.

A tiny ``AMBSession`` runs three epochs under ``jax.profiler``; the trace
is read back with the benchmark's loader (``bench.trace.load``), and the
spans' stats straight from the ``.xplane.pb``.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402
from test_api import _tiny_session  # noqa: E402

STEPS = 3
EPOCH_SPANS = ("amb.epoch", "amb.epoch.clock", "amb.epoch.dispatch",
               "amb.epoch.wait", "amb.epoch.record", "amb.on_step")
DATA_SPANS = ("amb.data.wait", "amb.data.build", "amb.data.put")


def _amb_events(path: Path) -> list:
    """(name, start_ns, end_ns, stats) of every ``amb.*`` host event."""
    from jax.profiler import ProfileData
    pb = sorted(path.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("amb."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module", params=[2, 0], ids=["prefetch2", "prefetch0"])
def traced(request, tmp_path_factory):
    """The session's three epochs under the profiler, with ``prefetch``."""
    path = tmp_path_factory.mktemp(f"trace{request.param}")
    session, _ = _tiny_session()
    seen = []
    # the Python tracer would record every call of the step's compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        session.run(STEPS, prefetch=request.param,
                    on_step=lambda epoch, m: seen.append((epoch, m["b"])))
    finally:
        jax.profiler.stop_trace()
    return {"prefetch": request.param, "session": session, "seen": seen,
            "load": trace.load(path), "events": _amb_events(path)}


def _by_name(events, name):
    return [e for e in events if e[0] == name]


def test_each_span_once_per_epoch(traced):
    events = traced["events"]
    spans = EPOCH_SPANS + (DATA_SPANS if traced["prefetch"] else ())
    for name in spans:
        got = sorted(e[3]["epoch"] for e in _by_name(events, name))
        assert got == list(range(STEPS)), name
    if not traced["prefetch"]:
        assert not [e for e in events if e[0].startswith("amb.data.")]
    # the benchmark's loader sees the same spans on the same clock
    loaded = {(n, s) for n, s, _, _ in traced["load"]["host"]
              if n.startswith("amb.")}
    assert loaded == {(n, s) for n, s, _, _ in events}


def test_epoch_phases_lie_inside_their_epoch(traced):
    events = traced["events"]
    epochs = {e[3]["epoch"]: e for e in _by_name(events, "amb.epoch")}
    children = [e for e in events if e[0].startswith("amb.epoch.")]
    assert len(children) == 4 * STEPS
    for name, s, e, stats in children:
        holders = [t for t, (_, es, ee, _) in epochs.items()
                   if es <= s and e <= ee]
        assert holders == [stats["epoch"]], name
    # the phases run in order inside an epoch
    for t in range(STEPS):
        starts = [next(e[1] for e in events
                       if e[0] == n and e[3]["epoch"] == t)
                  for n in EPOCH_SPANS[1:5]]
        assert starts == sorted(starts)


def test_record_counts_credited_and_computed(traced):
    session = traced["session"]
    seen = dict(traced["seen"])
    for _, _, _, stats in _by_name(traced["events"], "amb.epoch.record"):
        assert stats["credited"] == int(seen[stats["epoch"]].sum())
        assert stats["computed"] == session.global_batch


@pytest.mark.parametrize("traced", [2], indirect=True, ids=["prefetch2"])
def test_build_ends_before_its_wait_returns(traced):
    events = traced["events"]
    built = {e[3]["epoch"]: e[2] for e in _by_name(events, "amb.data.build")}
    put = {e[3]["epoch"]: e for e in _by_name(events, "amb.data.put")}
    for _, s, e, stats in _by_name(events, "amb.data.wait"):
        t = stats["epoch"]
        assert built[t] <= put[t][1] and put[t][2] <= e


def test_step_phases_carry_named_scopes():
    session, _ = _tiny_session()
    batch = session.batch_source().batch(0)
    b = jnp.full((session.n_workers,), 2, jnp.int32)
    text = session._step_fn.lower(session.state, batch, b).as_text(
        debug_info=True)
    assert "amb.fwd_bwd" in text
    assert "amb.dual_update" in text
