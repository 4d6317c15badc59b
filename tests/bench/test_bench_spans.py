"""The readers of the program's spans on small traces with known answers."""
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import harness, spans, trace  # noqa: E402

MS = 1e6
READERS = ("data_wait_ms.train", "host_ms.train", "host_exposed_ms.train")


def _ev(name, start_ms, end_ms):
    return [name, start_ms * MS, (end_ms - start_ms) * MS, ""]


# two epochs in a 100 ms window, ms.  The device runs [16,25) [27,34)
# [51,74): idle [0,16) [25,27) [34,51) [74,100).
HOST = [
    _ev("bench.window", 0, 100),
    _ev("AMBSession.run", 0, 95),
    # the prefetcher's thread
    _ev("amb.data.build", 0, 4), _ev("amb.data.put", 4, 6),
    _ev("amb.data.build", 6, 20), _ev("amb.data.put", 20, 21),
    # the epoch loop
    _ev("amb.data.wait", 5, 10),
    _ev("amb.epoch", 10, 40),
    _ev("amb.epoch.clock", 10, 12), _ev("amb.epoch.dispatch", 12, 15),
    _ev("amb.epoch.wait", 15, 35), _ev("amb.epoch.record", 35, 38),
    _ev("amb.on_step", 40, 42),
    _ev("amb.data.wait", 43, 45),
    _ev("amb.epoch", 45, 80),
    _ev("amb.epoch.clock", 45, 47), _ev("amb.epoch.dispatch", 47, 50),
    _ev("amb.epoch.wait", 50, 75), _ev("amb.epoch.record", 75, 78),
    _ev("amb.on_step", 80, 82),
]
OPS = [_ev("fusion.1", 16, 25), _ev("fusion.2", 27, 34),
       _ev("convolution.3", 51, 74)]


def _ctx(host=HOST, devices=None, lo=0.0, hi=100 * MS, epochs=2):
    devices = devices or {0: {"ops": OPS, "modules": []}}
    return types.SimpleNamespace(
        trace={"devices": devices, "host": host}, lo=lo, hi=hi,
        devices=sorted(devices), epochs=epochs)


def _read(name, ctx):
    return harness.metric_reader(name)(ctx)


def test_data_wait_is_the_union_of_queue_waits():
    # [5,10) the first fill and [43,45): 7 ms over 2 epochs
    assert _read("data_wait_ms.train", _ctx()) == pytest.approx(3.5)


def test_host_ms_is_the_epoch_less_its_wait():
    # (30 + 35) ms of epochs less (20 + 25) ms of waits, over 2 epochs
    assert _read("host_ms.train", _ctx()) == pytest.approx(10.0)


def test_host_exposed_counts_idle_under_host_work_only():
    # host work: [0,15) [35,42) [43,50) [75,82).  Idle under it: [0,15)
    # [35,42) [43,50) [75,82) = 36 ms.  Not counted: the gap [25,27)
    # and the edges [15,16) [34,35) [74,75), under amb.epoch.wait; the
    # gaps [42,43) and [82,100), under no amb.* span.
    assert _read("host_exposed_ms.train", _ctx()) == pytest.approx(18.0)


def test_host_exposed_averages_the_chips():
    devices = {0: {"ops": OPS, "modules": []},
               1: {"ops": [_ev("fusion.9", 0, 100)], "modules": []}}
    assert _read("host_exposed_ms.train", _ctx(devices=devices)) == \
        pytest.approx(9.0)


def test_spans_are_clipped_to_the_window():
    # the window [12, 60): epoch time [12,40) [45,60) less waits [15,35)
    # [50,60) = 13 ms; one epoch
    ctx = _ctx(lo=12 * MS, hi=60 * MS, epochs=1)
    assert _read("host_ms.train", ctx) == pytest.approx(13.0)
    assert _read("data_wait_ms.train", ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("name", READERS)
def test_no_program_spans_read_nothing(name):
    # the benchmark's own spans alone, as a program without spans leaves
    bench_only = [e for e in HOST if not e[0].startswith("amb.")]
    assert _read(name, _ctx(host=bench_only)) is None
    # epochs outside the window read nothing either
    assert _read(name, _ctx(lo=90 * MS, hi=100 * MS, epochs=0)) is None


def test_intersect_of_merged_intervals():
    assert spans.intersect([(0, 10), (20, 30)], [(5, 25)]) == [
        (5, 10), (20, 25)]
    assert spans.intersect([(0, 10)], []) == []


# ---------------------------------------------------------------------------
# a recorded chip trace: three window epochs of train.l12.shexp on a TPU v5
# lite, with the program's spans (their ``epoch`` stat in ``meta``), the
# XLA Modules line, the device ops that border each idle gap, and the rest
# of the device's busy time merged into ``busy`` intervals
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    import json
    path = Path(__file__).resolve().parent / "data" / \
        "trace_train_l12_spans.json"
    rec = json.loads(path.read_text())
    rec["devices"] = {int(k): v for k, v in rec["devices"].items()}
    return rec


def _epoch_of(ev):
    return int(ev[3].split("=", 1)[1])


def _program(rec, name):
    return {_epoch_of(e): e for e in rec["host"] if e[0] == name}


def test_recorded_spans_once_per_epoch(recorded):
    names = ("amb.epoch", "amb.epoch.clock", "amb.epoch.dispatch",
             "amb.epoch.wait", "amb.epoch.record", "amb.on_step",
             "amb.data.wait", "amb.data.build", "amb.data.put")
    for name in names:
        got = [_epoch_of(e) for e in recorded["host"] if e[0] == name]
        for t in recorded["epochs"]:
            assert got.count(t) == 1, (name, t)


def test_recorded_step_runs_between_its_dispatch_and_wait(recorded):
    """Program spans and device ops share one clock: each epoch's step
    program starts on the device after its dispatch starts and ends
    before the wait for its results ends."""
    dispatch = _program(recorded, "amb.epoch.dispatch")
    wait = _program(recorded, "amb.epoch.wait")
    step = [m for m in recorded["devices"][0]["modules"]
            if m[0] == recorded["step_module"]]
    for t in recorded["epochs"]:
        d, w = dispatch[t], wait[t]
        mine = [m for m in step if d[1] <= m[1] <= w[1] + w[2]]
        assert len(mine) == 1, t
        assert mine[0][1] + mine[0][2] <= w[1] + w[2]
    # and no step program runs outside some epoch's dispatch .. wait
    assert all(any(dispatch[t][1] <= m[1] and
                   m[1] + m[2] <= wait[t][1] + wait[t][2]
                   for t in recorded["epochs"])
               for m in step
               if recorded["window"][0] <= m[1] <= recorded["window"][1])


def test_recorded_readers(recorded):
    lo, hi = recorded["window"]
    ctx = types.SimpleNamespace(trace=recorded, lo=lo, hi=hi, devices=[0],
                                epochs=len(recorded["epochs"]))
    got = {name: _read(name, ctx) for name in READERS}
    assert all(v is not None and v >= 0 for v in got.values())
    idle_ms = (hi - lo - trace.busy_ns(
        recorded["devices"][0]["ops"], lo, hi)) * 1e-6 / ctx.epochs
    # exposed host time is idle time, and host work
    assert got["host_exposed_ms.train"] <= idle_ms
    work_ms = trace.length(spans.host_work(ctx)) * 1e-6 / ctx.epochs
    assert got["host_exposed_ms.train"] <= work_ms
