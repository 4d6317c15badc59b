"""The trace reduction on small traces with known answers."""
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402

from bench import trace  # noqa: E402

SPANS = ("bench.window", "AMBSession.run", "bench.block_until_ready")

# one device; ns.  busy: [0,20) [30,41) [45,50) [60,61)
SMALL = {
    "devices": {0: {"ops": [
        ["fusion.1", 0.0, 10.0, ""],
        ["fusion.2", 5.0, 15.0, ""],
        ["convolution.3", 30.0, 10.0, ""],
        ["collective-permute-start.1", 40.0, 1.0, ""],
        ["fusion.4", 45.0, 5.0, "jit(step)/dual_update"],
        ["collective-permute-done.1", 60.0, 1.0, ""]],
        "modules": [["jit_build", 0.0, 20.0, ""]]}},
    "host": [["bench.window", 0.0, 100.0, ""],
             ["AMBSession.run", 0.0, 80.0, ""],
             ["bench.block_until_ready", 80.0, 20.0, ""],
             ["unrelated", 10.0, 100.0, ""]],
}


def test_union_and_busy():
    ops = SMALL["devices"][0]["ops"]
    assert trace.union([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]
    assert trace.busy_ns(ops, 0.0, 100.0) == 37.0
    assert trace.busy_ns(ops, 10.0, 35.0) == 15.0


def test_idle_gaps_and_labels():
    lo, hi = trace.window(SMALL, "bench.window")
    assert (lo, hi) == (0.0, 100.0)
    gaps = trace.longest_gaps(SMALL, 0, lo, hi, SPANS)
    assert gaps[0] == ["bench.block_until_ready", pytest.approx(39e-9)]
    # a gap inside the run span goes to the innermost span holding it
    assert sorted(g[0] for g in gaps) == ["AMBSession.run"] * 3 + [
        "bench.block_until_ready"]
    assert sum(g[1] for g in gaps) == pytest.approx(63e-9)


def test_subtract_and_op_time():
    assert trace.subtract([(0, 10), (20, 30)], [(2, 3), (5, 25)]) == [
        (0, 2), (3, 5), (25, 30)]
    ops = SMALL["devices"][0]["ops"]
    assert trace.op_time_ns(ops[4:5], 0.0, 47.0) == 2.0


def test_top_ops():
    top = trace.top_ops(SMALL, 0.0, 100.0, k=2)
    assert top == [["fusion.2", pytest.approx(15e-9)],
                   ["fusion.1", pytest.approx(10e-9)]]


@pytest.mark.parametrize("text, code, container", [
    ("%while.2 = (s32[], bf16[3]{0}) while((s32[], bf16[3]) %t), body=%b",
     "while", True),
    ("%call.3 = f32[] call(f32[] %a), to_apply=%f", "call", True),
    ("%fusion.1 = (bf16[3]) fusion(u32[3] %x), kind=kLoop, calls=%c",
     "fusion", False),
    ("%custom-call.7 = bf16[3]{0} custom-call(bf16[3] %x)", "custom-call",
     False),
    ("while.7", "while", True)])
def test_opcode_of_hlo_text(text, code, container):
    assert trace.opcode(text) == code
    assert trace.is_container(text) == container


def test_top_ops_leave_out_loops():
    loop = {"devices": {0: {"ops": [
        ["%while.1 = (s32[]) while((s32[]) %t), body=%b", 0.0, 50.0, ""],
        ["%fusion.2 = f32[] fusion(f32[] %x), kind=kLoop", 10.0, 30.0, ""]],
        "modules": []}}, "host": []}
    assert trace.top_ops(loop, 0.0, 100.0) == [["fusion.2",
                                                 pytest.approx(30e-9)]]
    assert trace.busy_ns(loop["devices"][0]["ops"], 0.0, 100.0) == 50.0


# ---------------------------------------------------------------------------
# a recorded chip trace: the opening 74 ms of a train.l12.shexp window on a
# TPU v5 lite (1,500 device ops of the prefetcher's token build)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    path = Path(__file__).resolve().parent / "data" / "trace_train_l12.json"
    rec = json.loads(path.read_text())
    rec["devices"] = {int(k): v for k, v in rec["devices"].items()}
    return rec


def _sweep_busy(ops, lo, hi):
    """Busy time by a sweep over start/end events: an independent count."""
    events = []
    for _, s, d, _ in ops:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            events += [(s, 1), (e, -1)]
    events.sort(key=lambda x: (x[0], -x[1]))
    busy, depth, since = 0.0, 0, None
    for t, step in events:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_busy_and_idle(recorded):
    lo, hi = recorded["window"]
    ops = recorded["devices"][0]["ops"]
    busy = trace.busy_ns(ops, lo, hi)
    assert busy == pytest.approx(_sweep_busy(ops, lo, hi))
    gaps = trace.idle_gaps(ops, lo, hi)
    assert trace.length(gaps) == pytest.approx(hi - lo - busy)
    # the window opens 1.47 ms before the prefetcher's first build starts,
    # inside the run call; that is the longest gap of the opening
    first = min(s for _, s, _, _ in ops)
    assert gaps[0] == (lo, first)
    longest = trace.longest_gaps(recorded, 0, lo, hi, SPANS, k=3)
    assert longest[0] == ["AMBSession.run", pytest.approx((first - lo) * 1e-9)]


def test_dual_update_roofline_counts_the_kernel_calls_only():
    import json as _json
    import types
    from bench import harness, peaks
    conf = _json.loads((harness.BENCH_DIR / "configs"
                        / "qwen2-1.5b-l12.json").read_text())
    ops = [["%dual_update_pallas.18 = f32[8,128]{1,0} custom-call(f32[8,128] "
            "%pad.1), custom_call_target=\"tpu_custom_call\"", 0.0, 1e7, ""],
           ["%convert_element_type.4 = bf16[8,128]{1,0} convert(f32[8,128] "
            "%dual_update_pallas.18)", 1e7, 5e6, ""],
           ["%pad.1 = f32[8,128]{1,0} pad(f32[6,128] %z), padding=0_2x0_0",
            2e7, 5e6, ""]]
    ctx = types.SimpleNamespace(
        trace={"devices": {0: {"ops": ops, "modules": []}}, "host": []},
        lo=0.0, hi=1e9, devices=[0], epochs=1, config=conf,
        peak=peaks.peak("TPU v5 lite"))
    share = harness.metric_reader("dual_update_roofline")(ctx)
    # 12 B per element of every leaf at 819 GB/s, over the 10 ms kernel
    bytes_ = 12 * 1_028_322_816         # every element, biases and norms too
    assert share == pytest.approx(100 * bytes_ / 819e9 / 1e-2)
