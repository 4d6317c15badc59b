#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

  python3 bench/run.py --workload train.l12.shexp --seed 7 --seconds 20 --trace 0

The cell, its configuration, its traffic and its job kind are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics that ``bench/metrics/<name>.py`` read from it.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``; its last key, ``checks``, gives each number compared
beside its limit, as do the last lines of standard error.  Without a TPU,
or with fewer chips than the cell needs, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
import types                                                 # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, peaks, trace                      # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(bench: dict, name: str, ctx) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in harness.cell_metrics(bench, name, "per_layer"):
        value = harness.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced(res: dict, chips: int, device_kind: str, cell: dict):
    """Reader context and device times from the window's trace."""
    tr = trace.load(harness.TRACE_DIR)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    lo, hi = trace.window(tr, res["window_span"])
    devs = sorted(tr["devices"])[:chips]
    busy = [trace.busy_ns(tr["devices"][d]["ops"], lo, hi) for d in devs]
    ctx = types.SimpleNamespace(
        trace=tr, lo=lo, hi=hi, devices=devs, window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9, peak=peaks.peak(device_kind),
        chips=chips, epochs=res["epochs"],
        credited_tokens=res["credited_tokens"], config=cell["config"],
        traffic=cell["traffic"])
    breakdown = {"device_ops": trace.top_ops(tr, lo, hi),
                 "idle_gaps": trace.longest_gaps(tr, devs[0], lo, hi,
                                                 res["host_spans"])}
    return ctx, breakdown


def main(argv=None) -> int:
    args = _args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell(bench, args.workload)
    chips = cell["workload"]["chips"]
    try:
        device = harness.check_devices(chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    timer = harness.CompileTimer()
    ctx = types.SimpleNamespace(config=cell["config"],
                                traffic=cell["traffic"], seed=args.seed,
                                seconds=args.seconds, trace=bool(args.trace),
                                t_start=T_START, timer=timer)
    res = harness.kind(cell["traffic"]["kind"]).run(ctx)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        rctx, breakdown = traced(res, chips, device["kind"], cell)
        device["busy_s"], device["window_s"] = rctx.busy_s, rctx.window_s
        out["metrics"] = per_layer(bench, args.workload, rctx)
        out["breakdown"] = breakdown
    else:
        e2e = dict(res["end_to_end"], setup_s=res["setup_s"])
        out["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in harness.cell_metrics(bench, args.workload, "end_to_end")}
    out["device"] = device
    # step_s: the epochs from dispatch to their loss on the host; the
    # rest of the window is host work between epochs
    out["window"] = {"epochs": res["epochs"], "seconds": res["window_s"],
                     "step_s": res["step_s"],
                     "credited_share": res["credited_share"],
                     "compiles": res["compiles_in_window"],
                     "compile_s": timer.seconds}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, v, lim in res["checks"]}
    for k, v, lim in res["checks"]:
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
