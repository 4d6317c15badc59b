#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from.

  python3 bench/calibrate.py --workload train.l12.shexp --seeds 11 12 13
  python3 bench/calibrate.py --workload train.l12.shexp --seeds 11 --control 1
  python3 bench/calibrate.py --workload train.l12.shexp --seeds 11 --fault prox

For each seed, in one process: the program's set-up epochs exactly as a
benchmark run makes them, with ``--fault`` planted in the program
(``bench/faults.py``), then the float32 reference, each number compared
and judged against the cell's limits.  For the first ``--control`` seeds
also the control: the reference computed from float8 e4m3 operands, put
in the program's place.  Prints one JSON line per seed with every
number, its limit, ``correct`` for the program and for the control, and
each leaf's norms.  It needs the cell's chips, as a run does, and
measures no window.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default="none", choices=faults.FAULTS)
    ap.add_argument("--control", type=int, default=0,
                    help="seeds (the first ones) that also read the control")
    args = ap.parse_args(argv)
    import jax
    bench = harness.load_benchmark()
    cell = harness.cell(bench, args.workload)
    try:
        harness.check_devices(cell["workload"]["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    faults.plant(args.fault)
    train = harness.kind(cell["traffic"]["kind"])
    conf, traffic = cell["config"], cell["traffic"]
    limits = traffic["limits"]
    steps = traffic["check_steps"]
    devices = jax.devices()[:traffic["workers"]]
    for i, seed in enumerate(args.seeds):
        session, feed, prog = train.setup(conf, traffic, seed, devices)
        session.close()
        del session, feed
        gc.collect()
        ref = train.reference_readings(conf, traffic, seed, devices)
        numbers = train.compare(prog, ref)
        numbers["schedule_misses"] = train.schedule_misses(
            traffic, range(steps), prog["b"])
        checks, correct = train.judge(numbers, limits)
        line = {"seed": seed, "fault": args.fault,
                "b": [b.tolist() for b in prog["b"]],
                "loss": prog["loss"], "ref_loss": ref["loss"],
                "checks": checks, "correct": correct}
        if i < args.control:
            ctl = train.reference_readings(conf, traffic, seed, devices,
                                           quant="e4m3")
            numbers = dict(train.compare(ctl, ref), schedule_misses=0)
            checks, correct = train.judge(numbers, limits)
            line["control"] = {"checks": checks, "correct": correct}
            del ctl
        line["leaves"] = {k: {"program": prog[k].tolist(),
                              "ref": ref[k].tolist()}
                          for k in ("grad1", "param")}
        print(json.dumps(line), flush=True)
        del ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
