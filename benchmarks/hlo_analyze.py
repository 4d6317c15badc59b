"""HLO breakdown tool for §Perf hillclimbing (CPU dry-run profiling).

Lowers one (arch x shape) on the production mesh (depth-p unrolled variant,
same as the roofline measurement), compiles, and prints:

  * cost_analysis totals,
  * top ops by output bytes (what dominates the memory term),
  * every collective with shape + bytes (what dominates the collective term).

Usage:
    PYTHONPATH=src python -m benchmarks.hlo_analyze --arch qwen3-moe-30b-a3b \
        --shape train_4k [--top 25] [--layers 1]
"""
from __future__ import annotations

# must run before jax import (see repro.launch.dryrun)
from repro.launch import dryrun as D  # noqa: F401  (sets XLA_FLAGS)

import argparse      # noqa: E402
import collections   # noqa: E402
import re            # noqa: E402

import numpy as np   # noqa: E402

from repro.configs import SHAPES, get_config            # noqa: E402
from repro.dist import use_sharding                     # noqa: E402
from repro.models.common import unrolled_loops          # noqa: E402

_SHAPE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?\S+\s*=\s*(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"=\s*\w+\[[\d,]*\]\S*\s+(\S+?)\(")


def tensor_bytes(dt: str, dims: str) -> int:
    nbytes = D._DTYPE_BYTES.get(dt, 4)
    size = 1
    for d in dims.split(","):
        if d:
            size *= int(d)
    return size * nbytes


def analyze(arch: str, shape_name: str, layers: int, top: int,
            multi_pod: bool = False):
    shape = SHAPES[shape_name]
    cfg = get_config(arch, shape=shape_name)
    cfg = D._depth_variant(cfg, layers, shape.seq_len)
    mesh = D._mesh(multi_pod)
    with use_sharding(mesh), unrolled_loops():
        lowered = D._lower_combo(cfg, shape, mesh)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    print(f"== {arch} x {shape_name} (layers={layers}) "
          f"mesh={'2x16x16' if multi_pod else '16x16'}")
    print(f"flops/chip={ca.get('flops', 0):.4g}  "
          f"bytes/chip={ca.get('bytes accessed', 0):.4g}")

    text = compiled.as_text()
    by_op = collections.Counter()
    by_op_count = collections.Counter()
    colls = []
    for line in text.splitlines():
        m = _SHAPE_RE.match(line)
        if not m:
            continue
        dt, dims = m.group(1), m.group(2)
        if dt not in D._DTYPE_BYTES:
            continue
        nb = tensor_bytes(dt, dims)
        om = _OP_RE.search(line)
        op = om.group(1) if om else "?"
        by_op[op] += nb
        by_op_count[op] += 1
        if op.split(".")[0] in ("all-reduce", "all-gather", "reduce-scatter",
                                "all-to-all", "collective-permute"):
            colls.append((op, dt, dims, nb))

    print(f"\n-- top {top} ops by summed output bytes --")
    for op, nb in by_op.most_common(top):
        print(f"{nb / 2**20:12.1f} MiB  x{by_op_count[op]:<5d} {op}")

    print("\n-- collectives --")
    agg = collections.Counter()
    cnt = collections.Counter()
    for op, dt, dims, nb in colls:
        key = (op.split(".")[0], dt, dims)
        agg[key] += nb
        cnt[key] += 1
    for (op, dt, dims), nb in agg.most_common(40):
        print(f"{nb / 2**20:12.2f} MiB  x{cnt[(op, dt, dims)]:<4d} "
              f"{op:20s} {dt}[{dims}]")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    analyze(args.arch, args.shape, args.layers, args.top, args.multi_pod)


if __name__ == "__main__":
    main()
