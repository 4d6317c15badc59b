"""Drive a whole benchmark run on the CPU at a tiny size, with a fault.

  python tests/bench/bench_drive.py CELL FAULT

Skips the harness's look for a chip, shrinks the cell's configuration and
traffic to a few hundred thousand parameters (every limit and every other
setting kept), plants FAULT in the program's timed path (one of
``bench.faults.FAULTS``), runs ``bench/run.py``'s ``main`` and prints its
result line.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# float32 weights: the CPU cannot run the bf16 x bf16 -> f32 products of
# the chip, and bf16 products without f32 accumulation would read gaps
# the chip's path does not have
TINY = {"hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
        "num_hidden_layers": 2, "torch_dtype": "float32",
        "program_options": {"q_chunk": 16, "kv_chunk": 16}}
SEQ_LEN = 32


def tiny(cell: dict) -> dict:
    """``cell`` shrunk to the tiny size."""
    return dict(cell, config=dict(cell["config"], **TINY),
                traffic=dict(cell["traffic"], seq_len=SEQ_LEN))


def drive(cell: str, fault: str, cache) -> dict:
    """Run this script on a CPU device; its result line."""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, os.path.abspath(__file__), cell,
                          fault], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(cell_name: str, fault: str) -> int:
    from bench import faults, harness, run

    real = harness.cell
    harness.cell = lambda bench, name: tiny(real(bench, name))
    harness.check_devices = lambda chips: {"platform": "cpu", "kind": "cpu",
                                           "count": chips}
    faults.plant(fault)
    return run.main(["--workload", cell_name, "--seed", "2147483647",
                     "--seconds", "0.5", "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
