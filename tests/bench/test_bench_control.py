"""The control of each training cell comes out not correct: the float32
reference computed from float8 e4m3 operands, put in the program's
place, fails one of the cell's numbers against the float32 reference.
Tiny sizes on the CPU; the chip readings at the cells' sizes are in
PERF.md (``bench/calibrate.py --control``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench_drive import tiny  # noqa: E402

BENCH = harness.load_benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_control_fails_a_number(name):
    cell = tiny(harness.cell(BENCH, name))
    conf, traffic = cell["config"], cell["traffic"]
    train = harness.kind(traffic["kind"])
    devices = jax.devices()[:1]
    ref = train.reference_readings(conf, traffic, 9, devices)
    ctl = train.reference_readings(conf, traffic, 9, devices, quant="e4m3")
    numbers = dict(train.compare(ctl, ref), schedule_misses=0)
    checks, correct = train.judge(numbers, traffic["limits"])
    assert not correct, checks
