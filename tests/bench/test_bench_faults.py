"""A whole run of ``train.l12.shexp`` on the CPU at a tiny size: the
program as it is comes out correct, and each fault the cell can have,
planted in the timed path, comes out not correct under the cell's limits."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import pytest  # noqa: E402

from bench.faults import FAULTS  # noqa: E402
from bench_drive import drive  # noqa: E402


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    """One compile cache for the file's runs: the first one compiles."""
    return tmp_path_factory.mktemp("jax_cache")


@pytest.mark.parametrize("fault", FAULTS)
def test_run_correct_only_without_fault(fault, cache):
    res = drive("train.l12.shexp", fault, cache)
    checks = {k: (v["value"], v["limit"]) for k, v in res["checks"].items()}
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] == (fault == "none"), checks
