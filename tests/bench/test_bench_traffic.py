"""The benchmark's own traffic: token rows from the seed, and the b_i(t)
schedule of the straggler model and the Lemma-6 budget, which has to be
the one the program's clock gives, whatever seconds it measures."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness, reference, traffic  # noqa: E402

BENCH = harness.load_benchmark()
CELL = harness.cell(BENCH, "train.l12.shexp")


def _traffic(straggler: str, workers: int, per: int) -> dict:
    clock = dict(CELL["traffic"]["clock"], straggler=straggler)
    return dict(CELL["traffic"], clock=clock, workers=workers,
                batch_per_worker=per)


@pytest.mark.parametrize("straggler,workers,per", [
    ("shifted_exp", 1, 3), ("shifted_exp", 4, 8), ("deterministic", 2, 5)])
def test_schedule_is_the_program_clocks(straggler, workers, per):
    from repro.api import ClockSpec
    from repro.api.clock import make_clock
    from repro.core.stragglers import amb_batch_sizes
    t = _traffic(straggler, workers, per)
    epochs = np.arange(400)
    want = traffic.batch_sizes(t, epochs)
    clock = make_clock(ClockSpec(**t["clock"]), workers, per)
    key = jax.random.PRNGKey(t["schedule_seed"])
    rng = np.random.default_rng(0)
    for e in epochs:
        # the measured unit moves from epoch to epoch; b does not
        clock.update(float(rng.uniform(0.01, 3.0)), float(workers * per))
        times, budget = clock.epoch(jax.random.fold_in(key, 10_000 + int(e)))
        got = np.asarray(amb_batch_sizes(times, budget))
        assert (got == want[e]).all(), (e, got, want[e])
    assert want.max() == per and (want.min() < per) == (straggler != "deterministic")


def test_schedule_of_the_cell():
    b = traffic.batch_sizes(CELL["traffic"], range(3000))[:, 0]
    # three reference batches within 4/3 of the mean time, two within 2x
    p3 = 1 - np.exp(-(4 / 3 * 2.5 - 1) * 2 / 3)
    assert abs((b == 3).mean() - p3) < 0.03
    assert b[:3].tolist() == [2, 1, 1]


def test_token_rows_from_the_seed():
    t = CELL["traffic"]
    feed = traffic.TokenRows(t, 151936, 2**31 + 5)
    tok, lab = feed.rows(7)
    assert tok.shape == lab.shape == (3, 1024) and tok.dtype == np.int32
    assert (tok[:, 1:] == lab[:, :-1]).all()
    assert 0 <= tok.min() and max(tok.max(), lab.max()) < 151936
    again = traffic.TokenRows(t, 151936, 2**31 + 5).batch(7)
    assert (again["tokens"] == tok).all() and (again["labels"] == lab).all()
    assert not (feed.rows(8)[0] == tok).all()
    assert not (traffic.TokenRows(t, 151936, 3).rows(7)[0] == tok).all()
    # Zipf: id 0 is drawn about 1 / H(151936, 1) of the time
    ids = np.concatenate([feed.rows(e)[0].ravel() for e in range(20)])
    assert abs((ids == 0).mean() - 1 / np.sum(1.0 / np.arange(1, 151937))) < 0.01


def test_stored_change_rounds_like_bfloat16():
    rng = np.random.default_rng(1)
    w0 = {"m": jnp.asarray(rng.normal(size=(64, 32)), jnp.bfloat16),
          "n": jnp.asarray(1 + 0.1 * rng.normal(size=(32,)), jnp.float32)}
    z = {"m": jnp.asarray(rng.normal(size=(64, 32)) * 0.5, jnp.float32),
         "n": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
    beta = jnp.float32(280.0)
    got = np.asarray(reference.stored_change_norms(w0, z, beta))
    a = np.asarray(w0["m"], np.float32)
    w = (a - np.asarray(z["m"]) / (2 * np.float32(280.0)))
    m = np.linalg.norm(w.astype(ml_dtypes.bfloat16).astype(np.float32) - a)
    n = np.linalg.norm(np.asarray(z["n"]) / (2 * np.float32(280.0)))
    assert 0 < m < 0.9 * np.linalg.norm(w - a)   # rounding drops small steps
    np.testing.assert_allclose(got, [m, n], rtol=1e-5)


def test_steps_run_on_the_stored_weights():
    w0 = jnp.asarray([1.0, -2.0, 0.5], jnp.bfloat16)
    z = jnp.asarray([0.3, 1e-3, -40.0], jnp.float32)
    w = reference._primal(w0, z, jnp.float32(10.0))
    want = (np.asarray(w0, np.float32) - np.asarray(z) / 20).astype(
        ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(w), want)
    # the gradient passes the rounding: d w / d z = -1 / (2 beta)
    g = jax.grad(lambda z: reference._primal(w0, z, jnp.float32(10.0)).sum())(z)
    np.testing.assert_allclose(np.asarray(g), -0.05)
