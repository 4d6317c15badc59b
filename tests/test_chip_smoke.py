"""chip_smoke.py's phases at the reduced qwen2-1.5b config on the CPU.

The script itself refuses to run without a TPU; these tests drive the
same phase functions here, with the Pallas dual update in interpret mode
standing in for the compiled kernel.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402
from repro.configs import smoke_config  # noqa: E402

from test_dist import run_sub      # canonical forced-device subprocess


@pytest.fixture(scope="module")
def trained():
    cfg = smoke_config(chip_smoke.ARCH)
    session, train = chip_smoke.train_phase(
        cfg, steps=2, seq_len=32, batch_per_worker=2)
    return cfg, session, train


def test_train_phase_losses_finite(trained):
    _, session, train = trained
    assert len(train["loss"]) == 2 and np.all(np.isfinite(train["loss"]))
    assert all(s > 0 for s in train["step_s"])
    assert all(1 <= b <= 2 for b in train["global_batch"])
    assert session.steps_done == 2


def test_dual_update_check_interpret_matches_ref(trained):
    _, session, _ = trained
    diffs = chip_smoke.dual_update_check(session, force="pallas_interpret")
    assert diffs["kernel_vs_ref"] <= chip_smoke.DUAL_UPDATE_TOL
    assert diffs["params_vs_ref"] <= chip_smoke.PARAM_TOL


def test_serve_phase_matches_static_generate(trained):
    cfg, session, _ = trained
    served = chip_smoke.serve_phase(session.params, cfg, mesh=session.mesh,
                                    prompt_len=8, new_tokens=4)
    assert served["tokens"] == served["reference"]
    assert all(len(t) == 4 for t in served["tokens"])


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("consensus", ["gossip", "gossip_q8"])
def test_four_chip_phase_on_host_devices(consensus):
    """The --four-chips phase on 4 forced host devices, every kernel in
    interpret mode under shard_map: each worker's dual replica equals the
    same epoch on the jnp reference route, one dual replica per device,
    FSDP-sharded params; for fp32 gossip, the node-averaged primal equals
    exact consensus."""
    out = run_sub(f"""
        import chip_smoke
        from repro.configs import smoke_config
        from repro.kernels import router
        router.set_mode("pallas_interpret")
        out = chip_smoke.four_chip_phase(smoke_config(chip_smoke.ARCH),
                                         consensus="{consensus}",
                                         seq_len=32)
        assert out["z_vs_ref"] <= chip_smoke.GOSSIP_TOL, out
        if "{consensus}" == "gossip":
            assert out["primal_diff"] <= chip_smoke.PRIMAL_TOL, out
        assert out["replicas_ok"] and out["fsdp_ok"], out
        print("FOUR_OK", out)
    """, devices=4)
    assert "FOUR_OK" in out
