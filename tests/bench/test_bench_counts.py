"""Work counts of the benchmark against hand counts at small shapes."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

from bench import counts  # noqa: E402

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 4, "intermediate_size": 16, "vocab_size": 32,
         "num_hidden_layers": 3}


def test_matmul_params_by_hand():
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8, gate/up 8x16, down 16x8
    per_layer = 64 + 32 + 32 + 64 + 128 + 128 + 128
    assert counts.matmul_params_per_layer(SMALL) == per_layer
    assert counts.matmul_params(SMALL) == 3 * per_layer + 8 * 32


@pytest.mark.parametrize("seq_len", [1, 4, 16])
def test_train_flops_per_token_by_hand(seq_len):
    per_layer = 576
    dense = 6 * (3 * per_layer + 256)
    # causal: mean context (S + 1) / 2; QK and PV 2 * H * hd * ctx each,
    # forward + backward = 3x
    attn = 3 * 3 * (2 + 2) * 2 * 4 * (seq_len + 1) / 2
    assert counts.train_flops_per_token(SMALL, seq_len) == pytest.approx(
        dense + attn)


def test_leaf_sizes_cover_every_parameter():
    sizes = counts.param_leaf_sizes(SMALL)
    per_layer = 8 + 8 + 576 + 8 + 4 + 4     # norms, matrices, biases
    assert sum(sizes) == 2 * 32 * 8 + 8 + 3 * per_layer
    assert len(sizes) == 15


def test_dual_update_bytes_by_hand():
    # read z and w0, write w: three f32 per element
    assert counts.dual_update_bytes([10, 6]) == 3 * 4 * 16


def test_qwen2_flops_per_credited_token():
    import json
    from bench.harness import BENCH_DIR
    conf = json.loads((BENCH_DIR / "configs" / "qwen2-1.5b-l12.json").read_text())
    # 794,886,144 matrix-product weights; 6 each plus causal attention
    assert counts.matmul_params(conf) == 794_886_144
    assert counts.train_flops_per_token(conf, 1024) == pytest.approx(
        6 * 794_886_144 + 12 * 6 * 12 * 128 * 1025)
