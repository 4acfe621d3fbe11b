"""``flops.py`` against counts made by hand."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "num_hidden_layers": 3,
        "vocab_size": 10}


def test_gemm_counts_packed_codes_affine_terms_and_activations():
    ops, byt = flops.gemm(4, 256, 128, 4)
    assert ops == 2 * 4 * 256 * 128
    # 4-bit codes: 128 bytes per column; fp32 scale + bias per column;
    # bf16 input rows and output rows
    assert byt == 128 * 128 + 8 * 128 + 4 * 256 * 2 + 4 * 128 * 2


def test_gemm_rounds_packed_rows_up():
    _, byt = flops.gemm(1, 3, 2, 2, act_bytes=0, out_bytes=0)
    assert byt == 1 * 2 + 8 * 2  # 3 two-bit codes fill one byte


def test_paged_attention_reads_only_live_blocks():
    ops, byt = flops.paged_attention([10, 17], heads=4, kv_heads=2,
                                     head_dim=64, block=8)
    assert ops == 4 * 4 * 64 * (10 + 17)
    live = 16 + 24
    per_vec = 64 + (64 // 32) * 2  # int8 codes + fp16 scale per 32
    assert byt == 2 * live * 2 * per_vec + 2 * 2 * 4 * 64 * 2


def test_whole_step_counts():
    per_layer = 8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 16
    n = 3 * per_layer + 8 * 10
    assert flops.matmul_params(TINY) == n
    assert flops.attention_flops(TINY, 5) == 4 * 3 * 2 * 4 * 5
    assert flops.decode_token(TINY, 5) == 2 * n + 4 * 3 * 2 * 4 * 5
    # causal prefill of positions 2..4: contexts 3, 4, 5; head once
    body = 3 * per_layer
    assert flops.prefill(TINY, 2, 5) == (2 * body * 3 + 2 * 8 * 10
                                         + 4 * 3 * 2 * 4 * (3 + 4 + 5))
    assert flops.train_token(TINY, 7) == 6 * n + 3 * 4 * 3 * 2 * 4 * 4
