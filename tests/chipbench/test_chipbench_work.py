"""The FLOP and byte functions against hand counts for one small shape,
and the table of peaks."""

import json
from pathlib import Path

from chipbench import work

REPO = Path(__file__).resolve().parents[2]
C = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
     "num_key_value_heads": 2, "head_dim": 4, "num_hidden_layers": 3,
     "vocab_size": 10, "compute_dtype": "bfloat16"}


def test_layer_params_by_hand():
    # q, k, v, o: 4 * 8 * 8; gate, up, down: 3 * 8 * 16
    assert work.layer_matmul_params(C) == 256 + 384
    assert work.readout_params(C) == 80


def test_serve_request_flops_by_hand():
    # prompt 2, 2 new tokens: a prefill of 2 tokens (positions 0, 1; the
    # last read out), then one decode token at position 2.
    dense = 2 * 3 * 640
    head = 2 * 80
    att = lambda ctx: 4 * 3 * 2 * 4 * ctx
    prefill = 2 * dense + head + att(1) + att(2)
    decode = dense + head + att(3)
    assert work.serve_request_flops(C, 1, 2, 2) == prefill + decode
    assert work.serve_request_flops(C, 4, 2, 2) == 4 * (prefill + decode)
    assert work.serve_request_flops(C, 1, 2, 1) == prefill


def test_train_step_flops_by_hand():
    tokens = 2 * 5
    want = 6 * 3 * 640 * tokens + 6 * 80 * 2 * 4 + 12 * 3 * 2 * 4 * 5 * tokens
    assert work.train_step_flops(C, 2, 5) == want


def test_softmax_and_uniform_bytes_by_hand():
    # 1 row, 2 heads, 3 queries: 1 + 2 + 3 live scores per head, 4 + 2 bytes each
    assert work.softmax_bytes(C, 1, 3) == 2 * 6 * 6
    assert work.softmax_bytes(C, 2, 1) == 2 * 2 * 1 * 6
    assert work.uniform_bytes(C) == 40


def test_peaks_table():
    table = json.loads((REPO / "chipbench" / "peaks.json").read_text())
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
