"""Operations and bytes of a step, checked by hand at a tiny shape, and
the table of peaks."""

import json
import os

import numpy as np
import pytest

import run
from models import dense_lm

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 10,
        "rms_norm_eps": 1e-5}


def test_matmul_params_by_hand():
    # per layer: wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + 3 x 8x16 = 576
    # head 8x10 = 80
    assert dense_lm.matmul_params(TINY) == 3 * 576 + 80


def test_row_flops_by_hand():
    # one row at position 4 (5 keys): 2 * 1808 + 4 * 3 layers * 8 * 5
    assert dense_lm.row_flops(TINY, [5]) == 2 * 1808 + 4 * 3 * 8 * 5
    assert dense_lm.row_flops(TINY, np.array([1, 2])) == \
        2 * (2 * 1808) + 4 * 3 * 8 * 3


def test_decode_step_bytes_by_hand():
    # weights: (1808 matmul + 7 norm vectors of 8) * 4 bytes
    weights = (1808 + 7 * 8) * 4
    # one K/V row: 2 * 3 layers * 1 kv head * 4 dims * 4 bytes = 96
    per_slot = lambda n: 96 * (n + 1) + 8 * 4 + 10 * 4
    assert dense_lm.decode_step_bytes(TINY, [3, 0]) == \
        weights + per_slot(3) + per_slot(0)
    assert dense_lm.kv_bytes_per_token(TINY) == 96


def test_peaks_table_and_unknown_kind():
    p = run.peaks_for("TPU v5 lite")
    assert p["matmul_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(run.BenchError):
        run.peaks_for("TPU v99 imaginary")


def test_every_peak_has_a_source():
    with open(os.path.join(run.BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    assert table and all(v.get("source") for v in table.values())
