"""Kernel cost functions against hand-worked shapes; the peaks table."""

import pytest

from chipbench import spec
from chipbench.kernel_costs import decode_attention

V03 = {"num_attention_heads": 32, "num_key_value_heads": 8,
       "hidden_size": 4096, "num_hidden_layers": 16,
       "torch_dtype": "bfloat16", "sliding_window": None}


def test_decode_attention_full_context_by_hand():
    # 1000 cached positions + the token itself = 1001 keys.
    flops, bytes_ = decode_attention.cost(1000, V03)
    # per layer: Q.K^T 2*1001*32*128, P.V the same -> 4*1001*4096
    assert flops == 16 * 4 * 1001 * 4096
    # per layer: K and V, 1001 positions x 8 heads x 128 x 2 B each,
    # plus q in and o out: 2 x 32 x 128 x 2 B
    assert bytes_ == 16 * (2 * 1001 * 8 * 128 * 2 + 2 * 32 * 128 * 2)


def test_decode_attention_window_caps_what_is_needed():
    v01 = dict(V03, sliding_window=4096)
    assert decode_attention.cost(12000, v01) == \
        decode_attention.cost(4095, v01)
    assert decode_attention.cost(100, v01) == decode_attention.cost(100, V03)
    f, b = decode_attention.cost(12000, v01)
    assert f == 16 * 4 * 4096 * 4096
    assert b == 16 * (2 * 4096 * 8 * 128 * 2 + 2 * 32 * 128 * 2)


def test_decode_attention_is_memory_bound_on_v5e():
    p = spec.peaks_for("TPU v5 lite")
    f, b = decode_attention.cost(8000, V03)
    assert b / p["hbm_bytes_s"] > f / p["bf16_flops"]


def test_peaks_table():
    p = spec.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError, match="not in chipbench/peaks.json"):
        spec.peaks_for("cpu")
    with pytest.raises(KeyError):
        spec.peaks_for("TPU v9 imaginary")
