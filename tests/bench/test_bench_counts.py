"""Operation and byte counts from shapes, and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import counts
from bench.peaks import peaks

ROOT = Path(__file__).resolve().parents[2]


def _phi():
    doc = json.loads((ROOT / "bench/configs/phi4-mini-3.8b.json").read_text())
    return counts.Dense(doc["num_hidden_layers"], doc["hidden_size"],
                        doc["num_attention_heads"],
                        doc["num_key_value_heads"],
                        doc["hidden_size"] // doc["num_attention_heads"],
                        doc["intermediate_size"], doc["vocab_size"])


def test_phi4_mini_parameters_by_hand():
    sh = _phi()
    attn = 3072 * (24 + 2 * 8) * 128 + 24 * 128 * 3072   # q, k, v, o
    ffn = 3 * 3072 * 8192                                # gate, up, down
    assert sh.layer_params == attn + ffn == 100663296
    total = 32 * sh.layer_params + 200064 * 3072
    assert total == 3835822080                           # 3.84B
    assert sh.weight_bytes() == 2 * total
    assert sh.kv_bytes_per_position == 32 * 2 * 8 * 128 * 2 == 128 * 1024


def test_phi4_mini_prefill_and_decode_by_hand():
    sh = _phi()
    per_token = 2 * 32 * 100663296
    attn = 4 * 32 * 24 * 128                             # per (q, k) pair
    head = 2 * 3072 * 200064
    assert sh.prefill_flops(3) == 3 * per_token + attn * 6 + head
    assert sh.decode_flops([10, 20]) == 2 * (per_token + head) + attn * 30
    assert sh.decode_bytes([10, 20]) == (sh.weight_bytes()
                                         + 30 * 128 * 1024)


def test_least_seconds_takes_the_binding_roof():
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000, 50, pk) == 10.0    # compute-bound
    assert counts.least_seconds(100, 500, pk) == 50.0    # memory-bound


def test_peaks_by_device_kind():
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks("cpu")
