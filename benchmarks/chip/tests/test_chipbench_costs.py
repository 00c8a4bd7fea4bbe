"""Cost functions against hand counts for one Qwen1.5-0.5B decode step."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import costs, reference

CONF = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "qwen1.5-0.5b-bf16.json").read_text())
M = reference.dims(CONF)


def test_layer_params_by_hand():
    # q, k, v, o: 4 x 1024 x 1024; gate, up, down: 3 x 1024 x 2816
    assert costs.layer_params(M) == 4_194_304 + 8_650_752 == 12_845_056


def test_kv_bytes_per_token_by_hand():
    # 24 layers x (K, V) x 16 heads x 64 x 2 bytes
    assert costs.kv_bytes_per_token(M, None) == 98_304
    # int8 lanes plus one float32 scale per token and head
    assert costs.kv_bytes_per_token(M, 8) == 24 * 2 * 16 * (64 + 4)
    assert costs.kv_bytes_per_token(M, 8) == 52_224


def test_decode_step_flops_by_hand():
    # 8 slots at positions 99, 199, ..., 799: keys 100 + ... + 800
    keys = sum(100 * (i + 1) for i in range(8))
    layers = 2 * 12_845_056 * 24 * 8
    attn = 4 * keys * 16 * 64 * 24
    head = 2 * 1024 * 151_936 * 8
    assert costs.model_flops(M, 8, keys, 8) == layers + attn + head


def test_paged_attention_by_hand():
    f, b = costs.paged_attention(M, rows=8, keys=3600, kv_bits=8)
    assert f == 4 * 3600 * 16 * 64 * 24
    # keys and values with scales, plus q in and out per row and layer
    assert b == 3600 * 52_224 + 8 * 16 * 64 * 2 * 2 * 24


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.roofline_seconds(197e12, 1.0, peaks) == pytest.approx(1.0)
    assert costs.roofline_seconds(1.0, 819e9, peaks) == pytest.approx(1.0)
