"""Operations and bytes each call needs, computed from shapes.

Counts are of the work a call needs, whatever implements it: rows of
padding, masked keys and whole-page reads are not counted, so a kernel
that does less wasted work reads a higher share of its roofline, and no
kernel can read above 100%. One multiply-add is 2 operations.

``m`` is ``reference.dims(conf)``: D hidden, H heads of dh, KV key-value
heads, F MLP width, V vocabulary, L layers.
"""
from __future__ import annotations


def layer_matmuls(m: dict) -> list:
    """(name, K, N) of each weight matrix of one decoder layer."""
    d, f = m["D"], m["F"]
    return [("q", d, m["HD"]), ("k", d, m["KD"]), ("v", d, m["KD"]),
            ("o", m["HD"], d), ("gate", d, f), ("up", d, f),
            ("down", f, d)]


def layer_params(m: dict) -> int:
    return sum(k * n for _, k, n in layer_matmuls(m))


def attention_flops(m: dict, keys: int) -> float:
    """QK^T and PV of one query against ``keys`` keys, all layers."""
    return 4.0 * keys * m["H"] * m["dh"] * m["L"]


def head_flops(m: dict) -> float:
    """Logits of one position against the tied embedding."""
    return 2.0 * m["D"] * m["V"]


def model_flops(m: dict, tokens: int, keys: int, heads: int) -> float:
    """Model operations for ``tokens`` positions that attend to ``keys``
    keys in all (summed over positions) and whose logits are needed at
    ``heads`` positions."""
    return (2.0 * layer_params(m) * m["L"] * tokens
            + attention_flops(m, keys) + head_flops(m) * heads)


def kv_bytes_per_token(m: dict, kv_bits) -> float:
    """Bytes of one token's keys and values in all layers, scales
    included (int8 pages carry one float32 scale per token and head)."""
    per_head = m["dh"] * (2 if kv_bits is None else kv_bits / 8)
    if kv_bits is not None:
        per_head += 4
    return 2.0 * m["KV"] * per_head * m["L"]


def paged_attention(m: dict, rows: int, keys: int, kv_bits) -> tuple:
    """(operations, bytes) of decode attention over the paged pool for
    ``rows`` active slots holding ``keys`` tokens in all, all layers:
    every held key and value read once, queries read and outputs written
    in bfloat16."""
    flops = attention_flops(m, keys)
    io = rows * m["H"] * m["dh"] * 2 * 2 * m["L"]
    return flops, keys * kv_bytes_per_token(m, kv_bits) + io


def roofline_seconds(flops: float, byts: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak bfloat16 rate and bytes over HBM bandwidth."""
    return max(flops / peaks["bf16_flops"], byts / peaks["hbm_bytes_per_s"])
