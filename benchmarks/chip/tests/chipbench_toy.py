"""A toy-size copy of the benchmark for CPU tests.

``toy_root(tmp)`` copies ``benchmarks/chip`` and writes a
``BENCHMARK.json`` whose cells run a 2-layer Qwen2 of toy widths under
short mixes, each file added the way a later change would add one.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[3]
CHIP = REPO / "benchmarks" / "chip"

TOY_MODEL = {
    "model_type": "qwen2", "hidden_act": "silu", "hidden_size": 256,
    "initializer_range": 0.1, "intermediate_size": 512,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "tie_word_embeddings": True, "use_sliding_window": False,
    "vocab_size": 512, "adapter": "adapters/qwen2.py",
    "reference": "reference.py",
    "weights": {"bias_std": 0.1, "gain_std": 0.1, "qk_std": 0.1},
}

SERVING = {"max_batch": 4, "max_len": 256, "page_size": 16,
           "num_pages": 64, "policy": "fifo", "max_queue": 256}

CONFIGS = {
    "toy-bf16": dict(
        TOY_MODEL,
        precision={"stated": {"weight_bits": None, "embed_bits": None,
                              "kv_bits": None},
                   "control": {"weight_bits": 8, "embed_bits": 8,
                               "kv_bits": 8, "act_bits": 8}},
        serving=dict(SERVING, quant=None)),
    "toy-w4kv8": dict(
        TOY_MODEL,
        precision={"stated": {"weight_bits": 4, "embed_bits": None,
                              "kv_bits": 8},
                   "control": {"weight_bits": 3, "embed_bits": 8,
                               "kv_bits": 4, "act_bits": 8}},
        serving=dict(SERVING, quant={"bits": 4, "backend": "pallas",
                                     "kv_bits": 8})),
}

# bf16 at the published widths with 2 layers and a smaller vocabulary:
# at toy widths, bf16 rounding in the program is as large as the int8
# control's error, and the control cannot be told from a sound run
CONFIGS["toy-wide-bf16"] = dict(
    CONFIGS["toy-bf16"], hidden_size=1024, intermediate_size=2816,
    num_attention_heads=16, num_key_value_heads=16, vocab_size=8192,
    initializer_range=0.02,
    weights=dict(TOY_MODEL["weights"], qk_std=0.02))

MIXES = {
    "toy_chat": {
        "loop": "open_loop", "rate_per_s": 12.0,
        "prompt": {"shared_prefixes": {
            "count": 2, "length": {"dist": "uniform", "min": 16, "max": 40},
            "zipf_s": 1.1},
            "unique": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                       "min": 4, "max": 30}},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 4, "max": 16},
        "trace_seconds": 1, "check_requests": 4},
}

# limits of the toy cells, set as a real cell's are: above the largest
# mean gap of sound runs over six seeds (bf16: 0.0024; wide bf16: 1.6e-4
# over longer sequences; w4kv8: 0.054) and below the control's smallest
# where it separates (wide bf16: 7e-4; w4kv8: 2.3)
CHECKS = {
    "toy-bf16": {"served_gap_mean": {"limit": 0.01},
                 "tokens_compared": {"limit": 8}},
    "toy-wide-bf16": {"served_gap_mean": {"limit": 5e-4},
                      "tokens_compared": {"limit": 8}},
    "toy-w4kv8": {"served_gap_mean": {"limit": 0.5},
                  "tokens_compared": {"limit": 8}},
}


def toy_root(tmp) -> Path:
    root = Path(tmp)
    shutil.copytree(CHIP, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    chip = root / "benchmarks" / "chip"
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name, conf in CONFIGS.items():
        path = f"benchmarks/chip/configs/{name}.json"
        (root / path).write_text(json.dumps(conf))
        spec["configs"].append({"name": name, "source": "toy",
                                "file": path, "reduced": [], "why": "toy"})
    for name, mix in MIXES.items():
        (chip / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for conf in CONFIGS:
        for mix in MIXES:
            cell = f"{conf}.{mix}"
            spec["workloads"].append({"name": cell, "config": conf,
                                      "traffic": mix, "chips": 1,
                                      "why": "toy"})
            (chip / "checks" / f"{cell}.json").write_text(
                json.dumps(CHECKS[conf]))
    cells = [w["name"] for w in spec["workloads"]]
    for kind in ("end_to_end", "per_layer"):
        for e in spec[kind]:
            e["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
