"""A new configuration, traffic mix and per-layer metric are added by
new files and ``BENCHMARK.json`` entries alone; no file the benchmark
already has is edited."""
import hashlib
import json
import time

import chipbench_toy
from benchmarks.chip import harness


def _digest(root):
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "benchmarks" / "chip").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_alone_add_a_cell(tmp_path):
    root = chipbench_toy.toy_root(tmp_path)
    chip = root / "benchmarks" / "chip"
    before = _digest(root)

    conf = dict(chipbench_toy.CONFIGS["toy-bf16"])
    conf["serving"] = dict(conf["serving"], max_batch=2)
    (chip / "configs" / "toy-narrow.json").write_text(json.dumps(conf))
    mix = dict(chipbench_toy.MIXES["toy_chat"], rate_per_s=4.0)
    (chip / "traffic" / "toy_slow.json").write_text(json.dumps(mix))
    (chip / "metrics" / "toy_requests_sent.py").write_text(
        "def value(rec):\n    return len(rec['sent'])\n")
    (chip / "checks" / "toy-narrow.toy_slow.json").write_text(
        json.dumps(chipbench_toy.CHECKS["toy-bf16"]))

    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-narrow", "source": "toy",
                            "file": "benchmarks/chip/configs/toy-narrow.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "toy-narrow.toy_slow",
                              "config": "toy-narrow", "traffic": "toy_slow",
                              "chips": 1, "why": "toy"})
    spec["per_layer"].append({
        "name": "toy_requests_sent", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "front door",
        "moves": "output_tok_s", "workloads": ["toy-narrow.toy_slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    res = harness.run(root, "toy-narrow.toy_slow", 3, 2.0, True,
                      time.perf_counter())
    assert res["correct"] is True
    assert res["metrics"]["toy_requests_sent"]["value"] >= 1
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
