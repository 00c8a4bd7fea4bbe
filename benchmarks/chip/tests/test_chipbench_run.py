"""A toy-size CPU pass of the cell loop and the metric plug-ins, and the
entry point's refusal of any platform but a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import chipbench_toy
from benchmarks.chip import harness

REPO = chipbench_toy.REPO
E2E = {"output_tok_s", "itl_p95_ms", "ttft_p95_ms", "setup_s"}


def _run_entry(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen05b-bf16.chat", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_entry_refuses_the_cpu():
    p = _run_entry(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_entry_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip")
    p = _run_entry(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return chipbench_toy.toy_root(tmp_path_factory.mktemp("toy"))


def test_toy_cell_runs_and_reports_no_device_metric(toy):
    res = harness.run(toy, "toy-bf16.toy_chat", 2**31 + 99, 2.0, False,
                      time.perf_counter())
    assert res["correct"] is True
    assert set(res["metrics"]) == E2E
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert res["check"]["tokens_compared"]["value"] >= 8
    json.dumps(res)

    traced = harness.run(toy, "toy-bf16.toy_chat", 7, 2.0, True,
                         time.perf_counter())
    assert traced["correct"] is True
    # on the CPU only the program's own counters and spans are read:
    # nothing that comes from a device trace
    assert set(traced["metrics"]) == {"queue_wait_p95_ms",
                                      "prefix_hit_share"}
    assert "busy_s" not in traced["device"]
    assert "breakdown" not in traced
