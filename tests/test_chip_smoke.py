"""chip_smoke.py off the chip: its refusal, and its phases at toy size.

The script itself only runs on a TPU. Here it must refuse the CPU, and
its phases run at a toy size with the Pallas kernels in interpret mode
(the test forces it) and the Mosaic-kernel check stubbed out, since
nothing is compiled for a TPU. That keeps the script's control flow and
its reference comparisons working between chip runs.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.experimental import pallas as pl

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_refuses_to_run_without_a_tpu():
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout, r.stdout


@pytest.fixture
def interpreted_kernels(monkeypatch):
    pallas_call = pl.pallas_call

    def interpret(*args, **kwargs):
        kwargs["interpret"] = True
        return pallas_call(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", interpret)
    monkeypatch.setattr(chip_smoke, "has_mosaic_kernel", lambda prog: True)


def test_phases_pass_at_toy_size(interpreted_kernels, capsys):
    from repro.configs import get_arch

    cfg = get_arch("qwen1.5-0.5b").scaled(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=256, vocab=512)
    sizes = chip_smoke.Sizes(max_batch=4, max_len=128, n_requests=6,
                             prompt_max=40, new_tokens=8,
                             conv_layer="conv5_1")
    chip_smoke.run(jax.devices()[0], cfg, sizes, seed=0)
    out = capsys.readouterr().out
    for phase in ("kernels", "engine A (default path)",
                  "engine B (packed path)"):
        assert f"PASS {phase}" in out, out
    assert out.count("kernel ") == 11, out
