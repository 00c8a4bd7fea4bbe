"""The trace reduction and the readers of device metrics.

A synthetic window in the TPU trace's naming (ops ``%name.N = type
...``, programs ``jit_name(fingerprint)``) fixes the arithmetic by hand;
a trace recorded here on the CPU checks the reading of the file, the
window span and the spans' keyword stats.
"""
import json
from pathlib import Path

import pytest

from benchmarks.chip import costs, reference, trace
from benchmarks.chip.metrics import (decode_step_ms, device_idle_share, mfu,
                                     paged_attn_roofline, prefill_call_ms,
                                     tick_host_ms)

CONF = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "qwen1.5-0.5b-bf16.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # nanoseconds


def op(name, start_ms, dur_ms):
    return (f"%{name} = bf16[8,16]{{1,0}} custom-call(%p)", start_ms * MS,
            dur_ms * MS, 0)


def synthetic() -> trace.Trace:
    """A 100 ms window: one tick (10-60 ms) holding a decode call (20-50
    ms) whose program runs 22-48 ms; one prefill call (70-90 ms)."""
    ops = [op("paged_decode_attention.2", 22, 10),
           op("fusion.7", 32, 6),
           op("copy.1", 38, 10),
           op("fusion.7", 72, 12),
           op("fusion.3", 84, 4)]
    modules = [("jit_paged_ragged_serve_step", 22 * MS, 26 * MS),
               ("jit_paged_prefill_step", 72 * MS, 16 * MS)]
    spans = [("bench.window", 0, 100 * MS, {}),
             ("bench.tick", 10 * MS, 50 * MS, {}),
             ("bench.decode_call", 20 * MS, 30 * MS,
              {"rows": 8, "keys": 4000, "width": 64}),
             ("bench.prefill_call", 70 * MS, 20 * MS,
              {"rows": 2, "tokens": 600, "keys": 90_000, "bucket": 512,
               "width": 32})]
    return trace.Trace(window=(0, 100 * MS), ops=ops, modules=modules,
                       spans=spans, devices=1)


def record(tr):
    return {"trace": tr, "model": reference.dims(CONF), "peaks": PEAKS,
            "quant": CONF["serving"]["quant"]}


def test_names():
    name = "%copy.124 = bf16[256,16,16,64]{3,2,1,0:T(8,128)} copy(%x)"
    assert trace.op_base(name) == "copy"
    assert trace.op_label(name) == "copy bf16[256,16,16,64]"
    assert trace.module_base("jit_paged_prefill_step(184500)") == \
        "jit_paged_prefill_step"


def test_busy_idle_and_breakdown():
    tr = synthetic()
    # ops cover 22-48 and 72-88 ms: 42 ms busy in 100
    assert tr.busy_intervals(0) == [[22 * MS, 48 * MS], [72 * MS, 88 * MS]]
    assert tr.busy_s() == pytest.approx(0.042)
    assert device_idle_share.value(record(tr)) == pytest.approx(58.0)
    top = dict(trace.top_ops(tr))
    assert top["fusion bf16[8,16]"] == pytest.approx(0.022)
    gaps = dict(trace.idle_gaps(tr))
    # 0-22 (tick open from 10, at mid 11: tick), 48-72 (mid 60: tick
    # ends exactly at 60), 88-100 (none open)
    assert gaps["tick"] == pytest.approx(0.046)
    assert gaps["none"] == pytest.approx(0.012)


def test_program_and_host_times():
    tr = synthetic()
    assert decode_step_ms.value(record(tr)) == pytest.approx(26.0)
    assert prefill_call_ms.value(record(tr)) == pytest.approx(16.0)
    # the 50 ms tick overlaps 26 ms of device time
    assert tick_host_ms.value(record(tr)) == pytest.approx(24.0)


def test_rooflines_and_mfu_by_hand():
    tr, m = synthetic(), reference.dims(CONF)
    f, b = costs.paged_attention(m, 8, 4000, None)
    assert paged_attn_roofline.value(record(tr)) == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 0.010)
    flops = (costs.model_flops(m, 8, 4000, 8)
             + costs.model_flops(m, 600, 90_000, 2))
    assert mfu.value(record(tr)) == pytest.approx(
        100 * flops / (0.1 * 197e12))


def test_no_ops_reads_nothing():
    tr = synthetic()
    tr.ops = []
    for mod in (device_idle_share, paged_attn_roofline):
        assert mod.value(record(tr)) is None


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.decode_call", rows=3,
                                          keys=77, width=4):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.reduce(str(tmp_path))
    assert tr.window[1] > tr.window[0]
    calls = tr.spans_named("bench.decode_call")
    assert len(calls) == 1 and calls[0][3] == {"rows": 3, "keys": 77,
                                               "width": 4}
    # the CPU is no TPU plane: no device op, so no device metric
    assert tr.devices == 0 and tr.ops == []
    assert device_idle_share.value(record(tr)) is None
