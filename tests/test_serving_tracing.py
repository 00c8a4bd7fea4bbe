"""Spans, stamps and counters of the serving path.

``tracing.span`` must cost nothing but a check while no profiler
records; the engine's work counters and its ``t_prefill`` stamp must be
exact on a scripted run; the front door exports the new histogram and
the process's compile count.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.serving import AsyncServer, Request, tracing
from repro.serving.metrics import parse_prometheus


def test_span_computes_no_stats_without_a_profiler():
    calls = []

    def stats():
        calls.append(1)
        return {"n": 1}

    with tracing.span("serve.test", stats):
        pass
    assert calls == []
    # one shared no-op context, whatever the name
    assert tracing.span("serve.a", stats) is tracing.span("serve.b")


def test_span_computes_stats_while_the_profiler_records(tmp_path):
    calls = []

    def stats():
        calls.append(1)
        return {"n": 1}

    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("serve.test", stats):
            pass
    assert calls == [1]


def test_engine_computes_no_span_stats_without_a_profiler(serving):
    eng = serving.engine(max_batch=2)

    def boom(*_):
        raise AssertionError("span stats computed with the profiler off")

    eng._decode_stats = boom
    eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                       max_tokens=3))
    done = eng.run_to_completion()
    assert len(done) == 1 and len(done[0].generated) == 3


def test_work_counters_are_exact(serving):
    eng = serving.engine(max_batch=2, max_len=64, page_size=8,
                         prefix_sharing=False)
    eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                       max_tokens=4))
    eng.submit(Request(rid=1, prompt=np.arange(9, dtype=np.int32) + 50,
                       max_tokens=2))
    eng.run_to_completion()
    s = eng.stats
    # one batched prefill of suffixes 5 and 9 in a [2, 16] block
    assert s["prefill_calls"] == 1
    assert s["prefill_tokens"] == 14
    assert s["prefill_positions"] == 2 * 16
    # rid 1 needs one decode step after its prefill token, rid 0 three
    assert s["decode_steps"] == 3
    assert s["decode_rows"] == 2 + 1 + 1
    assert s["decode_slots"] == 3 * 2


def test_t_prefill_orders_and_survives_preemption(serving):
    prompts = [(np.arange(12) + 17 * i) % 256 for i in range(3)]
    eng = serving.engine(max_batch=2, page_size=8, num_pages=6,
                         admission="optimistic", prefix_sharing=False)
    seen = {}
    prefill = eng._prefill_batch

    def record(slots, reqs, effs, starts):
        for r in reqs:
            seen.setdefault(r.rid, []).append(r.t_prefill)
        return prefill(slots, reqs, effs, starts)

    eng._prefill_batch = record
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.astype(np.int32), max_tokens=20))
    eng.run_to_completion()
    assert eng.stats["preemptions"] > 0, eng.stats
    resumed = [rid for rid, stamps in seen.items() if len(stamps) > 1]
    assert resumed
    for r in eng.finished:
        assert r.error is None
        assert r.t_submit <= r.t_prefill <= r.t_admit
        # stamped by the first prefill launch, kept through the resume
        first = seen[r.rid]
        assert first[0] is None
        assert all(t == r.t_prefill for t in first[1:])


def test_snapshot_exports_queue_wait_and_compiles(serving):
    eng = serving.engine(max_batch=2)
    server = AsyncServer(eng, policy="fifo", max_queue=8)

    async def go():
        await server.start()
        streams = [server.submit(np.arange(4 + i), 3) for i in range(3)]
        await asyncio.gather(*(s.collect() for s in streams))
        await server.stop()

    asyncio.run(go())
    before = tracing.compiles()
    jax.monitoring.record_event_duration_secs(
        tracing.BACKEND_COMPILE_EVENT, 0.5)
    assert tracing.compiles() == before + 1
    snap = parse_prometheus(server.metrics_snapshot())
    assert snap["samd_process_compiles_total"] == tracing.compiles()
    assert snap["samd_request_queue_wait_seconds_count"] == 3
    for name in ("prefill_tokens", "prefill_positions", "decode_rows",
                 "decode_slots"):
        assert snap[f"samd_engine_{name}_total"] == eng.stats[name]
    waits = [r.t_prefill - r.t_submit for r in server.finished]
    assert snap["samd_request_queue_wait_seconds_sum"] == pytest.approx(
        sum(waits), rel=1e-6)
