"""The serving program's own spans and counters, and their readers.

A synthetic window fixes ``idle_by_span``, ``loop_host_ms`` and the new
per-layer readers by hand; a toy engine behind the front door, recorded
here on the CPU with the benchmark's own spans on, checks that every
``serve.*`` span is emitted where it belongs and carries the same
numbers as the benchmark's ``bench.*`` spans around the same calls.
"""
import asyncio
import types

import numpy as np
import pytest

from benchmarks.chip import harness, program_trace, trace
from benchmarks.chip.metrics import (admit_wait_p95_ms, decode_occupancy,
                                     prefill_useful_share)

MS = 1_000_000  # nanoseconds


def span(name, start_ms, end_ms, **stats):
    return (name, start_ms * MS, (end_ms - start_ms) * MS, stats)


def synthetic():
    """A 100 ms window, device busy 22-48 and 72-88 ms; a decode tick
    (5-60 ms) and a prefill tick (65-95 ms), the front door's dispatch
    before the first and publish after it."""
    ops = [("%fusion.1 = bf16[8]{0} fusion(%p)", 22 * MS, 26 * MS, 0),
           ("%fusion.2 = bf16[8]{0} fusion(%p)", 72 * MS, 16 * MS, 0)]
    tr = trace.Trace(window=(0, 100 * MS), ops=ops, modules=[],
                     spans=[("bench.window", 0, 100 * MS, {})], devices=1)
    spans = [span("serve.dispatch", 2, 4, dispatched=1),
             span("serve.tick", 5, 60, clock=1.0, active=2),
             span("serve.admit", 6, 12),
             span("serve.grant", 13, 15),
             span("serve.decode", 16, 50, rows=2, keys=9, width=1, spec=0),
             span("serve.advance", 51, 55),
             span("serve.publish", 61, 63),
             span("serve.tick", 65, 95, clock=1.06, active=2),
             span("serve.admit", 66, 90),
             span("serve.prefill", 68, 89, rows=1, tokens=5, keys=15,
                  bucket=8, width=1)]
    return tr, spans


def test_idle_by_span_by_hand():
    tr, spans = synthetic()
    got = dict(program_trace.idle_by_span(tr, spans))
    # idle 0-22, 48-72 and 88-100 ms, split at every span edge
    want = {"outside": 11, "tick": 15, "admit": 9, "decode": 8,
            "prefill": 5, "advance": 4, "dispatch": 2, "grant": 2,
            "publish": 2}
    assert set(got) == set(want)
    for k, ms in want.items():
        assert got[k] == pytest.approx(ms * 1e-3), k
    assert sum(got.values()) == pytest.approx(0.058)
    # the midpoint reading files each whole gap under one span
    assert dict(trace.idle_gaps(tr)) == {"none": pytest.approx(0.058)}


def test_loop_host_ms_by_hand():
    tr, spans = synthetic()
    # idle outside both ticks: 0-5, 60-65 and 95-100 ms, over 2 ticks
    assert program_trace.loop_host_ms(tr, spans) == pytest.approx(7.5)
    assert program_trace.loop_host_ms(tr, spans[:1]) is None
    assert program_trace.span_ms(spans)["serve.tick"] == [
        2, pytest.approx(42.5)]


def sent(due, t_prefill=None, stamped=True):
    req = types.SimpleNamespace()
    if stamped:
        req.t_prefill = t_prefill
    return types.SimpleNamespace(due=due, request=req)


def record(**kw):
    tr, _ = synthetic()
    rec = {"trace": tr, "window": (10.0, 20.0), "sent": [],
           "stats_open": {"prefill_tokens": 100, "prefill_positions": 1000,
                          "decode_rows": 10, "decode_slots": 16},
           "stats_close": {"prefill_tokens": 400,
                           "prefill_positions": 3000,
                           "decode_rows": 34, "decode_slots": 48}}
    rec.update(kw)
    return rec


def test_admit_wait_by_hand():
    rec = record(sent=[sent(9.0, 9.5),          # due before the window
                       sent(11.0, 11.2), sent(12.0, 12.5),
                       sent(13.0, None),        # not launched: 20 - 13
                       sent(14.0, 21.0)])       # launched after the close
    # waits 0.2, 0.5, 7, 6 s: linear 95th percentile 6.85 s
    assert admit_wait_p95_ms.value(rec) == pytest.approx(6850.0)


def test_counter_readers_by_hand():
    # 300 tokens written in 2000 positions computed
    assert prefill_useful_share.value(record()) == pytest.approx(15.0)
    # 24 active rows in 32 decode rows computed
    assert decode_occupancy.value(record()) == pytest.approx(75.0)


@pytest.mark.parametrize("mod", [admit_wait_p95_ms, prefill_useful_share,
                                 decode_occupancy])
def test_new_readers_read_nothing_without_a_trace(mod):
    rec = record(trace=None, sent=[sent(11.0, 11.2)])
    assert mod.value(rec) is None


def test_new_readers_read_nothing_without_admissions_or_counters():
    parent_stats = {"prefill_calls": 3, "decode_steps": 5}
    parent = record(stats_open=parent_stats, stats_close=parent_stats,
                    sent=[sent(11.0, stamped=False)])
    for mod in (admit_wait_p95_ms, prefill_useful_share, decode_occupancy):
        assert mod.value(parent) is None, mod.__name__
    assert admit_wait_p95_ms.value(record(sent=[sent(9.0, 9.5)])) is None
    idle = record(stats_close=record()["stats_open"])
    assert prefill_useful_share.value(idle) is None
    assert decode_occupancy.value(idle) is None


# ---------------------------------------------------------------------------
# recorded on the CPU: a toy engine behind the front door
# ---------------------------------------------------------------------------

ENGINE_SPANS = ("serve.admit", "serve.prefill", "serve.grant",
                "serve.decode", "serve.advance")
ALL_SPANS = ("serve.tick",) + ENGINE_SPANS + ("serve.dispatch",
                                              "serve.publish")


def _host_events(trace_dir):
    """(name, start, end, stats, thread line) of every serve.* and bench.*
    host event."""
    out = []
    for plane in program_trace._profile(trace_dir).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "bench.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats),
                                line.name))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    import jax

    from repro.configs import smoke_config
    from repro.serving import AsyncServer, ServingEngine

    cfg = smoke_config("qwen1.5-0.5b").scaled(
        n_layers=2, d_model=64, vocab=256, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=128)
    eng = ServingEngine(cfg, max_batch=2, max_len=64, page_size=8)
    server = AsyncServer(eng, policy="fifo", max_queue=16)
    harness.instrument(eng, server)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 256, size=16)
    prompts = [np.concatenate([shared, rng.integers(0, 256, size=3 + i)])
               for i in range(4)]

    async def go():
        await server.start()
        streams = [server.submit(p, max_tokens=5 + i)
                   for i, p in enumerate(prompts)]
        await asyncio.gather(*(s.collect() for s in streams))
        await server.stop()

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        asyncio.run(go())
    finally:
        jax.profiler.stop_trace()
    return eng, server, _host_events(trace_dir), trace_dir


def test_every_span_is_emitted_and_nests(served):
    eng, server, events, _ = served
    names = {e[0] for e in events}
    assert set(ALL_SPANS) <= names
    ticks = [e for e in events if e[0] == "serve.tick"]
    for e in events:
        if e[0] in ENGINE_SPANS:
            assert any(_inside(e, t) and e[4] == t[4] for t in ticks), e
    # the benchmark's tick wraps the engine's, one for one
    bench_ticks = [e for e in events if e[0] == "bench.tick"]
    assert len(bench_ticks) == len(ticks)
    assert all(any(_inside(t, b) for b in bench_ticks) for t in ticks)
    for name in ("serve.dispatch", "serve.publish"):
        for e in (e for e in events if e[0] == name):
            assert not any(_inside(e, t) for t in ticks), e
    assert sum(e[3]["dispatched"] for e in events
               if e[0] == "serve.dispatch") == 4
    assert len([e for e in events if e[0] == "serve.decode"]) == \
        eng.stats["decode_steps"]


def test_program_stats_equal_the_benchmarks(served):
    _, _, events, _ = served
    pairs = {"bench.prefill_call": "serve.prefill",
             "bench.decode_call": "serve.decode"}
    for bench_name, serve_name in pairs.items():
        calls = [e for e in events if e[0] == bench_name]
        assert calls, bench_name
        for call in calls:
            outer = [e for e in events
                     if e[0] == serve_name and _inside(call, e)]
            assert len(outer) == 1, call
            stats = dict(outer[0][3])
            if serve_name == "serve.decode":
                assert stats.pop("spec") == 0
            assert stats == call[3]


def test_clock_stat_places_t_prefill_in_its_prefill_span(served):
    _, server, events, _ = served
    ticks = [e for e in events if e[0] == "serve.tick"]
    prefills = [e for e in events if e[0] == "serve.prefill"]
    assert len(server.finished) == 4
    for req in server.finished:
        assert req.t_submit <= req.t_prefill <= req.t_admit
        hits = []
        for p in prefills:
            t = next(t for t in ticks if _inside(p, t))
            at = t[1] + (req.t_prefill - t[3]["clock"]) * 1e9
            hits.append(p[1] - MS <= at <= p[2] + MS)
        assert any(hits), req.rid


def test_program_spans_reads_the_window(served):
    _, _, events, trace_dir = served
    lo = min(e[1] for e in events)
    hi = max(e[2] for e in events)
    spans = program_trace.program_spans(trace_dir, (lo, hi + 1))
    assert len(spans) == sum(1 for e in events
                             if e[0].startswith("serve."))
    assert all(s[0].startswith("serve.") for s in spans)
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)


def test_measure_a_toy_cell(tmp_path):
    import chipbench_toy

    root = chipbench_toy.toy_root(tmp_path)
    out = program_trace.measure(root, "toy-bf16.toy_chat", 11, 2.0)
    # the CPU is no TPU plane: the device is idle the whole window, so
    # the program's spans split all of it
    assert out["busy_s"] == 0
    idle = dict(out["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(out["window_s"])
    assert {"tick", "decode", "outside"} <= set(idle)
    assert abs(out["serve_ticks"] - out["ticks"]) <= 2
    assert out["loop_host_ms"] > 0
    assert out["span_ms"]["serve.tick"][0] == out["serve_ticks"]
    assert out["device_ops"] == [] and out["top_op_stats"] == {}
    # the program's counters and stamps, read as on the chip
    assert {"admit_wait_p95_ms", "prefill_useful_share",
            "decode_occupancy"} <= set(out["metrics"])
    assert 0 < out["metrics"]["decode_occupancy"] <= 100
    assert 0 < out["metrics"]["prefill_useful_share"] <= 100
    assert not (root / ".chipbench_trace").exists()
