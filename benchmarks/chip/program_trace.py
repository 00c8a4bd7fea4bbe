"""The serving program's own spans in a profiler trace, and the device's
idle time by the span the host was in.

    python3 benchmarks/chip/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file.json>]

``trace.reduce`` keeps the benchmark's ``bench.*`` spans. The serving
package marks its own phases with ``serve.*`` spans
(``repro.serving.tracing``) on the same host plane and clock:
``program_spans`` reads them; ``idle_by_span`` files each stretch of
device-idle time under the innermost ``serve.*`` span open over it
(``outside`` where none is), where ``trace.idle_gaps`` files a whole gap
under the span open at its middle; ``loop_host_ms`` is the device-idle
time during which no ``serve.tick`` is open, per tick.

Run as a script, it serves one window of a cell traced as ``run.py
--trace 1`` traces it (the same ``harness.instrument`` spans) and prints
one JSON line: the ticks and ``tick_host_ms`` of the traced window, the
idle time by program span, ``loop_host_ms``, the mean host time of each
``serve.*`` span, the cell's per-layer metrics, the stats the top device
ops carry, and the device time of each named region of the step
programs (``embed``, ``attn``, ``kv_write``, ``mlp``, ``lm_head``,
``sample``) where the op events carry the region. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
PROGRAM_PREFIX = "serve."
TICK = "serve.tick"
OUTSIDE = "outside"
REGIONS = ("embed", "attn", "kv_write", "mlp", "lm_head", "sample")


def _profile(trace_dir: str):
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    return jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))


def program_spans(trace_dir: str, window: tuple) -> list:
    """The ``serve.*`` host events of the newest trace under
    ``trace_dir`` that overlap ``window``: (name, start, dur, stats), by
    start, in trace nanoseconds."""
    lo, hi = window
    out = []
    for plane in _profile(trace_dir).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if (e.name.startswith(PROGRAM_PREFIX)
                        and e.start_ns < hi
                        and e.start_ns + e.duration_ns > lo):
                    out.append((e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _pieces(spans: list, lo: float, hi: float) -> list:
    """(start, end, label) pieces that tile [lo, hi]: each labelled with
    the innermost span open over it (the latest started; its name less
    the prefix), ``outside`` where none is."""
    events = sorted([(min(max(s, lo), hi), 1, i)
                     for i, (_, s, _, _) in enumerate(spans)]
                    + [(min(max(s + d, lo), hi), 0, i)
                       for i, (_, s, d, _) in enumerate(spans)])

    def label(open_):
        if not open_:
            return OUTSIDE
        name = spans[max(open_, key=lambda i: (spans[i][1],
                                               -spans[i][2]))][0]
        return name[len(PROGRAM_PREFIX):]

    pieces, open_, t = [], set(), lo
    for at, starts, i in events:
        if at > t:
            pieces.append((t, at, label(open_)))
            t = at
        if starts:
            open_.add(i)
        else:
            open_.discard(i)
    if hi > t:
        pieces.append((t, hi, label(open_)))
    return pieces


def idle_gaps_ns(tr) -> list:
    """(start, end) of each stretch of the window in which device 0 ran
    no operation."""
    gaps, prev = [], tr.window[0]
    for s, e in tr.busy_intervals(0) + [[tr.window[1], tr.window[1]]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    return gaps


def idle_by_span(tr, spans: list) -> list:
    """Device-idle seconds of the traced window ``tr`` by the innermost
    of ``spans`` open over each part of each idle gap:
    [[span name less ``serve.``, or ``outside``, seconds]], largest
    first. The parts add up to the window's idle time."""
    pieces = _pieces(spans, *tr.window)
    tot: dict = {}
    j = 0
    for gs, ge in idle_gaps_ns(tr):
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ps, pe, lab = pieces[k]
            tot[lab] = tot.get(lab, 0) + min(pe, ge) - max(ps, gs)
            k += 1
    return [[k, v * 1e-9] for k, v in sorted(tot.items(),
                                             key=lambda kv: -kv[1])]


def loop_host_ms(tr, spans: list):
    """Device-idle milliseconds of the window during which no
    ``serve.tick`` is open, over the number of ticks."""
    ticks = [s for s in spans if s[0] == TICK]
    if not ticks:
        return None
    return dict(idle_by_span(tr, ticks)).get(OUTSIDE, 0.0) * 1e3 / len(ticks)


def span_ms(spans: list) -> dict:
    """Per ``serve.*`` name: [count, mean host milliseconds]."""
    out: dict = {}
    for name, _, d, _ in spans:
        out.setdefault(name, []).append(d * 1e-6)
    return {k: [len(v), sum(v) / len(v)] for k, v in out.items()}


def op_regions(trace_dir: str, window: tuple, top: list) -> dict:
    """What the device op events say of the program's named regions:
    the stats of one event of each of the ``top`` op labels, and the
    device seconds of the window by the innermost region named in an
    event's string stats (``none`` where an event names none)."""
    from benchmarks.chip.trace import op_label

    lo, hi = window
    want = set(top)
    samples: dict = {}
    seconds: dict = {}
    for plane in _profile(trace_dir).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                if e.start_ns < lo or e.start_ns + e.duration_ns > hi:
                    continue
                stats = {k: v for k, v in e.stats}
                lab = op_label(e.name)
                if lab in want and lab not in samples:
                    samples[lab] = {k: str(v)[:300] for k, v in stats.items()}
                region = "none"
                for v in stats.values():
                    if isinstance(v, str) and "/" in v:
                        parts = [p for p in v.split("/") if p in REGIONS]
                        if parts:
                            region = parts[-1]
                            break
                seconds[region] = seconds.get(region, 0) + e.duration_ns
    return {"top_op_stats": samples,
            "device_s_by_region": {k: v * 1e-9 for k, v in seconds.items()}}


def measure(root, workload: str, seed: int, seconds: float,
            peaks=None) -> dict:
    """Serve one window of ``workload`` under ``root``, traced as ``run.py
    --trace 1`` traces it, and reduce the trace (the module docstring
    lists what). Device metrics are read only where ``peaks`` is given:
    on a chip."""
    from benchmarks.chip import harness, readings, trace

    t0 = time.perf_counter()
    bench = harness.Bench(root)
    cell = harness.Cell(bench, workload, seed)
    setup_s = time.perf_counter() - t0
    trace_dir = str(Path(root) / ".chipbench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    win = cell.serve(seconds, trace_dir, traced=True)
    tr = trace.reduce(trace_dir)
    spans = program_spans(trace_dir, tr.window)
    top = trace.top_ops(tr)
    rec = {"seconds": seconds, "window": (win.t_open, win.t_close),
           "setup_s": setup_s, "sent": win.sent,
           "stats_open": win.stats_open, "stats_close": win.stats_close,
           "model": cell.ref.dims(cell.conf),
           "quant": cell.conf["serving"].get("quant"), "peaks": peaks,
           "trace": tr}
    metrics = {e["name"]: mod.value(rec)
               for e, mod in bench.metrics(workload, traced=True)
               if peaks is not None or e["source"] != "device_trace"}
    out = {
        "workload": workload, "seed": seed, "setup_s": setup_s,
        "window_s": tr.window_s, "busy_s": tr.busy_s(),
        "ticks": len(tr.spans_named("bench.tick")),
        "tick_host_ms": readings.host_self_ms(tr, "tick"),
        "serve_ticks": sum(1 for s in spans if s[0] == TICK),
        "loop_host_ms": loop_host_ms(tr, spans),
        "idle_by_span": idle_by_span(tr, spans),
        "idle_gaps": trace.idle_gaps(tr),
        "span_ms": span_ms(spans),
        "metrics": metrics,
        "device_ops": top,
        **op_regions(trace_dir, tr.window, [k for k, _ in top[:3]]),
    }
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None,
                    help="also write the JSON line to this file")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from benchmarks.chip import harness

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: needs a TPU; JAX has {dev.platform}", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    compile_cache.enable()
    harness.configure_cache(jax)
    out = measure(ROOT, args.workload, args.seed, args.seconds,
                  harness.Bench(ROOT).peaks(dev.device_kind))
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
