"""Reduce a JAX profiler trace to what the per-layer metrics read.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line
holds one event per operation (named ``%<op>.<n> = <shape> ...`` from
the HLO text, kernels by their Pallas name, e.g. ``%paged_decode_attention.3``) and
the ``XLA Modules`` line one event per program execution (named
``<jit name>(<fingerprint>)``). The benchmark's own host spans are the
``bench.*`` events of the host plane; their keyword arguments come back
as event stats. All share the trace's clock (nanoseconds).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")


def op_base(name: str) -> str:
    """``%copy.124 = bf16[...]...`` -> ``copy``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def op_label(name: str) -> str:
    """The op's base name and result type: ``copy bf16[2048,16,16,64]``."""
    if " = " not in name:
        return op_base(name)
    shape = name.split(" = ", 1)[1].split("{", 1)[0].split(" ", 1)[0]
    return f"{op_base(name)} {shape}"[:120]


def module_base(name: str) -> str:
    """``jit_paged_prefill_step(1845...)`` -> ``jit_paged_prefill_step``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Trace:
    """One traced window. Times are trace nanoseconds; every list is
    clipped to the window and sorted by start."""

    window: tuple                  # (start, end) of the bench.window span
    ops: list                      # (name, start, dur, device) per op
    modules: list                  # (name, start, dur) per program run
    spans: list                    # (name, start, dur, stats dict)
    devices: int                   # device planes found

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: int = 0) -> list:
        """Union of one device's op intervals, as merged [start, end]."""
        merged: list = []
        for _, s, d, dev in self.ops:
            if dev != device:
                continue
            e = s + d
            if merged and s <= merged[-1][1]:
                if e > merged[-1][1]:
                    merged[-1][1] = e
            else:
                merged.append([s, e])
        return merged

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        busy = sum(e - s for dev in range(self.devices)
                   for s, e in self.busy_intervals(dev))
        return busy * 1e-9 / max(self.devices, 1)

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]


def _clip(items, lo, hi):
    return [x for x in items if x[1] >= lo and x[1] + x[2] <= hi]


def reduce(trace_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    ops, modules, spans, devices = [], [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.duration_ns, devices)
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(module_base(e.name), e.start_ns,
                                 e.duration_ns) for e in line.events]
            devices += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns,
                                      dict(e.stats)))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    key = (lambda x: x[1])
    return Trace(window=(lo, hi), ops=sorted(_clip(ops, lo, hi), key=key),
                 modules=sorted(_clip(modules, lo, hi), key=key),
                 spans=sorted(_clip(spans, lo, hi), key=key),
                 devices=devices)


def top_ops(tr: Trace, n: int = 10) -> list:
    """The n op labels that took most device time: [[label, seconds]]."""
    tot: dict = {}
    for name, _, d, _ in tr.ops:
        lab = op_label(name)
        tot[lab] = tot.get(lab, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """Device idle time in the window, by the innermost benchmark span
    open at the middle of each gap ("none" where no span is open):
    [[what the host was doing, seconds]], the n largest."""
    gaps, prev = [], tr.window[0]
    for s, e in tr.busy_intervals(0) + [[tr.window[1], tr.window[1]]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [s for s in tr.spans if s[0] != WINDOW_SPAN]
    starts = [s[1] for s in spans]
    longest = max((s[2] for s in spans), default=0)
    tot: dict = {}
    for s, e in gaps:
        mid = (s + e) / 2
        inner = None
        # the innermost open span is the latest-started one holding mid
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            name, start, dur, _ = spans[i]
            if start + dur >= mid:
                inner = name
                break
            if start + longest < mid:
                break
        label = inner[len(SPAN_PREFIX):] if inner else "none"
        tot[label] = tot.get(label, 0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]
