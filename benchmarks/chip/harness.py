"""Run one cell of the chip benchmark once.

Everything is found by name from ``BENCHMARK.json`` at the checkout root:

* ``benchmarks/chip/configs/<config>.json``: the model (its published
  ``config.json`` keys), the serving sizes, the weights' distribution,
  the stated and control precisions, and the files of its ``adapter``
  (the program's model and weights) and ``reference``;
* ``benchmarks/chip/traffic/<traffic>.json``: the mix, whose ``loop``
  names ``benchmarks/chip/traffic/<loop>.py``, the generator that drives
  the window;
* ``benchmarks/chip/metrics/<metric>.py``: one ``value(rec)`` per metric,
  end-to-end or per-layer, returning a number or None (nothing to read);
* ``benchmarks/chip/checks/<workload>.json``: the limit of each number
  that decides ``correct``.

A run: build the engine from the configuration (weights made on the
device from the seed), warm every program shape the mix can reach, open
the window, drive ``AsyncServer.submit`` -> ``TokenStream`` from client
coroutines for ``seconds``, close it, read the peaks, free the program,
and compare a sample of the finished requests with the plain reference.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from benchmarks.chip import sampling

BENCH_DIR = Path("benchmarks") / "chip"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def info(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root):
        self.root = Path(root).resolve()
        self.dir = self.root / BENCH_DIR
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, kind: str, name: str) -> dict:
        for e in self.spec[kind]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {kind} entry named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        conf = json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())
        conf["name"] = name
        return conf

    def mix(self, traffic: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{traffic}.json")
                          .read_text())

    def check(self, workload: str) -> dict:
        return json.loads((self.dir / "checks" / f"{workload}.json")
                          .read_text())

    def module(self, rel: str):
        """Load ``benchmarks/chip/<rel>`` by path."""
        path = self.dir / rel
        name = "chipbench_" + rel.replace("/", "_").removesuffix(".py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, workload: str, traced: bool) -> list:
        """(entry, module) of each metric this cell reports in a run:
        the end-to-end ones untraced, the per-layer ones traced."""
        kind = "per_layer" if traced else "end_to_end"
        out = []
        for e in self.spec[kind]:
            if workload in e.get("workloads", [workload]):
                out.append((e, self.module(f"metrics/{e['name']}.py")))
        return out

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           "benchmarks/chip/peaks.json")
        return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the engine and its shapes
# ---------------------------------------------------------------------------


def _pow2(n: int) -> int:
    p = 1
    while p < max(1, n):
        p *= 2
    return p


def warm_shapes(bounds: dict, serving: dict) -> tuple:
    """Every (prefill bucket, page-table width) pair and every decode
    table width that a mix with these length ``bounds`` can make the
    engine use. Prefill rows hold the unshared suffix of each prompt,
    right-padded to a power of two (at least 8); both tables are
    power-of-two widths of pages (``ServingEngine._pow2_width``)."""
    ps, max_len = serving["page_size"], serving["max_len"]
    cap = -(-max_len // ps)

    def width(tokens):
        return min(_pow2(-(-tokens // ps)), cap)

    lo_total, hi_total = bounds["prompt_min"], bounds["prompt_max"]
    min_suffix = 1 if bounds["shared"] else lo_total
    buckets, lb = [], 8
    while lb < min_suffix:
        lb *= 2
    while True:
        buckets.append(min(lb, max_len))
        if lb >= hi_total or lb >= max_len:
            break
        lb *= 2
    prefill = []
    for lb in buckets:
        longest = (lb // 2 + 1) if lb > 8 else 1   # the row that set lb
        lo = width(max(longest, lo_total if bounds["shared"] else longest))
        hi = width(hi_total if bounds["shared"] else min(lb, hi_total))
        w = lo
        while w <= hi:
            prefill.append((lb, w))
            w *= 2
    decode, w = [], width(lo_total)
    while w <= width(bounds["total_max"]):
        decode.append(w)
        w *= 2
    return prefill, decode


def build_engine(bench: Bench, conf: dict, seed: int):
    from repro.serving import ServingEngine

    adapter = bench.module(conf["adapter"])
    s = conf["serving"]
    eng = ServingEngine(
        adapter.arch(conf), adapter.program_params(conf, seed),
        quant=adapter.quant(conf), max_batch=s["max_batch"],
        max_len=s["max_len"], page_size=s["page_size"],
        num_pages=s["num_pages"], seed=seed & 0x7FFFFFFF)
    return eng


def warm(eng, prefill: list, decode: list) -> None:
    """Run each step program once at each shape, with every row inactive
    (pages -1: nothing is written), so that the window compiles nothing.
    Arguments are built exactly as the engine builds them."""
    import jax.numpy as jnp

    nb = eng.max_batch
    temp = jnp.float32(eng.temperature)
    for lb, w in prefill:
        tok0, eng.cache = eng._prefill_step(
            eng.params, jnp.asarray(np.zeros((nb, lb), np.int32)),
            jnp.asarray(np.zeros(nb, np.int32)),
            jnp.asarray(np.zeros(nb, np.int32)),
            jnp.asarray(np.full((nb, w), -1, np.int32)),
            jnp.asarray(np.zeros(nb, bool)), eng.cache, eng._key, temp)
        np.asarray(tok0)
    for w in decode:
        ids, eng.cache = eng._ragged_step(
            eng.params, jnp.asarray(np.zeros((nb, 1), np.int32)), eng.cache,
            jnp.asarray(np.zeros(nb, np.int32)),
            jnp.asarray(np.zeros(nb, bool)),
            jnp.asarray(np.full((nb, w), -1, np.int32)), eng._key, temp)
        np.asarray(ids)


# ---------------------------------------------------------------------------
# spans on the program's entry points (traced runs only)
# ---------------------------------------------------------------------------


def instrument(eng, server) -> None:
    """Wrap the engine's phases and compiled step callables, by attribute
    on the instances, in ``bench.*`` trace spans. The step calls wait
    for their result inside the span (the engine waits for it right
    after anyway), so the span holds the program's device time; their
    keyword arguments carry the rows and keys each call serves."""
    import jax
    from jax.profiler import TraceAnnotation

    def span(obj, attr, name):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with TraceAnnotation(name):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)

    for attr, name in (("step", "tick"), ("_admit", "admit"),
                       ("_grant_pages", "grant_pages")):
        span(eng, attr, "bench." + name)
    span(server, "_publish", "bench.publish")

    pending = {}
    batch = eng._prefill_batch

    def prefill_batch(slots, reqs, effs, starts):
        pending["rows"] = [(int(s), len(e) - int(s))
                           for e, s in zip(effs, starts)]
        with TraceAnnotation("bench.prefill_batch"):
            return batch(slots, reqs, effs, starts)

    prefill_step = eng._prefill_step

    def prefill(*a):
        rows = pending.pop("rows", [])
        n_tok = sum(n for _, n in rows)
        keys = sum(n * s + n * (n + 1) // 2 for s, n in rows)
        with TraceAnnotation("bench.prefill_call", rows=len(rows),
                             tokens=n_tok, keys=keys,
                             bucket=int(a[1].shape[1]),
                             width=int(a[4].shape[1])):
            out = prefill_step(*a)
            jax.block_until_ready(out[0])
        return out

    decode_step = eng._ragged_step

    def decode(*a):
        act = eng.active
        keys = int((eng.slot_pos[act].astype(np.int64) + 1).sum())
        with TraceAnnotation("bench.decode_call", rows=int(act.sum()),
                             keys=keys, width=int(a[5].shape[1])):
            out = decode_step(*a)
            jax.block_until_ready(out[0])
        return out

    eng._prefill_batch = prefill_batch
    eng._prefill_step = prefill
    eng._ragged_step = decode


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Sent:
    """One request as its client saw it (host perf_counter seconds)."""

    due: float
    sent: float
    n_prompt: int
    max_tokens: int
    prompt: np.ndarray
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    refused: str | None = None
    request: object = None          # the engine's Request


class Window:
    """What a traffic generator sees: the clock, the mix, and ``send``.

    Traffic starts at ``t_start``; the measured window opens ``preroll``
    seconds later (the mix's ``preroll_s``), on requests already in
    flight, and closes ``seconds`` after that."""

    def __init__(self, server, mix, space, seed, seconds):
        self.server, self.mix, self.space = server, mix, space
        self.seed, self.seconds = seed, seconds
        self.preroll = float(mix.get("preroll_s", 0.0))
        self.sent: list = []
        self.tasks: list = []
        self.t_start = self.t_open = self.t_close = 0.0
        self.stats_open = self.stats_close = None

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    async def sleep_until(self, t: float) -> None:
        delay = t - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)

    def send(self, prompt, max_tokens: int, due: float):
        """Submit now; returns the task that reads the request's tokens
        (it ends when the request retires)."""
        from repro.serving import RejectedRequest

        rec = Sent(due=due, sent=time.perf_counter(), n_prompt=len(prompt),
                   max_tokens=int(max_tokens), prompt=prompt)
        self.sent.append(rec)
        try:
            stream = self.server.submit(prompt, max_tokens=int(max_tokens))
        except RejectedRequest as rej:
            rec.refused = rej.code
            rec.request = rej.request
            task = asyncio.get_running_loop().create_future()
            task.set_result(None)
            return task
        rec.request = stream.request

        async def read():
            async for tok in stream:
                rec.times.append(time.perf_counter())
                rec.tokens.append(int(tok))

        task = asyncio.create_task(read())
        self.tasks.append(task)
        return task


async def _window(server, win: Window, loop_mod, trace_dir, trace_s):
    import jax

    await server.start()
    win.t_start = time.perf_counter()
    win.t_open = win.t_start + win.preroll
    win.t_close = win.t_open + win.seconds
    driver = asyncio.create_task(loop_mod.drive(win))
    if trace_dir is not None:
        # the profiler starts off the event loop, and stops only once the
        # window has closed: collecting a trace holds the interpreter for
        # seconds, which would stall the clients inside the window
        await win.sleep_until(win.t_open + (win.seconds - trace_s) / 2)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        await asyncio.to_thread(jax.profiler.start_trace, trace_dir,
                                profiler_options=opts)
        with jax.profiler.TraceAnnotation(
                "bench.window", seconds=trace_s):
            await win.sleep_until(time.perf_counter() + trace_s)
    await win.sleep_until(win.t_close)
    driver.cancel()
    await server.stop(drain=False)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    for t in win.tasks:
        t.cancel()
    done = await asyncio.gather(driver, *win.tasks, return_exceptions=True)
    for r in done:
        if isinstance(r, Exception):
            raise r   # a generator or client that failed fails the run


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class Cell:
    """One workload's engine, built from the seed and warmed: what the
    window drives, and what the benchmark's tools reuse across seeds."""

    def __init__(self, bench: Bench, workload: str, seed: int):
        self.bench, self.workload, self.seed = bench, workload, seed
        entry = bench.workload(workload)
        self.conf = bench.config(entry["config"])
        self.mix = bench.mix(entry["traffic"])
        self.check = bench.check(workload)
        self.loop = bench.module(f"traffic/{self.mix['loop']}.py")
        self.ref = bench.module(self.conf["reference"])
        self.space = sampling.Space(self.mix, self.conf["vocab_size"])
        self.eng = build_engine(bench, self.conf, seed)
        self.prefill, self.decode = warm_shapes(self.space.bounds(),
                                                self.conf["serving"])
        warm(self.eng, self.prefill, self.decode)

    def reseed(self, seed: int) -> None:
        """New weights from ``seed`` in the same compiled engine."""
        from repro.models import build_template, quantize_params

        eng, adapter = self.eng, self.bench.module(self.conf["adapter"])
        params = adapter.program_params(self.conf, seed)
        if eng.quant.enabled:
            params = quantize_params(
                params, build_template(eng.cfg, stacked=False), eng.quant)
        eng.params = params
        eng.reset()
        self.seed = seed

    def serve(self, seconds: float, trace_dir=None, mix=None,
              traced: bool = False) -> "Window":
        """One measured window on the engine; returns what clients saw."""
        from repro.serving import AsyncServer

        s = self.conf["serving"]
        server = AsyncServer(self.eng, policy=s["policy"],
                             max_queue=s["max_queue"],
                             clock=time.perf_counter)
        if traced:
            instrument(self.eng, server)
        mix = mix or self.mix
        win = Window(server, mix, self.space, self.seed, seconds)
        win.stats_open = dict(self.eng.stats)
        trace_s = min(float(mix.get("trace_seconds", seconds)), seconds)
        asyncio.run(_window(server, win, self.loop, trace_dir, trace_s))
        win.stats_close = dict(self.eng.stats)
        return win


def sample(sent: list, k: int, seed: int) -> list:
    """The request with the most served tokens and k - 1 others drawn
    from the seed, among requests that served tokens without error
    (finished, or still in flight at the close)."""
    ok = [s for s in sent if len(s.tokens) >= 2 and s.request is not None
          and s.request.error is None and not s.request.truncated]
    if not ok:
        return []
    ok.sort(key=lambda s: (-len(s.tokens), s.due))
    rest = ok[1:]
    pick = np.random.default_rng([seed, 7]).permutation(len(rest))
    return [ok[0]] + [rest[i] for i in pick[:k - 1]]


def numbers(check: dict, gaps: list) -> dict:
    """The numbers that decide ``correct``, each with its limit: the mean
    gap by which a served token's logit lies below the reference's best
    at its position, and how many tokens were compared."""
    flat = np.concatenate(gaps) if gaps else np.zeros(0)
    values = {"served_gap_mean": float(flat.mean()) if flat.size else None,
              "tokens_compared": int(flat.size)}
    return {k: {"value": v, "limit": check[k]["limit"]}
            for k, v in values.items()}


def compare(cell: Cell, seed: int, picked: list) -> dict:
    """Teacher-forced gaps of the sampled requests' served tokens under
    the reference at the stated precision, as ``numbers``."""
    conf, ref = cell.conf, cell.ref
    w = ref.init_weights(conf, seed)
    gaps = [ref.served_gaps(conf, w, s.prompt, s.tokens,
                            conf["precision"]["stated"])[0]
            for s in picked]
    if gaps:
        info(f"widest gap (not compared): {max(g.max() for g in gaps)}")
    return numbers(cell.check, gaps)


def passes(numbers: dict) -> bool:
    """Every gap within its limit, and at least as many tokens compared
    as the limit asks."""
    n = numbers["tokens_compared"]
    if n["value"] < n["limit"]:
        return False
    return all(v["value"] is not None and np.isfinite(v["value"])
               and v["value"] <= v["limit"]
               for k, v in numbers.items() if k != "tokens_compared")


def run(root, workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, fault=None) -> dict:
    """One run of one cell; returns the result object (the last line of
    a run's standard output). ``fault(eng)`` may break the program
    before the window (the harness's own tests use it)."""
    import jax

    bench = Bench(root)
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    peaks = bench.peaks(dev.device_kind) if on_chip else None
    compile_times: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **_: ev in COMPILE_EVENTS
        and compile_times.append(time.perf_counter()))

    cell = Cell(bench, workload, seed)
    if fault is not None:
        fault(cell.eng)
    trace_dir = None
    if traced:
        trace_dir = str(Path(root) / ".chipbench_trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.perf_counter() - t_start
    win = cell.serve(seconds, trace_dir, traced=traced)
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    kv_bytes = cell.eng.kv_cache_bytes()
    rec = {
        "seconds": float(seconds), "window": (win.t_open, win.t_close),
        "setup_s": setup_s, "sent": win.sent, "stats_open": win.stats_open,
        "stats_close": win.stats_close, "model": cell.ref.dims(cell.conf),
        "quant": cell.conf["serving"].get("quant"), "peaks": peaks,
        "trace": None,
    }
    lateness = [r.sent - r.due for r in win.sent]
    in_window = sum(1 for t in compile_times
                    if win.t_open <= t <= win.t_close)
    info(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    info(f"setup_s {setup_s:.3f}; warmed {len(cell.prefill)} prefill and "
         f"{len(cell.decode)} decode shapes; traffic for {win.preroll} s "
         "before the window opened")
    info(f"compiles inside the window: {in_window}")
    if lateness:
        info("generator lateness p95 "
             f"{np.percentile(lateness, 95) * 1e3:.3f} ms over "
             f"{len(lateness)} sends")
    info(f"peak_bytes_in_use {peak}; KV pool logical bytes {kv_bytes}")
    info("engine stats over the window: " + json.dumps(
        {k: win.stats_close[k] - win.stats_open[k]
         for k in win.stats_close}))
    if traced and on_chip:
        from benchmarks.chip import trace as trace_mod

        rec["trace"] = trace_mod.reduce(trace_dir)
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)

    attempted = sum(1 for r in win.sent if r.due < win.t_close)
    failed = sum(1 for r in win.sent
                 if r.refused or (r.request is not None and (
                     r.request.error or r.request.truncated)))
    picked = sample(win.sent, int(cell.mix["check_requests"]), seed)
    # free the program before the reference runs on the same device
    cell.eng = None
    del win
    gc.collect()
    numbers = compare(cell, seed, picked)
    correct = passes(numbers)

    metrics = {}
    for entry, mod in bench.metrics(workload, traced):
        if entry["source"] == "device_trace" and not on_chip:
            continue   # a device metric never comes from another platform
        v = mod.value(rec)
        if v is not None:
            metrics[entry["name"]] = {"value": float(v),
                                      "unit": entry["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if rec["trace"] is not None:
        from benchmarks.chip import trace as trace_mod

        tr = rec["trace"]
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
    for name, n in numbers.items():
        info(f"check {name}: {n['value']} (limit {n['limit']})")
    result["check"] = numbers
    return result


def configure_cache(jax) -> None:
    """Cache every program, however fast it compiled, and never evict:
    eviction reads a timestamp file beside each entry, and a cache
    directory restored without them refuses every write."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
