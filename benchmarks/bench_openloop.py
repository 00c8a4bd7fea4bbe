"""Open-loop Poisson-arrival serving: latency under offered load.

Closed-loop tokens/s (bench_serving) measures how fast the engine can
drain a queue it controls. Real traffic is OPEN-LOOP: arrivals come at
whatever rate millions of independent users generate, regardless of how
backed up the server is — the regime where queueing delay, admission
policy and backpressure dominate, and where a scheduler win shows up in
p99 latency long before it shows up in tokens/s.

This benchmark drives the async front door (``serving/server.py``)
with Poisson arrivals at fixed fractions of MEASURED capacity and
reports per-request latency percentiles plus reject accounting:

  * ``openloop/load0.5x_slo``  — half capacity, SLO policy
  * ``openloop/load0.9x_slo``  — near saturation, SLO policy
  * ``openloop/load2.5x_slo``  — sustained overload, SLO policy:
                                 earliest-deadline-first scheduling +
                                 deadline-aware ADMISSION (hopeless
                                 requests are refused at submit, so the
                                 admitted ones keep their SLO)
  * ``openloop/load2.5x_fifo`` — same overload, FIFO order and NO
                                 admission control (only the queue
                                 bound): the baseline that shows what
                                 unbounded queueing delay does to TTFT

Method: capacity is measured first as a closed-loop burst on the warmed
engine (``capacity_rps`` / ``capacity_tokens_per_s``); the SLO is then
set relative to capacity (``SLO_TOKEN_BUDGET / capacity_rps`` seconds),
so rows are comparable across hosts of different speeds. Each row reruns
the arrival process ``--repeats`` times on the same warm engine
(fresh server, ``engine.reset()`` between runs) and keeps the run with
the BEST p99 TPOT (the noise-floor statistic the perf gate diffs; the
per-run values stay in ``p99_tpot_ms_runs``).

Row naming for the perf gate (``benchmarks/perf_gate.py``): the gate
diffs ``p99_tpot_ms`` LOWER-IS-BETTER and must never cross-compare rows
whose ``reject_rate`` differs — rejecting more requests trivially buys
lower latency for the survivors, so such a pair is a policy change, not
a regression (the same reasoning as the rename rule). CI passes
``--guard-key reject_rate`` for exactly this.

Every run asserts conservation (completed + rejected == offered — a
request that vanished is the silent-drop bug this PR fixed) and that
the server's Prometheus snapshot stays machine-parseable.
``--check-slo`` additionally asserts the acceptance criterion: at 2.5x
offered load the SLO policy holds p99 TPOT at or below FIFO's while
rejecting at admission instead of queueing.

Run:  PYTHONPATH=src python -m benchmarks.bench_openloop [--repeats 3]
          [--n-requests 80] [--check-slo] [--out-dir .]
"""
from __future__ import annotations

import argparse
import asyncio
import time

import numpy as np

from benchmarks.bench_serving import _cfg, _requests, _serve_burst, _warm
from benchmarks.jsonio import write_bench_json

# the SLO, in units of 1/capacity_rps (i.e. mean request service times
# at full throughput): ~3.4x a request's fair-share latency — loose
# enough that an unloaded server always meets it, tight enough that
# unbounded queueing at 2.5x load blows straight through it
SLO_TOKEN_BUDGET = 30.0

# offered-load fractions x admission/scheduling variant (policy, and
# whether deadline-aware admission is on — FIFO measures pure queueing)
ROWS = [
    (0.5, "slo"),
    (0.9, "slo"),
    (2.5, "slo"),
    (2.5, "fifo"),
]

MAX_QUEUE = 64


def measure_capacity(eng, cfg, n_requests: int, seed: int):
    """Closed-loop burst on the warmed engine: the drain rate open-loop
    utilization is defined against. Returns (rps, tokens_per_s)."""
    reqs = _requests(cfg.vocab, n_requests, seed)
    t0 = time.perf_counter()
    tokens = _serve_burst(eng, reqs)
    dt = time.perf_counter() - t0
    assert len(eng.finished) == len(reqs)
    eng.reset()
    return len(reqs) / dt, tokens / dt


async def _drive_open_loop(server, reqs, arrivals_s):
    """Submit each request at its Poisson arrival time; collect every
    stream. Returns (completed_requests, rejected_requests)."""
    from repro.serving import RejectedRequest

    completed, rejected = [], []
    t0 = server.clock()

    async def one(req, at):
        delay = at - (server.clock() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        try:
            stream = server.submit(req.prompt, req.max_tokens,
                                   eos_id=req.eos_id, rid=req.rid)
        except RejectedRequest as rej:
            rejected.append(rej)
            return
        await stream.collect()
        completed.append(stream.request)

    await server.start()
    await asyncio.gather(
        *[one(r, at) for r, at in zip(reqs, arrivals_s)]
    )
    await server.stop()
    return completed, rejected


def run_row(eng, cfg, *, load: float, policy: str, capacity_rps: float,
            capacity_tps: float, n_requests: int, repeats: int,
            seed: int) -> dict:
    """One openloop/* row: best-of-``repeats`` open-loop runs (fresh
    server + engine.reset() each; best = lowest p99 TPOT)."""
    from repro.serving import AsyncServer
    from repro.serving.metrics import parse_prometheus, summarize

    slo_s = SLO_TOKEN_BUDGET / capacity_rps
    offered_rps = load * capacity_rps
    runs = []
    for rep in range(max(1, repeats)):
        eng.reset()
        server = AsyncServer(
            eng,
            policy=policy,
            max_queue=MAX_QUEUE,
            # FIFO is the no-admission-control baseline: requests queue
            # (up to the bound) no matter how hopeless their deadline
            default_slo_s=slo_s if policy == "slo" else None,
            capacity_tokens_per_s=capacity_tps,
        )
        rng = np.random.default_rng(seed + 1000 * rep)
        reqs = _requests(cfg.vocab, n_requests, seed + 1000 * rep)
        arrivals = np.cumsum(
            rng.exponential(1.0 / offered_rps, size=n_requests)
        )
        t0 = time.perf_counter()
        completed, rejected = asyncio.run(
            _drive_open_loop(server, reqs, arrivals)
        )
        dt = time.perf_counter() - t0
        # conservation: every offered request is accounted for — the
        # silent-drop regression guard, asserted on every single run
        assert len(completed) + len(rejected) == n_requests, (
            len(completed), len(rejected), n_requests,
        )
        assert server.counters["completed"] == len(completed)
        # the observability surface must stay machine-readable
        snapshot = parse_prometheus(server.metrics_snapshot())
        assert snapshot["samd_server_completed_total"] == len(completed)
        summ = summarize(completed, slo_s=slo_s)
        runs.append({
            "completed": len(completed),
            "rejected": len(rejected),
            "reject_rate": len(rejected) / n_requests,
            "seconds": dt,
            "goodput_tokens_per_s":
                sum(len(r.generated) for r in completed) / dt,
            "deadline_misses": summ["deadline_misses"],
            "rejected_by_code": {
                code: sum(1 for r in rejected if r.code == code)
                for code in ("queue_full", "infeasible", "slo")
            },
            "server": dict(server.counters),
            **{k: summ[k] for k in (
                "p50_ttft_ms", "p99_ttft_ms",
                "p50_tpot_ms", "p99_tpot_ms",
            )},
        })
    best = min(
        runs,
        key=lambda r: (
            r["p99_tpot_ms"] if r["p99_tpot_ms"] is not None
            else float("inf")
        ),
    )
    server_counts = best.pop("server")
    rej_codes = best.pop("rejected_by_code")
    return {
        "name": f"openloop/load{load}x_{policy}",
        "offered_load": load,
        "offered_rps": offered_rps,
        "capacity_rps": capacity_rps,
        "capacity_tokens_per_s": capacity_tps,
        "slo_s": slo_s,
        "repeats": len(runs),
        "p99_tpot_ms_runs": [r["p99_tpot_ms"] for r in runs],
        "n_requests": n_requests,
        **best,
        **{f"server_{k}": v for k, v in server_counts.items()},
        **{f"rejected_{k}": v for k, v in rej_codes.items()},
    }


def run(n_requests: int = 80, repeats: int = 3, seed: int = 0,
        check_slo: bool = False) -> list[dict]:
    from repro.serving import ServingEngine

    cfg = _cfg()
    eng = ServingEngine(cfg, max_batch=4, max_len=96, kv_mode="paged")
    _warm(eng, cfg)
    # untimed full-workload pass (the PR 6 warmup rule): first-touch
    # costs must not land in run 0 of the capacity measurement
    _serve_burst(eng, _requests(cfg.vocab, n_requests, seed))
    eng.reset()
    capacity_rps, capacity_tps = measure_capacity(
        eng, cfg, n_requests, seed
    )
    rows = []
    for load, policy in ROWS:
        rows.append(run_row(
            eng, cfg, load=load, policy=policy,
            capacity_rps=capacity_rps, capacity_tps=capacity_tps,
            n_requests=n_requests, repeats=repeats, seed=seed,
        ))
    if check_slo:
        by_name = {r["name"]: r for r in rows}
        slo = by_name["openloop/load2.5x_slo"]
        fifo = by_name["openloop/load2.5x_fifo"]
        assert slo["p99_tpot_ms"] <= fifo["p99_tpot_ms"], (
            "SLO policy must hold p99 TPOT at or below FIFO's under "
            f"2.5x overload: {slo['p99_tpot_ms']:.2f}ms vs "
            f"{fifo['p99_tpot_ms']:.2f}ms"
        )
        assert slo["rejected_slo"] > 0, (
            "under 2.5x overload the SLO policy must shed load AT "
            "ADMISSION (deadline-aware rejects), not by queueing"
        )
        assert slo["p99_ttft_ms"] < fifo["p99_ttft_ms"], (
            "admission control exists to cap queue wait: SLO p99 TTFT "
            f"{slo['p99_ttft_ms']:.1f}ms must beat FIFO's "
            f"{fifo['p99_ttft_ms']:.1f}ms"
        )
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-requests", type=int, default=80)
    ap.add_argument("--repeats", type=int, default=3,
                    help="best-of-N arrival processes per row (best = "
                         "lowest p99 TPOT; CI uses 3)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-slo", action="store_true",
                    help="assert the acceptance criterion: at 2.5x "
                         "load, SLO p99 TPOT <= FIFO p99 TPOT with "
                         "admission-time rejects")
    ap.add_argument("--out-dir", default=".")
    args = ap.parse_args()

    rows = run(n_requests=args.n_requests, repeats=args.repeats,
               seed=args.seed, check_slo=args.check_slo)
    print("name,p99_tpot_ms,p99_ttft_ms,reject_rate,goodput_tokens_per_s")
    for r in rows:
        print(f"{r['name']},{r['p99_tpot_ms']:.3f},"
              f"{r['p99_ttft_ms']:.3f},{r['reject_rate']:.4f},"
              f"{r['goodput_tokens_per_s']:.1f}")
    path = write_bench_json("openloop", rows, out_dir=args.out_dir)
    print(f"# wrote {path}")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
