#!/usr/bin/env python
"""One-chip smoke test of the serving engine at full Qwen1.5-0.5B width.

Run from the repository root on a machine with one TPU:

    python chip_smoke.py [--seed N]

It refuses to run, exits non-zero and prints no result unless JAX's
first device is a TPU: there is no CPU fallback and no interpret mode.
In one process it

1. runs every Pallas kernel of the serving path on the chip at
   Qwen1.5-0.5B widths (paged decode and verify attention over bf16 and
   packed int8 KV pages, ``samd_matmul`` at 4 and 8 bits for decode and
   prefill GEMMs) plus ``samd_conv2d`` on VGG-B conv3_1, each against
   its pure-jnp reference;
2. serves requests through ``ServingEngine`` on the default path (bf16
   weights, paged bf16 KV, fused paged attention, greedy), checks that
   the compiled decode program holds Mosaic kernels, and compares one
   decode step's logits with the dense-gather reference path;
3. serves the same requests on the packed path: 4-bit SAMD-packed
   weights through ``samd_matmul``, packed int8 KV pages, and
   self-speculative decoding with K=2 (the packed verify kernel).

Weights are random, made from ``--seed``; no checkpoint ships with the
repository. The times it prints are smoke timings, not benchmark
results. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def require_tpu():
    import jax

    dev = jax.devices()[0]
    check(
        dev.platform == "tpu",
        f"chip_smoke needs a TPU; JAX's first device is platform "
        f"{dev.platform!r} ({dev.device_kind}). There is no CPU fallback.",
    )
    return dev


@dataclasses.dataclass
class Sizes:
    """Engine and workload sizes of one smoke run."""

    max_batch: int = 8
    max_len: int = 1024
    page_size: int = 16
    n_requests: int = 8
    prompt_max: int = 512
    new_tokens: int = 32
    verify_len: int = 3       # speculative K=2 verifies K+1 tokens
    conv_layer: str = "conv3_1"


class CompileClock:
    """While entered, sums JAX's backend-compile durations (persistent-
    cache reads included), so each phase reports compile seconds beside
    its wall seconds."""

    def __init__(self):
        self.seconds = 0.0
        self._listener = self._on_event

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)


def has_mosaic_kernel(compiled) -> bool:
    """True iff the compiled program calls a Mosaic (Pallas TPU) kernel."""
    return "tpu_custom_call" in compiled.as_text()


def run_phase(name, fn, clock, dev):
    t0, c0 = time.perf_counter(), clock.seconds
    fn()
    wall = time.perf_counter() - t0
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print(
        f"[smoke timing, not a benchmark] {name}: wall {wall:.2f} s, "
        f"backend compile {clock.seconds - c0:.2f} s, "
        f"peak_bytes_in_use {peak}",
        flush=True,
    )
    print(f"PASS {name}", flush=True)


# ---------------------------------------------------------------------------
# phase 1: kernels at real widths against their references
# ---------------------------------------------------------------------------


def _paged_pools(rng, cfg, sizes, packed):
    """Random KV pools plus a page table and positions in which every slot
    holds a different number of pages (the engine's ragged state)."""
    import jax.numpy as jnp

    from repro.quant.packing import pack_int8_lanes

    b, ps = sizes.max_batch, sizes.page_size
    n_pp = sizes.max_len // ps
    pages = b * n_pp
    # heads folded into the minor dim, as init_paged_cache stores them
    shape = (pages, ps, cfg.n_kv_heads, cfg.head_dim)
    folded = (pages, ps, -1)
    if packed:
        def pool():
            vals = rng.integers(-127, 128, size=shape)
            words = pack_int8_lanes(jnp.asarray(vals, jnp.int8))
            return words.reshape(folded)

        def scale():
            return jnp.asarray(
                rng.uniform(0.002, 0.02, size=shape[:3]), jnp.float32)

        kp, vp, ks, vs = pool(), pool(), scale(), scale()
    else:
        def pool():
            vals = rng.normal(size=shape).reshape(folded)
            return jnp.asarray(vals, jnp.bfloat16)

        kp, vp, ks, vs = pool(), pool(), None, None
    perm = rng.permutation(pages)
    table = np.full((b, n_pp), -1, np.int32)
    pos = np.zeros(b, np.int32)
    for i in range(b):
        held = int(rng.integers(1, n_pp + 1))
        table[i, :held] = perm[i * n_pp:i * n_pp + held]
        pos[i] = (held - 1) * ps + int(rng.integers(0, ps))
    return kp, vp, ks, vs, jnp.asarray(table), pos


def _attention_cases(rng, cfg, sizes):
    import jax.numpy as jnp

    from repro.kernels import paged_attention as pa
    from repro.kernels import ref

    b, h, dh, sq = sizes.max_batch, cfg.n_heads, cfg.head_dim, sizes.verify_len
    cases = []
    for packed in (False, True):
        kind = "int8" if packed else "bf16"
        kp, vp, ks, vs, pt, pos = _paged_pools(rng, cfg, sizes, packed)
        q = jnp.asarray(rng.normal(size=(b, h, dh)), jnp.bfloat16)

        def decode(q, kp, vp, pt, pos, ks, vs):
            return pa.paged_decode_attention(
                q, kp, vp, pt, pos, k_scale=ks, v_scale=vs, interpret=False)

        def decode_ref(q, kp, vp, pt, pos, ks, vs):
            return ref.paged_attention_ref(q, kp, vp, pt, pos, ks, vs)

        cases.append((f"paged_decode_attention[{kind}]", decode, decode_ref,
                      (q, kp, vp, pt, jnp.asarray(pos), ks, vs), 2e-2))

        # verify q-block: each slot's window ends at its position; the
        # last slot's budget leaves its final query masked (-1)
        qv = jnp.asarray(rng.normal(size=(b, sq, h, dh)), jnp.bfloat16)
        vpos = np.maximum(pos[:, None] - (sq - 1) + np.arange(sq), 0)
        vpos[-1, -1] = -1

        def verify(q, kp, vp, pt, pos, ks, vs):
            return pa.paged_verify_attention(
                q, kp, vp, pt, pos, k_scale=ks, v_scale=vs, interpret=False)

        def verify_ref(q, kp, vp, pt, pos, ks, vs):
            cols = [
                ref.paged_attention_ref(q[:, j], kp, vp, pt, pos[:, j],
                                        ks, vs)
                for j in range(q.shape[1])
            ]
            out = jnp.stack(cols, axis=1)
            return jnp.where((pos >= 0)[:, :, None, None], out, 0)

        cases.append((f"paged_verify_attention[{kind},S={sq}]", verify,
                      verify_ref, (qv, kp, vp, pt, jnp.asarray(vpos), ks, vs),
                      2e-2))
    return cases


def _matmul_cases(rng, cfg, sizes):
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels import samd_matmul as mm
    from repro.quant import QuantConfig, pack_weights

    d, f = cfg.d_model, cfg.d_ff
    gemms = [
        ("decode", sizes.max_batch, d, f),   # gate/up projection
        ("decode", sizes.max_batch, f, d),   # down projection
        ("prefill", sizes.prompt_max, d, f),
    ]
    cases = []
    for bits in (4, 8):
        qcfg = QuantConfig(bits=bits, backend="pallas")
        for tag, m, k, n in gemms:
            x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
            w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
            packed, scale = pack_weights(w, qcfg)

            def kern(x, packed, scale, k=k, qcfg=qcfg):
                return mm.samd_matmul(x, packed, scale, k, qcfg)

            def want(x, packed, scale, k=k, qcfg=qcfg):
                return ref.samd_matmul_ref(
                    x.astype(jnp.float32), packed, scale, k, qcfg)

            cases.append((f"samd_matmul[b{bits},{tag} {m}x{k}x{n}]", kern,
                          want, (x, packed, scale), None))
    return cases


def _conv_case(rng, sizes):
    import jax.numpy as jnp

    from repro.configs import VGGB_LAYERS
    from repro.kernels import ref
    from repro.kernels import samd_conv as sc
    from repro.quant import QuantConfig
    from repro.quant.packing import pack_conv_weights

    name, c_in, c_out, hgt, wid = next(
        layer for layer in VGGB_LAYERS if layer[0] == sizes.conv_layer)
    qcfg = QuantConfig(bits=4, backend="pallas")
    x = jnp.asarray(rng.normal(size=(c_in, hgt, wid)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)
    packed, scale = pack_conv_weights(w, qcfg)

    def kern(x, packed, scale):
        return sc.samd_conv2d(x, packed, scale, qcfg, padding=1)

    def want(x, packed, scale):
        return ref.samd_conv2d_ref(
            x.astype(jnp.float32), packed, scale, qcfg, padding=1)

    return (f"samd_conv2d[b4,{name} {c_in}->{c_out} {hgt}x{wid}]", kern,
            want, (x, packed, scale), None)


def kernels_phase(cfg, sizes, seed):
    """Every kernel compiled for the chip, run, and compared with its
    reference. ``tol`` None means 1e-2 of the reference's largest
    magnitude: the kernels accumulate exact integer codes in f32, so the
    gap to an f32 reference is the bf16 rounding of the output."""
    import jax

    rng = np.random.default_rng(seed)
    cases = (_attention_cases(rng, cfg, sizes)
             + _matmul_cases(rng, cfg, sizes) + [_conv_case(rng, sizes)])
    for name, kern, want_fn, args, tol in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(kern).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check(has_mosaic_kernel(compiled),
              f"{name}: compiled program holds no Mosaic kernel")
        got = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(want_fn)(*args), np.float32)
        check(got.shape == want.shape, f"{name}: shape {got.shape} "
              f"!= reference {want.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        if tol is None:
            tol = 1e-2 * float(np.max(np.abs(want)))
        err = float(np.max(np.abs(got - want)))
        print(f"kernel {name}: max_abs_err {err:.3e} (tol {tol:.3e}), "
              f"compile {compile_s:.2f} s", flush=True)
        check(err <= tol, f"{name}: max_abs_err {err:.3e} > tol {tol:.3e}")


# ---------------------------------------------------------------------------
# phases 2 and 3: the serving engine
# ---------------------------------------------------------------------------


def make_requests(cfg, sizes, seed):
    """``n_requests`` prompts of 16..prompt_max tokens (the longest at
    prompt_max), each asking for ``new_tokens`` tokens, no eos."""
    from repro.serving import Request

    rng = np.random.default_rng(seed + 1)
    lens = rng.integers(16, sizes.prompt_max + 1, size=sizes.n_requests)
    lens[0] = sizes.prompt_max
    return [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab, size=int(n)).astype(
                    np.int32),
                max_tokens=sizes.new_tokens)
        for i, n in enumerate(lens)
    ]


def check_served(done, reqs, cfg, label):
    check(len(done) == len(reqs),
          f"{label}: {len(done)} of {len(reqs)} requests finished")
    for r in done:
        check(r.error is None, f"{label}: request {r.rid} error {r.error!r}")
        check(not r.truncated, f"{label}: request {r.rid} truncated")
        check(len(r.generated) == r.max_tokens,
              f"{label}: request {r.rid} got {len(r.generated)} of "
              f"{r.max_tokens} tokens")
        check(all(0 <= t < cfg.vocab for t in r.generated),
              f"{label}: request {r.rid} emitted a token outside the vocab")
    n_tok = sum(len(r.generated) for r in done)
    print(f"{label}: {len(done)} requests served, {n_tok} tokens, "
          f"prompts {min(len(r.prompt) for r in done)}.."
          f"{max(len(r.prompt) for r in done)} tokens", flush=True)


def decode_logits_vs_gather(eng, rel_tol=2e-2):
    """One decode step's logits at the engine's live, ragged slot state:
    the fused paged-attention kernel path against the dense page-gather
    reference path, over the same weights and KV pool. Passes iff
    max|fused - gather| <= rel_tol * max|gather|."""
    import jax
    import jax.numpy as jnp

    from repro.models import forward

    def logits(paged_attn):
        def fn(params, tokens, cache, pos, table):
            lg, _, _ = forward(
                params, tokens, eng.cfg, positions=pos[:, None], cache=cache,
                page_table=table, page_size=eng.page_size,
                paged_attn=paged_attn)
            return lg[:, -1].astype(jnp.float32)

        return np.asarray(jax.jit(fn)(
            eng.params, jnp.asarray(eng.slot_next[:, None]), eng.cache,
            jnp.asarray(eng.slot_pos), jnp.asarray(eng.page_table)))

    rows = np.nonzero(eng.active)[0]
    check(len(rows) > 1, "logits check needs several active slots")
    fused, gather = logits("fused")[rows], logits("gather")[rows]
    err = float(np.max(np.abs(fused - gather)))
    tol = rel_tol * float(np.max(np.abs(gather)))
    same = int(np.sum(fused.argmax(-1) == gather.argmax(-1)))
    print(f"decode logits fused vs gather: {len(rows)} slots at positions "
          f"{sorted(int(p) for p in eng.slot_pos[rows])}, max_abs_err "
          f"{err:.3e} (tol {tol:.3e} = {rel_tol} x max|logit|), argmax "
          f"agrees on {same}/{len(rows)}", flush=True)
    check(bool(np.isfinite(fused).all()), "fused decode logits non-finite")
    check(err <= tol, f"decode logits: max_abs_err {err:.3e} > {tol:.3e}")


def engine_default_phase(cfg, sizes, seed):
    from repro.serving import ServingEngine

    eng = ServingEngine(cfg, max_batch=sizes.max_batch,
                        max_len=sizes.max_len, page_size=sizes.page_size,
                        seed=seed)
    print(f"engine A: bf16 weights, paged bf16 KV ({eng.num_pages} pages of "
          f"{eng.page_size}), paged_attn={eng.paged_attn}, greedy, "
          f"KV pool {eng.kv_cache_bytes()} bytes", flush=True)
    reqs = make_requests(cfg, sizes, seed)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):   # admit everyone, then decode at ragged positions
        eng.step()
    check(bool(eng.active.any()), "engine A: no slot active after prefill")
    prog = eng.decode_program()
    n_kernels = prog.as_text().count("tpu_custom_call")
    print(f"engine A decode program: {n_kernels} tpu_custom_call sites for "
          f"{cfg.n_layers} layers", flush=True)
    check(has_mosaic_kernel(prog),
          "engine A: the fused decode program holds no Mosaic kernel")
    decode_logits_vs_gather(eng)
    done = eng.run_to_completion()
    check_served(done, reqs, cfg, "engine A")
    print(f"engine A stats: {eng.stats}", flush=True)


def engine_packed_phase(cfg, sizes, seed):
    from repro.quant import QuantConfig
    from repro.serving import ServingEngine

    quant = QuantConfig(bits=4, backend="pallas", kv_bits=8)
    eng = ServingEngine(cfg, quant=quant, speculative=2,
                        max_batch=sizes.max_batch, max_len=sizes.max_len,
                        page_size=sizes.page_size, seed=seed)
    print(f"engine B: {quant}, speculative=2, KV pool "
          f"{eng.kv_cache_bytes()} bytes", flush=True)
    reqs = make_requests(cfg, sizes, seed)
    for r in reqs:
        eng.submit(r)
    done = eng.run_to_completion()
    check_served(done, reqs, cfg, "engine B")
    check(eng.stats["spec_ticks"] > 0, "engine B ran no speculative tick")
    proposed = eng.stats["draft_proposed"]
    rate = eng.stats["draft_accepted"] / max(proposed, 1)
    print(f"engine B accept rate {rate:.4f} ({eng.stats['draft_accepted']}/"
          f"{proposed} drafts), {eng.stats['spec_ticks']} speculative "
          f"ticks", flush=True)
    print(f"engine B stats: {eng.stats}", flush=True)


def run(dev, cfg, sizes: Sizes, seed: int) -> None:
    print(f"device: {dev.platform} {dev.device_kind}; model {cfg.name}: "
          f"{cfg.n_layers} layers, d {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
          f"{sizes}", flush=True)
    with CompileClock() as clock:
        run_phase("kernels", lambda: kernels_phase(cfg, sizes, seed),
                  clock, dev)
        run_phase("engine A (default path)",
                  lambda: engine_default_phase(cfg, sizes, seed), clock,
                  dev)
        gc.collect()  # engine A's pool and weights leave the device
        run_phase("engine B (packed path)",
                  lambda: engine_packed_phase(cfg, sizes, seed), clock,
                  dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and prompts")
    args = ap.parse_args(argv)
    try:
        dev = require_tpu()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_arch
    from repro.launch import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    try:
        run(dev, get_arch("qwen1.5-0.5b"), Sizes(), args.seed)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
