"""Mosaic compiles of the serving-path kernels for a described TPU v5e.

Interpret mode runs a kernel body in Python and accepts what Mosaic
refuses: unaligned blocks, shape casts it cannot lay out, vector loads
from scalar memory. These tests compile each kernel for one chip of a
described ``v5e:2x2`` topology (no chip attached: the TPU compiler runs
on the host) at Qwen1.5-0.5B widths, and check that the program holds a
Mosaic kernel. The kernel modules are called directly: ``kernels.ops``
dispatch sees the CPU and would pick the XLA lowering.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import VGGB_LAYERS, get_arch
from repro.kernels import paged_attention as pa
from repro.kernels import samd_conv as sc
from repro.kernels import samd_matmul as mm
from repro.quant.config import QuantConfig

QWEN = get_arch("qwen1.5-0.5b")
BATCH, MAX_LEN, PAGE = 8, 1024, 16
PAGES = BATCH * MAX_LEN // PAGE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attention(packed, verify):
    """Paged decode (or S=3 verify) attention over a full-width pool."""
    h, hkv, dh = QWEN.n_heads, QWEN.n_kv_heads, QWEN.head_dim
    width = dh // 4 if packed else dh
    pool = ((PAGES, PAGE, hkv * width),
            jnp.uint32 if packed else jnp.bfloat16)
    q = ((BATCH, 3, h, dh) if verify else (BATCH, h, dh), jnp.bfloat16)
    pos = ((BATCH, 3) if verify else (BATCH,), jnp.int32)
    scales = [((PAGES, PAGE, hkv), jnp.float32)] * 2 if packed else []
    kernel = pa.paged_verify_attention if verify else pa.paged_decode_attention

    def fn(q, kp, vp, pt, pos, *scales):
        ks, vs = scales if scales else (None, None)
        return kernel(q, kp, vp, pt, pos, k_scale=ks, v_scale=vs,
                      interpret=False)

    return fn, [q, pool, pool, ((BATCH, MAX_LEN // PAGE), jnp.int32), pos,
                *scales]


def _matmul(bits, m, k, n):
    cfg = QuantConfig(bits=bits, backend="pallas")
    words = -(-k // cfg.values_per_word)
    fn = functools.partial(mm.samd_matmul, k=k, cfg=cfg)
    return fn, [((m, k), jnp.bfloat16), ((words, n), jnp.uint32),
                ((1, n), jnp.float32)]


def _conv(bits, layer):
    _, c_in, c_out, hgt, wid = next(v for v in VGGB_LAYERS if v[0] == layer)
    cfg = QuantConfig(bits=bits, backend="pallas")
    words = -(-c_in // cfg.values_per_word)
    fn = functools.partial(sc.samd_conv2d, cfg=cfg, padding=1)
    return fn, [((c_in, hgt, wid), jnp.bfloat16),
                ((3, 3, words, c_out), jnp.uint32), ((1, c_out), jnp.float32)]


D, F = QWEN.d_model, QWEN.d_ff
CASES = {
    "paged_decode_bf16": lambda: _attention(packed=False, verify=False),
    "paged_decode_int8": lambda: _attention(packed=True, verify=False),
    "paged_verify_bf16_s3": lambda: _attention(packed=False, verify=True),
    "paged_verify_int8_s3": lambda: _attention(packed=True, verify=True),
    "samd_matmul_b4_decode": lambda: _matmul(4, BATCH, D, F),
    "samd_matmul_b8_decode": lambda: _matmul(8, BATCH, F, D),
    "samd_matmul_b4_prefill": lambda: _matmul(4, 512, D, F),
    "samd_conv2d_b4_conv3_1": lambda: _conv(4, "conv3_1"),
}


# the op name the device trace gives each kernel (``%<name>.<n>``); the
# chip benchmark's readers find a kernel's events by it
KERNEL_NAMES = {
    "paged_decode": "paged_decode_attention",
    "paged_verify": "paged_verify_attention",
    "samd_matmul": "samd_matmul",
    "samd_conv2d": "samd_conv2d",
}


@pytest.fixture(scope="module")
def compiled(one_chip):
    """The compiled program text of a case, compiled once per module."""
    texts = {}

    def get(case):
        if case not in texts:
            fn, shapes = CASES[case]()
            args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                    for s, dt in shapes]
            texts[case] = jax.jit(fn).lower(*args).compile().as_text()
        return texts[case]

    return get


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, compiled):
    assert "tpu_custom_call" in compiled(case), case


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_instruction_name(case, compiled):
    want = next(v for k, v in KERNEL_NAMES.items() if case.startswith(k))
    names = re.findall(r"%([\w.\-]+) = [^\n]*tpu_custom_call",
                       compiled(case))
    assert names, case
    assert {re.sub(r"\.\d+$", "", n) for n in names} == {want}, names


# ---------------------------------------------------------------------------
# the step programs keep the KV pools in the layout they are stored in
# ---------------------------------------------------------------------------

SERVE_PAGES = 2048  # the chip benchmark's pool: 32768 tokens a layer


@pytest.fixture(scope="module")
def step_programs(one_chip):
    """Optimized program text of the paged ragged decode step and the
    paged prefill step of a two-layer Qwen1.5-0.5B at published widths,
    pools donated as the engine donates them, and the pool shapes."""
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.kernels import ops
    from repro.launch import steps
    from repro.models import (
        build_template, init_paged_cache, shape_dtype_from_spec,
    )

    cfg = QWEN.scaled(n_layers=2)
    run = RunConfig(arch=cfg, shape=ShapeConfig("serve", MAX_LEN, BATCH,
                                                "decode"))

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = on_chip(shape_dtype_from_spec(build_template(cfg,
                                                          stacked=False)))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_cache(cfg, SERVE_PAGES, PAGE)))
    key, temp = arg((2,), jnp.uint32), arg((), jnp.float32)
    rows, flags = arg((BATCH,), jnp.int32), arg((BATCH,), jnp.bool_)
    # ops dispatch sees the CPU: steer it to the Mosaic kernel
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_default_interpret", lambda: False)
        decode = jax.jit(
            steps.make_paged_ragged_serve_step(cfg, run, PAGE),
            donate_argnums=(2,),
        ).lower(params, arg((BATCH, 1), jnp.int32), cache, rows, flags,
                arg((BATCH, MAX_LEN // PAGE), jnp.int32), key, temp)
        prefill = jax.jit(
            steps.make_paged_prefill_step(cfg, run, PAGE),
            donate_argnums=(6,),
        ).lower(params, arg((BATCH, 256), jnp.int32), rows, rows,
                arg((BATCH, 256 // PAGE), jnp.int32), flags, cache, key,
                temp)
        texts = {"decode": decode.compile().as_text(),
                 "prefill": prefill.compile().as_text()}
    pools = {tuple(x.shape) for x in jax.tree.leaves(cache)}
    return texts, pools


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_step_program_copies_no_kv_pool(program, step_programs):
    """A pool the compiler lays out other than as it is stored is copied
    into that layout and back on every call: with [P, 16, 16, 64] pools,
    four pool-sized copies a layer. Folded [P, 16, 1024] pools need none."""
    texts, pools = step_programs
    text = texts[program]
    copies = [
        tuple(int(d) for d in m.group(1).split(","))
        for m in re.finditer(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", text)
    ]
    assert copies, "no copy at all: the pattern no longer reads the HLO"
    assert [c for c in copies if c in pools] == [], pools
    if program == "decode":
        assert "tpu_custom_call" in text
