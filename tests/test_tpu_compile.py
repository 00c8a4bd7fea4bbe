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
    pool = ((PAGES, PAGE, hkv, width), jnp.uint32 if packed else jnp.bfloat16)
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
