"""The plain float32 reference against the program at toy size: prefill
then decode through the paged engine, bf16 and 4-bit weights with int8
KV pages, and the reference's own arithmetic."""
import numpy as np
import pytest

import chipbench_toy
from benchmarks.chip import reference
from benchmarks.chip.adapters import qwen2

# the widest gap of a served token at toy size: bf16 activations and, at
# 4 bits, the kernels' accumulation order leave near-ties to rounding
WIDEST = {"toy-bf16": 0.3, "toy-w4kv8": 2.0}


@pytest.mark.parametrize("name", ["toy-bf16", "toy-w4kv8"])
def test_engine_tokens_are_the_references(name):
    from repro.serving import Request, ServingEngine

    conf = dict(chipbench_toy.CONFIGS[name], name=name)
    s = conf["serving"]
    eng = ServingEngine(qwen2.arch(conf), qwen2.program_params(conf, 5),
                        quant=qwen2.quant(conf), max_batch=s["max_batch"],
                        max_len=s["max_len"], page_size=s["page_size"],
                        num_pages=s["num_pages"], seed=5)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, conf["vocab_size"], n).astype(np.int32)
               for n in (40, 17, 33)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_tokens=24))
    done = {r.rid: r for r in eng.run_to_completion()}
    w = reference.init_weights(conf, 5)
    for i, p in enumerate(prompts):
        gaps, _ = reference.served_gaps(conf, w, p, done[i].generated,
                                        conf["precision"]["stated"])
        assert len(gaps) == 24
        assert float(gaps.max()) <= WIDEST[name]


def test_program_weights_are_the_references():
    conf = dict(chipbench_toy.CONFIGS["toy-bf16"], name="toy")
    prog = qwen2.program_params(conf, 2**33 + 1)
    w = reference.init_weights(conf, 2**33 + 1)
    np.testing.assert_array_equal(np.asarray(prog["embed"]),
                                  np.asarray(w["embed"]))
    np.testing.assert_array_equal(
        np.asarray(prog["blocks"][1]["attn"]["bk"]), np.asarray(w["k_b"][1]))
    np.testing.assert_array_equal(
        np.asarray(prog["blocks"][0]["mlp"]["wd"]),
        np.asarray(w["down_w"][0]))
    other = reference.init_weights(conf, 1)
    assert not np.array_equal(np.asarray(other["embed"]),
                              np.asarray(w["embed"]))


def test_fake_quant_by_hand():
    x = np.array([[0.7, -7.0], [0.3, 2.4]], np.float32)
    # per column: scale max|x| / 7 at 4 bits, so 0.1 and 1.0
    got = np.asarray(reference.fake_quant(x, 4, axis=0))
    want = np.array([[0.7, -7.0], [0.3, 2.0]])
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert reference.fake_quant(x, None, axis=0) is x


def test_padding_changes_nothing_before_it():
    conf = dict(chipbench_toy.CONFIGS["toy-bf16"], name="toy")
    w = reference.init_weights(conf, 3)
    toks = np.arange(50, dtype=np.int32)
    h_short, _ = reference.final_hidden(conf, w, toks, {})
    h_long, _ = reference.final_hidden(
        conf, w, np.concatenate([toks, np.ones(600, np.int32)]), {})
    np.testing.assert_allclose(np.asarray(h_short)[:50],
                               np.asarray(h_long)[:50], atol=1e-5)
