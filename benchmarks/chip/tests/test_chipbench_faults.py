"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference one precision step below the stated
one) reads above the limit that sound runs stay under.

Toy-size, on the CPU; the harness's look for a chip is skipped."""
import time

import jax
import numpy as np
import pytest

import chipbench_toy
from benchmarks.chip import harness, reference
from benchmarks.chip.adapters import qwen2


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return chipbench_toy.toy_root(tmp_path_factory.mktemp("toy"))


def alter_tokens(eng):
    """A token altered where it is produced: the decode step's output."""
    step = eng._ragged_step

    def broken(*a):
        ids, cache = step(*a)
        return jax.numpy.where(ids >= 0, (ids + 1) % eng.cfg.vocab, ids), \
            cache

    eng._ragged_step = broken


def keep_state(eng):
    """A step that returns its state unchanged: decode's KV writes are
    dropped, so later tokens attend to stale pages."""
    step = eng._ragged_step

    def broken(*a):
        old = jax.tree.map(lambda x: x.copy(), a[2])
        ids, _ = step(*a)
        return ids, old

    eng._ragged_step = broken


@pytest.mark.parametrize("fault", [alter_tokens, keep_state])
@pytest.mark.parametrize("cell", ["toy-bf16.toy_chat",
                                  "toy-w4kv8.toy_chat"])
def test_fault_makes_correct_false(toy, cell, fault):
    res = harness.run(toy, cell, 11, 1.5, False, time.perf_counter(),
                      fault=fault)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for k, v in res["check"].items()
               if k != "tokens_compared")


@pytest.mark.parametrize("name", ["toy-wide-bf16", "toy-w4kv8"])
def test_control_fails_where_sound_runs_pass(name):
    """The mean gap of served tokens stays under the cell's limit, and
    the control's (the reference one precision step below) does not:
    three seeds, a fixed set of requests through the paged engine."""
    from repro.serving import Request, ServingEngine

    conf = dict(chipbench_toy.CONFIGS[name], name=name)
    limit = chipbench_toy.CHECKS[name]["served_gap_mean"]["limit"]
    s, prec = conf["serving"], conf["precision"]
    served, control = [], []
    for seed in (21, 22, 23):
        eng = ServingEngine(qwen2.arch(conf), qwen2.program_params(conf, seed),
                            quant=qwen2.quant(conf), max_batch=s["max_batch"],
                            max_len=s["max_len"], page_size=s["page_size"],
                            num_pages=s["num_pages"], seed=seed)
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, conf["vocab_size"], n).astype(np.int32)
                   for n in (40, 17, 33)]
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_tokens=32))
        done = {r.rid: r.generated for r in eng.run_to_completion()}
        w = reference.init_weights(conf, seed)
        gaps = [reference.served_gaps(conf, w, p, done[i], prec["stated"],
                                      prec["control"])
                for i, p in enumerate(prompts)]
        served.append(float(np.mean(np.concatenate([g for g, _ in gaps]))))
        control.append(float(np.mean(np.concatenate([k for _, k in gaps]))))
    assert max(served) <= limit < min(control)
