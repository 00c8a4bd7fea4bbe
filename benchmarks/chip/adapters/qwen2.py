"""Qwen2-family configuration file -> the program's model and weights.

The one place that knows both the benchmark's tensor names
(``reference.init_weights``) and the program's parameter layout
(``repro.models.build_template(cfg, stacked=False)``).
"""
from __future__ import annotations

import functools

import jax

from benchmarks.chip import reference


def arch(conf: dict):
    """The program's ``ArchConfig`` for a Qwen2 ``config.json``."""
    from repro.configs.base import ArchConfig

    if conf["hidden_act"] != "silu" or conf.get("use_sliding_window"):
        raise ValueError("the adapter covers SwiGLU, full-attention Qwen2")
    m = reference.dims(conf)
    return ArchConfig(
        name=conf["name"], family="dense", n_layers=m["L"], d_model=m["D"],
        vocab=m["V"], n_heads=m["H"], n_kv_heads=m["KV"], head_dim=m["dh"],
        qkv_bias=True, rope_theta=float(m["theta"]), d_ff=m["F"],
        activation="swiglu", norm_eps=float(m["eps"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
    )


def quant(conf: dict):
    """The program's ``QuantConfig`` from the file's ``serving.quant``."""
    from repro.quant import QuantConfig

    q = conf["serving"].get("quant")
    return None if q is None else QuantConfig(**q)


_NAMES = {
    "attn": {"ln": "in_norm", "wq": "q_w", "bq": "q_b", "wk": "k_w",
             "bk": "k_b", "wv": "v_w", "bv": "v_b", "wo": "o_w"},
    "mlp": {"ln": "post_norm", "wg": "gate_w", "wu": "up_w",
            "wd": "down_w"},
}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _program_init(n_layers: int, spec: tuple, key: jax.Array) -> dict:
    w = reference.init(spec, key)
    return {
        "embed": w["embed"],
        "final_ln": w["final_norm"],
        "blocks": [
            {part: {p: w[b][i] for p, b in names.items()}
             for part, names in _NAMES.items()}
            for i in range(n_layers)
        ],
    }


def program_params(conf: dict, seed: int) -> dict:
    """The run's weights in the program's layout, made on the device in
    one jitted call; the same values ``reference.init_weights`` draws."""
    return _program_init(conf["num_hidden_layers"],
                         reference.weight_spec(conf),
                         reference.seed_key(seed))
