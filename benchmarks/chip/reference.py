"""Plain float32 reference of the Qwen2 decoder (Qwen1.5), and its weights.

This file imports nothing of the program under test. It defines

* the benchmark's random weights (``init_weights``): every tensor of a
  Qwen2 checkpoint, drawn from the run's seed in one jitted call and kept
  in bfloat16, the type they are served in. The harness hands the same
  tensors to the program; the reference draws them again from the seed;
* the forward pass (``final_hidden``): token embedding, then per layer
  RMSNorm, Q/K/V projections with bias, rotary embedding (half-split,
  ``rope_theta``), causal softmax attention, output projection, residual,
  RMSNorm, SwiGLU MLP (``silu(x W_gate) * (x W_up)`` then ``W_down``),
  residual; a final RMSNorm; logits against the tied embedding. All in
  float32 with every matmul at ``Precision.HIGHEST``;
* the stated precision as plain fake-quantization: ``weight_bits``
  quantizes each layer matrix symmetrically per output channel (scale
  ``max|w| / (2^(b-1) - 1)``, round half to even, clip), ``embed_bits``
  does the same per vocabulary row of the tied embedding, ``kv_bits``
  quantizes each key and value vector (after RoPE) per token and head,
  and ``act_bits`` each activation row that enters a matmul, per token.
  None keeps the tensor as its bfloat16 value (activations float32);
* ``served_gaps``: teacher-forced over a prompt and the tokens a server
  produced, the gap by which each served token's logit lies below the
  reference's best logit at its position, and the same gap for the token
  that a second, lower-precision forward puts first (the control).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# (name, shape in units of the config, kind); layer tensors are stacked
# over a leading layer axis
_LAYER_TENSORS = (
    ("in_norm", ("D",), "gain"),
    ("q_w", ("D", "HD"), "matrix"),
    ("q_b", ("HD",), "bias"),
    ("k_w", ("D", "KD"), "matrix"),
    ("k_b", ("KD",), "bias"),
    ("v_w", ("D", "KD"), "matrix"),
    ("v_b", ("KD",), "bias"),
    ("o_w", ("HD", "D"), "matrix"),
    ("post_norm", ("D",), "gain"),
    ("gate_w", ("D", "F"), "matrix"),
    ("up_w", ("D", "F"), "matrix"),
    ("down_w", ("F", "D"), "matrix"),
)
MATRICES = tuple(n for n, _, k in _LAYER_TENSORS if k == "matrix")


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    dh = d // h
    return {
        "D": d, "H": h, "KV": conf["num_key_value_heads"], "dh": dh,
        "HD": h * dh, "KD": conf["num_key_value_heads"] * dh,
        "F": conf["intermediate_size"], "V": conf["vocab_size"],
        "L": conf["num_hidden_layers"], "eps": conf["rms_norm_eps"],
        "theta": conf["rope_theta"],
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (64 bits are used)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weight_shapes(conf: dict) -> dict:
    m = dims(conf)
    out = {"embed": ((m["V"], m["D"]), "embed"),
           "final_norm": ((m["D"],), "gain")}
    for name, shape, kind in _LAYER_TENSORS:
        out[name] = ((m["L"],) + tuple(m[s] for s in shape), kind)
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def init(spec: tuple, key: jax.Array) -> dict:
    out = {}
    for i, (name, shape, kind, std) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (1.0 + std * x if kind == "gain" else std * x).astype(
            jnp.bfloat16)
    return out


def weight_spec(conf: dict) -> tuple:
    """The static description ``init`` draws the weights from: each
    tensor's name, shape, kind and standard deviation. Matrices and the
    embedding take ``initializer_range``, the query and key projections
    ``weights.qk_std``, biases ``weights.bias_std``, and norm gains are
    1 + N(0, ``weights.gain_std``)."""
    w = conf["weights"]
    std = {"gain": w["gain_std"], "bias": w["bias_std"],
           "matrix": conf["initializer_range"],
           "embed": conf["initializer_range"]}
    return tuple(
        (n, s, k, float(w["qk_std"] if n in ("q_w", "k_w") else std[k]))
        for n, (s, k) in sorted(weight_shapes(conf).items()))


def init_weights(conf: dict, seed: int) -> dict:
    """Every tensor of the checkpoint, bfloat16, on the default device."""
    return init(weight_spec(conf), seed_key(seed))


# ---------------------------------------------------------------------------
# fake quantization of the stated precision
# ---------------------------------------------------------------------------


def fake_quant(x: jax.Array, bits, axis: int) -> jax.Array:
    """Symmetric quantize-dequantize along ``axis`` (the reduced axis)."""
    if bits is None:
        return x
    qmax = float((1 << (bits - 1)) - 1)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def fake_quant_kv(x: jax.Array, bits) -> jax.Array:
    """Per-(token, head) quantize-dequantize of keys or values [T, Hkv, dh]."""
    if bits is None:
        return x
    qmax = float((1 << (bits - 1)) - 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, pos, theta):
    """x [T, heads, dh]; rotate the two halves of dh (Qwen2 convention)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, chunk):
    """Causal attention; q [T, H, dh], k/v [T, KV, dh]; query chunks."""
    t, h, dh = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(t)

    def one(args):
        qc, qpos = args
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(dh))
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    n = t // chunk
    out = jax.lax.map(one, (q.reshape(n, chunk, h, dh),
                            jnp.arange(t).reshape(n, chunk)))
    return out.reshape(t, h, dh)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _final_hidden(m: tuple, prec: tuple, w: dict, tokens: jax.Array):
    m = dict(m)
    wbits, ebits, kvbits, abits = prec

    def _linear(x, w, b=None):
        x = fake_quant(x, abits, axis=-1)
        y = jnp.matmul(x, w, precision=HIGHEST)
        return y if b is None else y + b

    t = tokens.shape[0]
    pos = jnp.arange(t)
    emb = fake_quant(w["embed"].astype(jnp.float32), ebits, axis=1)
    x = emb[tokens]
    layers = {n: w[n] for n, _, _ in _LAYER_TENSORS}

    def layer(x, lw):
        f = {n: a.astype(jnp.float32) for n, a in lw.items()}
        for n in MATRICES:
            f[n] = fake_quant(f[n], wbits, axis=0)
        h = _rms_norm(x, f["in_norm"], m["eps"])
        q = _linear(h, f["q_w"], f["q_b"]).reshape(t, m["H"], m["dh"])
        k = _linear(h, f["k_w"], f["k_b"]).reshape(t, m["KV"], m["dh"])
        v = _linear(h, f["v_w"], f["v_b"]).reshape(t, m["KV"], m["dh"])
        q = _rope(q, pos, m["theta"])
        k = fake_quant_kv(_rope(k, pos, m["theta"]), kvbits)
        v = fake_quant_kv(v, kvbits)
        a = _attention(q, k, v, min(t, 512)).reshape(t, m["HD"])
        x = x + _linear(a, f["o_w"])
        h = _rms_norm(x, f["post_norm"], m["eps"])
        gate = _linear(h, f["gate_w"])
        x = x + _linear(jax.nn.silu(gate) * _linear(h, f["up_w"]),
                        f["down_w"])
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    x = _rms_norm(x, w["final_norm"].astype(jnp.float32), m["eps"])
    return fake_quant(x, abits, axis=-1), emb


def final_hidden(conf: dict, w: dict, tokens: np.ndarray, prec: dict):
    """Final normed hidden states [T_pad, D] and the (fake-quantized)
    float32 embedding. ``tokens`` is padded to a power of two (at least
    512) so that few shapes compile; padding sits after every real token
    and, attention being causal, changes nothing before it."""
    n = len(tokens)
    t = 512
    while t < n:
        t *= 2
    padded = np.zeros(t, np.int32)
    padded[:n] = tokens
    m = tuple(sorted(dims(conf).items()))
    p = (prec.get("weight_bits"), prec.get("embed_bits"),
         prec.get("kv_bits"), prec.get("act_bits"))
    return _final_hidden(m, p, w, jnp.asarray(padded))


@jax.jit
def _gap_block(h, emb, served, h_ctl, emb_ctl):
    logits = jnp.matmul(h, emb.T, precision=HIGHEST)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    ctl = jnp.argmax(jnp.matmul(h_ctl, emb_ctl.T, precision=HIGHEST), -1)
    ctl_got = jnp.take_along_axis(logits, ctl[:, None], axis=-1)[:, 0]
    return best - got, best - ctl_got


def served_gaps(conf: dict, w: dict, prompt, served, stated: dict,
                control: dict | None = None, block: int = 256):
    """Teacher-forced gaps over one request.

    ``prompt`` [P] and ``served`` [G] are token ids; the reference reads
    prompt + served[:-1] and, at position P - 1 + j, compares its logits
    with served[j]. Returns numpy arrays (served_gap [G], control_gap
    [G] or None): each the reference's best logit minus its logit for
    the served token, or for the control's first choice.
    """
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    h, emb = final_hidden(conf, w, seq, stated)
    h_c, emb_c = (h, emb) if control is None else final_hidden(
        conf, w, seq, control)
    first = len(prompt) - 1
    gaps, ctl = [], []
    for s in range(0, len(served), block):
        rows = slice(first + s, first + min(s + block, len(served)))
        tok = np.zeros(block, np.int32)
        n = rows.stop - rows.start
        tok[:n] = served[s:s + n]
        # fixed block shape: pad the rows by repeating the last one
        idx = np.minimum(np.arange(rows.start, rows.start + block),
                         rows.stop - 1)
        g, c = _gap_block(h[idx], emb, jnp.asarray(tok), h_c[idx], emb_c)
        gaps.append(np.asarray(g)[:n])
        ctl.append(np.asarray(c)[:n])
    return (np.concatenate(gaps),
            None if control is None else np.concatenate(ctl))
