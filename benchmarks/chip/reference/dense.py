"""The ``dense`` family: the decoder of the configurations whose ``model``
says ``"family": "dense"`` — its parameter layout, its plain float32
forward and loss, and its model FLOPs (the family API of
:mod:`benchmarks.chip.manifest`).

Straightforward ``jax.numpy`` under ``precision="highest"``: no kernels, no
cache, no batching tricks, one layer at a time so that the reference fits
beside the served weights at full width.  It follows the model the program
runs, which departs from the published checkpoints where the configuration
file lists it under ``assumed``:

* positions by rotary embedding (rotate-half, theta from the file), also for
  OPT, whose checkpoint learns absolute positions;
* token embeddings scaled by sqrt(d_model) before the first block;
* no biases on the linear layers except q/k/v where ``qkv_bias`` is set
  (OPT's checkpoint has biases on every linear layer);
* untied input embedding and output head.

``precision="fp8"`` is the control: every tensor the configuration holds in
bfloat16 — weights, the embedding rows, the residual stream, the outputs of
norms, matrix products and attention — is held in float8 (e4m3, one scale
per tensor) instead, the step below bfloat16 that a faster forward would
take; sums and softmax stay in float32, as the program keeps them.

A weight source is a function ``get(path, layer)`` that returns a float32
leaf, or its slice for one layer (``layer`` None for unstacked leaves); the
embedding is asked for by rows (``get_rows(ids)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.chip.weights import padded_vocab

HIGHEST = jax.lax.Precision.HIGHEST

# Small models that the CPU tests run against the program: an OPT-like and
# a Qwen2-like block (grouped kv heads, gated FFN, RMSNorm, q/k/v biases).
CPU_CASES = {
    "opt-like": {"name": "opt-tiny", "family": "dense", "n_layers": 2,
                 "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 256,
                 "vocab_size": 300, "activation": "relu", "gated_ffn": False,
                 "norm": "layernorm", "qkv_bias": False,
                 "rope_theta": 10000.0, "max_seq": 64, "dtype": "float32"},
    "qwen2-like": {"name": "qwen-tiny", "family": "dense", "n_layers": 2,
                   "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
                   "vocab_size": 300, "activation": "silu", "gated_ffn": True,
                   "norm": "rmsnorm", "qkv_bias": True, "rope_theta": 1e6,
                   "max_seq": 64, "dtype": "float32"},
}


def leaf_specs(model: dict) -> dict:
    """``path -> (shape, kind, std)`` of every leaf.  ``kind`` is ``normal``
    (std given), ``ones`` or ``zeros``.  Layers stacked on axis 0, the
    layout the program's dense forward takes:

        embed (Vp, d) · head (d, Vp) · ln_f
        layers: ln1, ln2 {scale[, bias]} · attn {wq, wk, wv, wo[, bq, bk, bv]}
                · mlp {w1, w2[, w3]}

    Vp is the vocabulary padded to a multiple of 128."""
    d, L, ff = model["d_model"], model["n_layers"], model["d_ff"]
    H = model["n_heads"]
    kv = model.get("n_kv_heads") or H
    hd = d // H
    V = padded_vocab(model)
    rms = model.get("norm", "layernorm") == "rmsnorm"

    def norm(prefix, lead=()):
        out = {f"{prefix}/scale": (lead + (d,), "zeros" if rms else "ones", 0.0)}
        if not rms:
            out[f"{prefix}/bias"] = (lead + (d,), "zeros", 0.0)
        return out

    s = {"embed": ((V, d), "normal", 0.02),
         "head": ((d, V), "normal", d ** -0.5)}
    s.update(norm("ln_f"))
    s.update(norm("layers/ln1", (L,)))
    s.update(norm("layers/ln2", (L,)))
    s["layers/attn/wq"] = ((L, d, H * hd), "normal", d ** -0.5)
    s["layers/attn/wk"] = ((L, d, kv * hd), "normal", d ** -0.5)
    s["layers/attn/wv"] = ((L, d, kv * hd), "normal", d ** -0.5)
    s["layers/attn/wo"] = ((L, H * hd, d), "normal", (H * hd) ** -0.5)
    if model.get("qkv_bias"):
        s["layers/attn/bq"] = ((L, H * hd), "normal", 0.02)
        s["layers/attn/bk"] = ((L, kv * hd), "normal", 0.02)
        s["layers/attn/bv"] = ((L, kv * hd), "normal", 0.02)
    s["layers/mlp/w1"] = ((L, d, ff), "normal", d ** -0.5)
    s["layers/mlp/w2"] = ((L, ff, d), "normal", ff ** -0.5)
    if model.get("gated_ffn"):
        s["layers/mlp/w3"] = ((L, d, ff), "normal", d ** -0.5)
    return s


def matmul_params(model: dict) -> int:
    """Every leaf but the embedding (a gather, not a matmul); the head
    counts the true vocabulary only."""
    n = 0
    for path, (shape, _, _) in leaf_specs(model).items():
        if path == "embed":
            continue
        size = 1
        for dim in shape:
            size *= dim
        n += size
    return n - model["d_model"] * (padded_vocab(model) - model["vocab_size"])


def attention_flops(model: dict, batch: int, q_len: int, kv_len: int) -> int:
    hd = model["d_model"] // model["n_heads"]
    return 2 * 2 * model["n_layers"] * batch * q_len * kv_len \
        * model["n_heads"] * hd


def forward_flops(model: dict, batch: int, seq: int) -> int:
    """One forward over ``batch`` rows of ``seq`` tokens: 2·N per token for
    the N matmul parameters plus 4·S_q·S_kv·H·hd per layer and row for
    attention, scores and values, over the whole square as the program
    computes it.  A copy of the program's ``analysis/flops.py``."""
    return 2 * matmul_params(model) * batch * seq \
        + attention_flops(model, batch, seq, seq)


def _q8(x):
    """x held in float8 e4m3 with one scale for the tensor: 3 mantissa
    bits, the largest magnitude mapped to 240 (the top of an e4m3 format
    with infinities).  An explicit ``reduce_precision``, which the compiler
    may not drop as it may drop a pair of casts."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _lo(x, precision: str):
    """A tensor as the configuration's storage precision holds it: float32
    for the reference, float8 for the control."""
    return _q8(x) if precision == "fp8" else x


def _mm(a, b, precision: str):
    return _lo(jnp.matmul(_lo(a, precision), _lo(b, precision),
                          precision=HIGHEST), precision)


def _norm(model, x, w):
    if model.get("norm", "layernorm") == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + w["scale"])
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w["scale"] + w["bias"]


def _rope(x, positions, theta: float):
    """x (B, S, heads, hd); positions (B, S)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (B, S, half)
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _act(name, x):
    return {"relu": jax.nn.relu, "silu": jax.nn.silu,
            "gelu": jax.nn.gelu}[name](x)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _block(mkey, x, w, positions, precision):
    model = dict(mkey)
    B, S, d = x.shape
    H = model["n_heads"]
    kv = model.get("n_kv_heads") or H
    hd = d // H
    G = H // kv
    h = _lo(_norm(model, x, w["ln1"]), precision)
    a = w["attn"]
    q = _mm(h, a["wq"], precision)
    k = _mm(h, a["wk"], precision)
    v = _mm(h, a["wv"], precision)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    theta = model.get("rope_theta", 10000.0)
    q = _rope(q.reshape(B, S, H, hd), positions, theta)
    k = _rope(k.reshape(B, S, kv, hd), positions, theta)
    v = v.reshape(B, S, kv, hd)
    k = jnp.repeat(k, G, axis=2)                 # query head h reads kv h // G
    v = jnp.repeat(v, G, axis=2)
    q, k, v = _lo(q, precision), _lo(k, precision), _lo(v, precision)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    scores = jnp.where(causal, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    p = _lo(p, precision)
    o = _lo(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST), precision)
    x = _lo(x + _mm(o.reshape(B, S, H * hd), a["wo"], precision), precision)
    h = _lo(_norm(model, x, w["ln2"]), precision)
    m = w["mlp"]
    f = _lo(_act(model["activation"], _mm(h, m["w1"], precision)), precision)
    if "w3" in m:
        f = _lo(f * _mm(h, m["w3"], precision), precision)
    return _lo(x + _mm(f, m["w2"], precision), precision)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(mkey, x, ln_f, head, precision):
    """Final norm and the logits over the true vocabulary."""
    model = dict(mkey)
    return _mm(_lo(_norm(model, x, ln_f), precision),
               head[:, :model["vocab_size"]], precision)


@jax.jit
def _nll(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def model_key(model: dict) -> tuple:
    """The model's sizes as a hashable, static jit argument."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool))))


def _layer_weights(model, get, layer):
    out = {}
    norm_keys = ("scale",) if model.get("norm") == "rmsnorm" \
        else ("scale", "bias")
    for n in ("ln1", "ln2"):
        out[n] = {k: get(f"layers/{n}/{k}", layer) for k in norm_keys}
    attn = ["wq", "wk", "wv", "wo"] + (["bq", "bk", "bv"]
                                       if model.get("qkv_bias") else [])
    out["attn"] = {k: get(f"layers/attn/{k}", layer) for k in attn}
    mlp = ["w1", "w2"] + (["w3"] if model.get("gated_ffn") else [])
    out["mlp"] = {k: get(f"layers/mlp/{k}", layer) for k in mlp}
    return out


def final_hidden(model: dict, get, get_rows, tokens, positions=None,
                 precision: str = "f32"):
    """Hidden state after the last block, (B, S, d) float32."""
    mkey = model_key(model)
    tokens = jnp.asarray(tokens)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = get_rows(tokens) * jnp.float32(model["d_model"]) ** 0.5
    if precision == "fp8":
        x = _q8(x)
    for layer in range(model["n_layers"]):
        x = _block(mkey, x, _layer_weights(model, get, layer), positions,
                   precision)
    return x


def logits(model: dict, get, get_rows, tokens, positions=None,
           precision: str = "f32"):
    """(B, S, vocab_size) float32 logits."""
    x = final_hidden(model, get, get_rows, tokens, positions, precision)
    norm_keys = ("scale",) if model.get("norm") == "rmsnorm" \
        else ("scale", "bias")
    ln_f = {k: get(f"ln_f/{k}", None) for k in norm_keys}
    return _head(model_key(model), x, ln_f, get("head", None), precision)


def loss(model: dict, get, get_rows, tokens, labels, precision: str = "f32",
         rows_per_block: int = 4) -> float:
    """Mean next-token cross entropy over every position, computed in blocks
    of batch rows so the float32 logits stay small."""
    x = final_hidden(model, get, get_rows, tokens, None, precision)
    norm_keys = ("scale",) if model.get("norm") == "rmsnorm" \
        else ("scale", "bias")
    ln_f = {k: get(f"ln_f/{k}", None) for k in norm_keys}
    head = get("head", None)
    mkey = model_key(model)
    total, n = jnp.float32(0.0), 0
    labels = jnp.asarray(labels)
    for r in range(0, x.shape[0], rows_per_block):
        lg = _head(mkey, x[r:r + rows_per_block], ln_f, head, precision)
        nll = _nll(lg, labels[r:r + rows_per_block])
        total = total + jnp.sum(nll)
        n += nll.size
    return float(total) / n
