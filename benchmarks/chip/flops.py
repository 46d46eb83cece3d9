"""Operations and bytes the work needs, from shapes alone.

A copy of the program's model-FLOP accounting (its ``analysis/flops.py``),
kept with the benchmark so that no change to the program moves the
yardstick: a forward costs 2·N per token for the N matmul parameters (the
embedding gather is not a matmul, the output head is; the head counts the
true vocabulary only) plus 4·S_q·S_kv·H·hd per layer and row for attention,
scores and values, over the whole square as the program computes it.
"""
from __future__ import annotations

from benchmarks.chip import weights as wgen


def matmul_params(model: dict) -> int:
    n = 0
    for path, (shape, _, _) in wgen.leaf_specs(model).items():
        if path == "embed":
            continue
        size = 1
        for dim in shape:
            size *= dim
        n += size
    return n - model["d_model"] * (wgen.padded_vocab(model) - model["vocab_size"])


def attention_flops(model: dict, batch: int, q_len: int, kv_len: int) -> int:
    hd = model["d_model"] // model["n_heads"]
    return 2 * 2 * model["n_layers"] * batch * q_len * kv_len \
        * model["n_heads"] * hd


def forward_flops(model: dict, batch: int, seq: int) -> int:
    """One forward over ``batch`` rows of ``seq`` tokens."""
    return 2 * matmul_params(model) * batch * seq \
        + attention_flops(model, batch, seq, seq)


def param_bytes(model: dict) -> int:
    """Bytes of every parameter in the served dtype."""
    item = 2 if model["dtype"] in ("bfloat16", "float16") else 4
    n = 0
    for shape, _, _ in wgen.leaf_specs(model).values():
        size = 1
        for dim in shape:
            size *= dim
        n += size
    return n * item


def zo_kernel_bytes_per_step(model: dict) -> int:
    """HBM bytes the perturbation kernels of one SPSA step need: three
    passes over θ (+εz, −2εz, restore and update), each reading and writing
    every parameter once."""
    return 3 * 2 * param_bytes(model)
