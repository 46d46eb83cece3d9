"""Operations and bytes the work needs, from shapes alone, kept with the
benchmark so that no change to the program moves the yardstick.  A
forward's model FLOPs are the family's (``reference/<family>.py``); the
bytes below hold for any family: they count every leaf.
"""
from __future__ import annotations

from benchmarks.chip import manifest
from benchmarks.chip import weights as wgen


def forward_flops(model: dict, batch: int, seq: int) -> int:
    """Model FLOPs of one forward over ``batch`` rows of ``seq`` tokens, as
    the model's family module counts them."""
    return manifest.family(model["family"]).forward_flops(model, batch, seq)


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
