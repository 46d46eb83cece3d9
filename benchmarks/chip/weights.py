"""Random weights for a configuration, made from the run's seed.

The benchmark owns the weights: it makes them on the device in one jitted
call, in the dtype the configuration serves, and hands them to the program.
The reference makes the same bits again from the same seed, leaf by leaf, so
it never reads a weight the program has held.

The layout is the model family's (``reference/<family>.py``'s
``leaf_specs``); each leaf is drawn from the seed and its path alone.  The
vocabulary is padded to a multiple of 128; the padded rows and columns are
drawn like the rest and never reach a loss or a token.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import manifest


def seeds(seed: int) -> dict:
    """Independent 32-bit streams from one ``--seed`` of any size."""
    w = np.random.SeedSequence(int(seed)).generate_state(3, dtype=np.uint32)
    return {"weights": int(w[0]), "data": int(w[1]),
            "zo": int(w[2] & 0x7FFFFFFF)}


def padded_vocab(model: dict) -> int:
    return -(-model["vocab_size"] // 128) * 128


def leaf_specs(model: dict) -> dict:
    """``path -> (shape, kind, std)`` of every leaf, as the model's family
    module lays its tree out.  ``kind`` is ``normal`` (std given), ``ones``
    or ``zeros``."""
    return manifest.family(model["family"]).leaf_specs(model)


def _leaf(key, path: str, shape, kind: str, std: float, dtype):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = v
    return tree


def shapes(model: dict) -> dict:
    """The tree :func:`make` returns, as shapes and dtypes."""
    dtype = jnp.dtype(model["dtype"])
    return _nest({p: jax.ShapeDtypeStruct(spec[0], dtype)
                  for p, spec in leaf_specs(model).items()})


def make(model: dict, weights_seed: int) -> dict:
    """All leaves, on the default device, in one jitted call."""
    specs = leaf_specs(model)
    dtype = jnp.dtype(model["dtype"])

    @jax.jit
    def build(key):
        return _nest({p: _leaf(key, p, *spec, dtype)
                      for p, spec in specs.items()})
    return build(jax.random.PRNGKey(weights_seed))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _one(key, path, shape, kind, std, dtype):
    return _leaf(key, path, shape, kind, std, dtype)


def leaf(model: dict, weights_seed: int, path: str) -> jnp.ndarray:
    """One leaf, bit for bit as :func:`make` draws it."""
    shape, kind, std = leaf_specs(model)[path]
    return _one(jax.random.PRNGKey(weights_seed), path, shape, kind, std,
                jnp.dtype(model["dtype"]))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _gap(x, key, path, shape, kind, std, dtype):
    d = (x.astype(jnp.float32)
         - _leaf(key, path, shape, kind, std, dtype).astype(jnp.float32))
    return jnp.sqrt(jnp.sum(d * d))


def gap_to_seed(x, model: dict, weights_seed: int, path: str):
    """‖x − θ_0‖ in float32 for leaf ``path``, θ_0 drawn from the seed inside
    the same program: the compiler fuses the draw into the reduction, so θ_0
    is never held whole on the device beside ``x``."""
    shape, kind, std = leaf_specs(model)[path]
    return _gap(x, jax.random.PRNGKey(weights_seed), path, shape, kind, std,
                jnp.dtype(model["dtype"]))


def flat_paths(tree: dict) -> list:
    """Leaf paths in the order ``jax.tree_util`` flattens the tree: the
    order that numbers the leaves' z streams."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(k.key for k in path))
    return out
