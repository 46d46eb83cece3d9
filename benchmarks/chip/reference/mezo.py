"""Plain MeZO (the paper's Algorithm 1) over the float32 reference loss of
the model's family (``reference/<family>.py``).

The parameters are stored in the dtype the configuration states and are
perturbed in place, as Algorithm 1 does: each of the three writes of a step
(θ+εz, θ−2εz, θ+εz−η·g·z) rounds to that dtype.  The losses are float32
forwards of the stored values.  z comes from :mod:`zgen`, the weights from
:mod:`weights`: nothing here is taken from the program under test.

``fault`` plants a fault in the reference put in the program's place (for
the calibration of the limits and for the tests):

* ``"half_batch"`` — both losses over the first half of the rows only;
* ``"frozen"``     — the step computes its losses and returns θ unchanged;
* ``"loss_token"`` — the reported loss of every step is that of a batch
  whose first label is altered;
* ``"neg_grad"``   — g with its sign flipped, (L− − L+)/2ε, reported and
  applied: θ moves up the gradient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import manifest
from benchmarks.chip import weights as wgen
from benchmarks.chip.reference import zgen


def _store(x, dtype):
    """Round a float32 value to ``dtype`` and keep it in float32.  An
    explicit ``reduce_precision``: the compiler may drop a pair of casts
    (excess precision), never this."""
    if dtype == jnp.float32:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _views(x, z, eps):
    """θ+εz and θ−εz as Algorithm 1 stores them: each write rounded."""
    plus = _store(x.astype(jnp.float32) + eps * z, x.dtype)
    minus = _store(plus + (-2.0 * eps) * z, x.dtype)
    return plus, minus


@functools.partial(jax.jit, static_argnums=(3, 4))
def _perturbed(x, seed, eps, mode: str, offset: int):
    """float32 view of a block of θ (starting at flat element ``offset`` of
    its leaf) as the ``+`` or ``-`` forward sees it."""
    plus, minus = _views(x, zgen.leaf_z(x.shape, seed, offset), eps)
    return plus if mode == "+" else minus


@functools.partial(jax.jit, static_argnums=(4,))
def _perturbed_rows(table, ids, seed, eps, mode: str):
    """The ``+``/``-`` view of the embedding rows ``ids``: z at flat
    positions ``id · d + column``."""
    d = table.shape[1]
    idx = (ids.astype(jnp.uint32)[..., None] * jnp.uint32(d)
           + jnp.arange(d, dtype=jnp.uint32))
    plus, minus = _views(table[ids], zgen.gaussian(idx, seed), eps)
    return plus if mode == "+" else minus


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_update(x, seed, eps, coeff):
    """θ ← dtype(θ− + (ε − η·g)·z), with θ− recomputed from θ."""
    z = zgen.leaf_z(x.shape, seed, 0)
    _, minus = _views(x, z, eps)
    return (minus + (eps - coeff) * z).astype(x.dtype)


def _source(theta, seeds, eps, mode):
    def get(path, layer):
        x = theta[path]
        if layer is None:
            return _perturbed(x, seeds[path], eps, mode, 0)
        per = int(np.prod(x.shape[1:]))
        return _perturbed(x[layer], seeds[path], eps, mode, layer * per)

    def get_rows(ids):
        return _perturbed_rows(theta["embed"], jnp.asarray(ids),
                               seeds["embed"], eps, mode)
    return get, get_rows


def steps(model: dict, weights_seed: int, zo_seed: int, batches: list,
          lr: float, eps: float, precision: str = "f32",
          fault: str | None = None) -> dict:
    """Run ``len(batches)`` MeZO steps from the seed's weights.

    Returns per-step ``losses`` (mean of the two), per-step projected
    gradients ``g``, and ``change``: per leaf, ‖θ_T − θ_0‖ in float32."""
    loss = manifest.family(model["family"]).loss
    specs = wgen.leaf_specs(model)
    tree = wgen._nest({p: 0 for p in specs})
    paths = wgen.flat_paths(tree)
    theta = {p: wgen.leaf(model, weights_seed, p) for p in paths}
    eps32, losses, gs = jnp.float32(eps), [], []
    for t, batch in enumerate(batches):
        tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        if fault == "half_batch":
            tokens, labels = tokens[: len(tokens) // 2], labels[: len(labels) // 2]
        st = zgen.step_seed(zo_seed, t)
        seeds = {p: zgen.leaf_seed(st, i) for i, p in enumerate(paths)}
        lp = loss(model, *_source(theta, seeds, eps32, "+"), tokens, labels,
                  precision)
        lm = loss(model, *_source(theta, seeds, eps32, "-"), tokens, labels,
                  precision)
        g = (np.float32(lp) - np.float32(lm)) / np.float32(2.0 * eps)
        if fault == "neg_grad":
            g = -g
        reported = 0.5 * (lp + lm)
        if fault == "loss_token":
            bad = labels.copy()
            bad[0, 0] = (bad[0, 0] + 1) % model["vocab_size"]
            reported = 0.5 * (
                loss(model, *_source(theta, seeds, eps32, "+"), tokens, bad,
                     precision)
                + loss(model, *_source(theta, seeds, eps32, "-"), tokens, bad,
                       precision))
        losses.append(float(reported))
        gs.append(float(g))
        if fault != "frozen":
            coeff = jnp.float32(lr) * jnp.float32(g)
            for p in paths:
                theta[p] = _restore_update(theta[p], seeds[p], eps32, coeff)
    change = {p: float(wgen.gap_to_seed(theta[p], model, weights_seed, p))
              for p in paths}
    return {"losses": losses, "g": gs, "change": change}
