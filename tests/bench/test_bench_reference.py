"""The benchmark's plain float32 reference against the program, on the CPU
at a small size, for every model family under ``benchmarks/chip/reference``
and each of its ``CPU_CASES``: the teacher-forced forward and loss through
the registry's ``Bundle``, and prefill through the KV cache followed by
decoding one token at a time.  Also the reference's copy of the z
generator against the program's Pallas kernel (interpret mode): the same
stream to within the last bits of float32 (the kernel pins each rounding
stage; the copy lets the compiler fuse them)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import manifest
from benchmarks.chip import weights as wgen
from benchmarks.chip.reference import dense, zgen

CASES = [pytest.param(manifest.family(name), model, id=case)
         for name in manifest.families()
         for case, model in manifest.family(name).CPU_CASES.items()]
TOL = 2e-4     # float32 on both sides; only the order of sums differs


def _program(model):
    from repro.models import ModelConfig, bundle
    return bundle(ModelConfig(**model))


def _plain_source(params):
    flat = {"/".join(k.key for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}

    def get(path, layer):
        x = flat[path].astype(jnp.float32)
        return x if layer is None else x[layer]

    def get_rows(ids):
        return flat["embed"][jnp.asarray(ids)].astype(jnp.float32)
    return get, get_rows


def _tokens(model, seed, shape):
    return np.random.default_rng(seed).integers(0, model["vocab_size"], shape,
                                                dtype=np.int32)


@pytest.mark.parametrize("family,model", CASES)
def test_reference_forward_matches_program(family, model):
    """The logits and the loss that the program's training step evaluates
    against the family's reference, on the same weights."""
    b = _program(model)
    params = wgen.make(model, 7)
    toks = _tokens(model, 1, (2, 24))
    labels = _tokens(model, 3, (2, 24))
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    prog = b.train_logits_fn()(params, batch)
    ref = family.logits(model, *_plain_source(params), toks)
    V = model["vocab_size"]
    np.testing.assert_allclose(np.asarray(prog[..., :V]), np.asarray(ref),
                               atol=TOL, rtol=TOL)
    loss = family.loss(model, *_plain_source(params), toks, labels)
    np.testing.assert_allclose(float(b.loss_fn()(params, batch)), loss,
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("family,model", CASES)
def test_reference_matches_prefill_then_decode(family, model):
    b = _program(model)
    params = wgen.make(model, 8)
    P, T = 10, 6
    toks = _tokens(model, 2, (2, P + T))
    last, cache = b.prefill_fn()(params, {"tokens": jnp.asarray(toks[:, :P])})
    decode = b.decode_fn()
    got = [np.asarray(last[:, 0])]
    for t in range(P, P + T - 1):
        lg, cache = decode(params, {"token": jnp.asarray(toks[:, t:t + 1]),
                                    "cache": cache, "cache_pos": jnp.int32(t)})
        got.append(np.asarray(lg[:, 0]))
    ref = np.asarray(family.logits(model, *_plain_source(params), toks))
    V = model["vocab_size"]
    for i, g in enumerate(got):
        np.testing.assert_allclose(g[:, :V], ref[:, P - 1 + i], atol=TOL,
                                   rtol=TOL)


def test_reference_z_is_the_kernels_z():
    """The copy of the generator draws what the program's Pallas kernel
    adds: perturb a zero leaf by b = 1 and read z back.  |z| < 6, so two
    float32 ulps are under 1e-6."""
    from repro.perturb import StreamRef
    from repro.perturb.pallas import PallasBackend
    key = jax.random.fold_in(jax.random.PRNGKey(12345), 3)
    params = {"a": jnp.zeros((3, 70), jnp.float32),
              "b": jnp.zeros((2, 5, 33), jnp.float32)}
    got = PallasBackend(interpret=True).perturb(params, StreamRef(key), 1.0,
                                                "gaussian")
    seed_t = zgen.step_seed(12345, 3)
    for i, name in enumerate(["a", "b"]):
        want = zgen.leaf_z(params[name].shape, zgen.leaf_seed(seed_t, i))
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want),
                                   rtol=0, atol=1e-6)
    # a layer of a stacked leaf starts at its flat offset
    tail = zgen.leaf_z((5, 33), zgen.leaf_seed(seed_t, 1), 5 * 33)
    np.testing.assert_allclose(np.asarray(got["b"][1]), np.asarray(tail),
                               rtol=0, atol=1e-6)


def test_weights_one_leaf_is_the_same_as_all():
    model = dict(dense.CPU_CASES["qwen2-like"], dtype="bfloat16")
    params = wgen.make(model, 99)
    flat = {"/".join(k.key for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for path in ("embed", "layers/attn/bk", "layers/mlp/w3"):
        np.testing.assert_array_equal(np.asarray(flat[path]),
                                      np.asarray(wgen.leaf(model, 99, path)))
