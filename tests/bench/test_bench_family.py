"""A model family is one module, ``benchmarks/chip/reference/<family>.py``,
found by the configuration's ``model["family"]``: the weights, the FLOP
count and the reference MeZO steps take the layout, the count and the loss
from it, and the dense family still counts what it counted."""
from __future__ import annotations

import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import flops, manifest
from benchmarks.chip import weights as wgen
from benchmarks.chip.reference import dense, mezo

TOY = {"name": "toy-tiny", "family": "toy", "n_layers": 2, "d_model": 16,
       "vocab_size": 40, "dtype": "bfloat16"}


def _toy_family(calls: list):
    """A family whose tree is an embedding, one stacked leaf and a head."""
    mod = types.ModuleType("benchmarks.chip.reference.toy")

    def leaf_specs(model):
        d, L, V = model["d_model"], model["n_layers"], wgen.padded_vocab(model)
        return {"embed": ((V, d), "normal", 0.02),
                "layers/w": ((L, d, d), "normal", d ** -0.5),
                "head": ((d, V), "normal", d ** -0.5)}

    def logits(model, get, get_rows, tokens):
        x = get_rows(jnp.asarray(tokens))
        for layer in range(model["n_layers"]):
            x = x + jnp.tanh(x @ get("layers/w", layer))
        return x @ get("head", None)[:, :model["vocab_size"]]

    def loss(model, get, get_rows, tokens, labels, precision="f32"):
        calls.append(precision)
        lg = logits(model, get, get_rows, tokens)
        gold = jnp.take_along_axis(lg, jnp.asarray(labels)[..., None], -1)
        return float(jnp.mean(jax.nn.logsumexp(lg, -1) - gold[..., 0]))

    mod.leaf_specs, mod.logits, mod.loss = leaf_specs, logits, loss
    mod.forward_flops = lambda model, batch, seq: 1000 * batch * seq
    mod.CPU_CASES = {}
    return mod


@pytest.fixture
def toy(monkeypatch):
    calls: list = []
    mod = _toy_family(calls)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod, calls


def test_a_new_family_is_one_module(toy):
    mod, calls = toy
    assert manifest.family("toy") is mod
    shapes = wgen.shapes(TOY)
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure({"embed": 0, "head": 0,
                                      "layers": {"w": 0}})
    assert shapes["layers"]["w"].shape == (2, 16, 16)
    assert shapes["embed"].shape == (128, 16)
    assert flops.forward_flops(TOY, 4, 8) == 1000 * 4 * 8
    assert flops.param_bytes(TOY) == 2 * (128 * 16 * 2 + 2 * 16 * 16)

    rng = np.random.default_rng(0)
    batches = [{"tokens": rng.integers(0, 40, (2, 5), dtype=np.int32),
                "labels": rng.integers(0, 40, (2, 5), dtype=np.int32)}
               for _ in range(2)]
    out = mezo.steps(TOY, 11, 12, batches, lr=1e-2, eps=1e-2)
    assert calls == ["f32"] * 4                  # two forwards a step
    assert sorted(out["change"]) == ["embed", "head", "layers/w"]
    assert all(v > 0 for v in out["change"].values())
    assert all(np.isfinite(out["losses"])) and len(out["g"]) == 2


def test_an_unknown_family_names_the_families_present():
    assert "dense" in manifest.families()
    assert "mezo" not in manifest.families()     # a module, not a family
    for name in ("moe-nowhere", "mezo", "../dense"):
        with pytest.raises(KeyError, match=r"has the families \[.*'dense'") as e:
            manifest.family(name)
        assert repr(name) in str(e.value)


def test_dense_counts_of_opt_13b_8l():
    """The counts the cells' metrics rest on, from shapes alone."""
    model = manifest.config("opt-13b-8l")["model"]
    assert manifest.family(model["family"]) is dense
    per_step = {t: 2 * flops.forward_flops(model, **{
        k: manifest.traffic(t)[k] for k in ("batch", "seq")})
        for t in ("zo-short", "zo-long")}
    assert per_step == {"zo-short": 22_811_728_936_960,
                        "zo-long": 92_277_707_898_880}
    assert flops.zo_kernel_bytes_per_step(model) == 36_382_433_280
    sizes = [int(np.prod(s[0])) for s in wgen.leaf_specs(model).values()]
    assert sum(sizes) == 3_031_869_440
    assert dense.matmul_params(model) == 2_774_149_120
