"""A run's ``correct`` comes out false when the timed path is broken.

Each test drives a whole run of the training cell on the CPU, at a size a
test can hold, with the harness's look for a chip skipped and a fault
planted under the step the window drives, and reads the result line.  Last,
the control: the reference put in the program's place, computed in float8,
fails the numbers.

At this size a step sees 64 tokens, not 2,048, and the smallest leaves hold
64 elements, not 40,960, so sound runs read up to ten times what they read
at the cell's size on ``loss_gap`` and ``change_gap`` (2.7e-4 and 5.8e-3 on
four seeds, against 2.1e-5 and 5.4e-4 over twelve on the chip).  The tests
hold those two to ten times the cell's limits; every fault reads far over
them.  ``g_gap`` is a gap over the gradient's own scale and reads 0.05-0.34
here, so it keeps the cell's limit; a flipped sign reads 2.6-3.3.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import manifest, run
from benchmarks.chip.kinds import zo_train
from benchmarks.chip.reference import mezo

CELL = "opt-13b-8l.zo-short"
MODEL = {"name": "opt-tiny", "family": "dense", "n_layers": 2, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 4, "d_ff": 256, "vocab_size": 500,
         "activation": "relu", "gated_ffn": False, "norm": "layernorm",
         "qkv_bias": False, "rope_theta": 10000.0, "max_seq": 128,
         "dtype": "bfloat16"}
SEED = 2**31 + 1234
SCALE = {"loss_gap": 10, "change_gap": 10, "g_gap": 1}


def _limits():
    return {k: SCALE[k] * v for k, v in manifest.limits(CELL).items()}


def _traffic():
    tr = dict(manifest.traffic(manifest.cell(manifest.load(), CELL)["traffic"]))
    tr.update(batch=4, seq=16)
    return tr


def _frozen(step):
    """The step computes and returns its state, but θ comes back as it
    went in."""
    def f(params, state, batch):
        keep = jax.tree_util.tree_map(jnp.copy, params)
        _, state, m = step(params, state, batch)
        return keep, state, m
    return f


def _half_batch(step):
    """Half of the rows left out, the mean taken over the rest: the other
    half is a copy of the first."""
    def f(params, state, batch):
        half = batch["tokens"].shape[0] // 2
        dup = {k: jnp.concatenate([v[:half], v[:half]]) for k, v in
               batch.items()}
        return step(params, state, dup)
    return f


def _token(step):
    """A token altered where the feed produces it."""
    def f(params, state, batch):
        bad = dict(batch, tokens=batch["tokens"].at[0, 0].add(1))
        return step(params, state, bad)
    return f


def _neg_grad(step):
    """The projected gradient's sign flipped where the step produces it:
    in the optimizer's state and in what the ledger records."""
    def f(params, state, batch):
        params, state, m = step(params, state, batch)
        g = -state.last_projected_grad
        return (params, state._replace(last_projected_grad=g),
                dict(m, projected_grad=g))
    return f


def _run(capsys, fault=None) -> dict:
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "0.3", "--trace", "0"], allow_cpu=True, fault=fault,
                  overrides={"config": {"model": MODEL},
                             "traffic": _traffic(), "limits": _limits()})
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    out = _run(capsys)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_frozen, _half_batch, _token, _neg_grad],
                         ids=["state-unchanged", "half-batch", "token-altered",
                              "gradient-negated"])
def test_fault_makes_the_run_incorrect(capsys, fault):
    out = _run(capsys, fault)
    assert not out["correct"], out["checks"]


def test_control_fails_the_limits():
    """The reference at float8 in the program's place, against the float32
    reference: at least one number over its limit."""
    tr = _traffic()
    seeds = run.wgen.seeds(SEED)
    feed = zo_train.Feed(seeds["data"], tr["batch"], tr["seq"],
                         MODEL["vocab_size"])
    batches = [feed.host(t) for t in range(tr["checked_steps"])]
    args = (MODEL, seeds["weights"], seeds["zo"], batches, tr["lr"], tr["eps"])
    ref = mezo.steps(*args)
    ctl = mezo.steps(*args, precision="fp8")
    numbers = zo_train.compare(zo_train.as_program(ctl), ref,
                               zo_train.sizes(MODEL))
    limits = _limits()
    assert any(numbers[k] > limits[k] for k in limits), (numbers, limits)


def test_only_a_traced_run_hands_readers_op_names_and_traffic():
    """The compiled step's op names (for the readers of program spans) and
    the traffic reach the readers in a traced run, taken after the window;
    an untraced run does not read the compiled text at all."""
    from benchmarks.chip import phases
    cell = manifest.cell(manifest.load(), CELL)
    for trace in (False, True):
        ctx = run.Ctx(cell, {"model": MODEL}, _traffic(), SEED, 0.3, trace,
                      jax.devices()[:1], span_names=zo_train.SPAN_NAMES)
        try:
            out = zo_train.run(ctx)
        finally:
            jax.monitoring.unregister_event_duration_listener(ctx._on_event)
        lc = out["layer_ctx"]
        if not trace:
            assert "op_names" not in lc and "traffic" not in lc
            assert "op_names_s" not in out["info"]
            continue
        assert lc["traffic"] == _traffic()
        assert out["info"]["op_names_s"] >= 0
        named = {phases.phase_of(v) for v in lc["op_names"].values()}
        assert set(phases.PHASES) <= named
