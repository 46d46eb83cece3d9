"""Chip smoke: the repo's main path, once, on a TPU.

qwen2-0.5b at its full width (24 layers, d_model 896, 14 heads, GQA kv=2,
d_ff 4864, vocab 151936, bf16), random weights from seed 0, driven through
the entry points a user calls:

  device  platform, device kind, device count, JAX version; exits non-zero
          unless the platform is ``tpu``.
  train   ``repro.launch.train.main`` for a few steps on the ``xla``
          backend, on the compiled ``pallas`` backend, and under ``fzoo(8)``
          on ``pallas``: finite losses, one ledger entry per step, the
          Mosaic kernel (``tpu_custom_call``) in the compiled pallas step,
          and the pallas ledger replayed onto the initial parameters
          (``StepProgram.replay``) against the live parameters, with the
          initial parameters and a negated-grad replay as controls that must
          lie farther away.
  serve   ``repro.launch.serve.main`` with the pallas ledger: 4 requests x 8
          new tokens on the paged KV path, once with the XLA gather and once
          with the pallas gather; the tokens must be identical.

``--chips 4`` runs only the seed-parallel phase: ``seed_parallel(4)`` on a
``data=4`` mesh (one seed group per chip, loss scalars the only cross-chip
traffic) against its own ledger replayed on one chip, and against
``seed_parallel(4)`` on one chip, same steps, same batch: step 0 closely,
all steps within the bound that their differing grads and roundings allow.

Every line before the last is a smoke check, not a benchmark number.  The
last line of stdout is ``{"ok": true, "device": {...}}``; any failed check
raises, and the script then exits non-zero without that line.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # one host with four chips
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402
import numpy as np                                              # noqa: E402

from repro import exec as zexec                                 # noqa: E402
from repro.core import TrajectoryLedger                         # noqa: E402
from repro.kernels import interpret_default                     # noqa: E402
from repro.launch import serve as serve_launcher                # noqa: E402
from repro.launch import train as train_launcher                # noqa: E402
from repro.launch.cache import enable_compile_cache             # noqa: E402
from repro.train.loop import train                              # noqa: E402

ARCH = ["--arch", "qwen2-0.5b"]
STEPS = 3
OUT = os.path.join(HERE, ".smoke_out")
# Two runs of the same steps may differ per element by their roundings to
# the parameter dtype — half a unit each — plus, where their recorded g
# differ, Z_MAX · Σ |Δ update coefficient|.  Z_MAX bounds |z| of the counter
# Box–Muller, and a unit is finfo(dtype).eps · (|θ| + Z_MAX·(ε + Σ |update
# coefficient|)): no value rounded on the way to the final θ was larger.  An SPSA
# step rounds θ three times (+ε, −2ε, restore-and-update); a ledger replay
# step once; a seed_parallel(n) step 3 times per group it evaluates in turn
# and n times in its update chain.
Z_MAX = 6.0
EPS = 1e-3
# That bound holds for any two runs that agree, but in bf16 it can be too
# wide to tell them from runs that do not.  So the runs whose parameters are
# compared take a learning rate at which three steps move θ by many ulps,
# and each comparison has controls — θ left at its initial value, a ledger
# replayed with its projected grads negated — that must lie MARGIN times
# farther from the live θ, in L2, than the reference does.
LR = 1e-3
MARGIN = 4.0


def check(ok: bool, detail) -> None:
    """A smoke check: raises (so the script exits non-zero) when it fails."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {detail}")


def say(phase: str, **fields) -> None:
    print(json.dumps({"smoke": phase, **fields}), flush=True)


def reach(ledger: TrajectoryLedger, n_groups: int = 1) -> float:
    """Z_MAX · Σ |update coefficient| of the ledger's steps: how far from
    its initial value any element of θ can have been on the way."""
    g = np.abs(np.asarray(ledger.grads, np.float64)).reshape(len(ledger), -1)
    lrs = np.asarray(ledger.lrs, np.float64).reshape(-1, 1)
    return Z_MAX * float(np.sum(lrs / n_groups * g))


def tree_gap(live, ref, roundings: int, reach: float,
             slack: float = 0.0) -> dict:
    """Elementwise distance between two parameter trees beyond ``slack``,
    in units of the dtype's relative precision at |ref| + Z_MAX·ε +
    ``reach``; the bound is half a unit per rounding of either run.  Also
    the L2 distance, for the controls."""
    max_abs, max_units, sq, n_diff, n = 0.0, 0.0, 0.0, 0, 0
    for a, b in zip(jax.tree_util.tree_leaves(live),
                    jax.tree_util.tree_leaves(ref)):
        if not jnp.issubdtype(b.dtype, jnp.floating):
            continue
        unit = float(jnp.finfo(b.dtype).eps)
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        d = jnp.abs(a32 - b32)
        max_abs = max(max_abs, float(jnp.max(d)))
        sq += float(jnp.sum(d * d))
        max_units = max(max_units, float(jnp.max(
            (d - slack) / (unit * (jnp.abs(b32) + Z_MAX * EPS + reach)))))
        n_diff += int(jnp.sum(a != b))
        n += b.size
    return {"max_abs": max_abs, "slack": slack, "reach": reach,
            "max_units": max_units, "l2": math.sqrt(sq),
            "frac_differ": n_diff / n,
            "bound_units": roundings / 2}


def check_separates(what: str, same: dict, controls: dict) -> None:
    """``same`` is within its rounding bound, and every control at least
    MARGIN times farther in L2: the comparison tells the runs apart."""
    say(what, **same, controls={k: {"max_units": c["max_units"],
                                    "l2": c["l2"]}
                                for k, c in controls.items()}, margin=MARGIN)
    check(same["max_units"] <= same["bound_units"], (what, same))
    for name, c in controls.items():
        check(c["l2"] >= MARGIN * same["l2"],
              (what, f"control {name!r} is not {MARGIN}x farther", c))


def negated(ledger: TrajectoryLedger) -> TrajectoryLedger:
    """The ledger with every projected grad negated."""
    return dataclasses.replace(
        ledger, grads=[(-np.asarray(g)).tolist() for g in ledger.grads])


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train_argv(tag: str, *extra: str, arch=ARCH) -> list:
    ckpt = os.path.join(OUT, tag)
    shutil.rmtree(ckpt, ignore_errors=True)
    return [*arch, "--steps", str(STEPS), "--batch", "8", "--seq", "64",
            "--eps", str(EPS), "--ckpt-dir", ckpt,
            "--ckpt-interval", str(10 * STEPS), *extra]


def check_run(tag: str, run, t0: float) -> None:
    losses = [loss for _, loss in run.result.losses]
    check(len(losses) == STEPS and all(map(math.isfinite, losses)), losses)
    check(run.ledger is not None and len(run.ledger) == STEPS, run.ledger)
    say(f"train {tag}", losses=losses, ledger_entries=len(run.ledger),
        seconds_incl_compile=time.time() - t0)


def step_hlo(run) -> str:
    """Compiled HLO of the launcher's step at the run's shapes."""
    b, opt = run.bundle, run.optimizer
    program = zexec.as_step_program(opt)
    params = jax.eval_shape(lambda: b.init(jax.random.PRNGKey(0)))
    state = program.init(run.result.params, seed=0)
    batch = run.pipeline.batch(0)
    step = jax.jit(program.step_fn(b.loss_fn()), donate_argnums=(0,))
    return step.lower(params, state, batch).compile().as_text()


def phase_train(arch=ARCH) -> str:
    t0 = time.time()
    check_run("xla", train_launcher.main(
        train_argv("train-xla", "--backend", "xla", arch=arch)), t0)

    t0 = time.time()
    run = train_launcher.main(
        train_argv("train-pallas", "--backend", "pallas", "--lr", str(LR),
                   arch=arch))
    check_run("pallas", run, t0)
    backend = run.optimizer.backend
    check(backend.interpret is (jax.default_backend() == "cpu"), backend)
    if jax.default_backend() == "tpu":
        check("tpu_custom_call" in step_hlo(run), "no Mosaic kernel in step")
    params0 = run.bundle.init(jax.random.PRNGKey(0))
    program = zexec.as_step_program(run.optimizer)
    gap = functools.partial(tree_gap, run.result.params,
                            roundings=STEPS * (3 + 1), reach=reach(run.ledger))
    same = gap(program.replay(params0, run.ledger))
    controls = {"initial params": gap(params0),
                "negated grads": gap(program.replay(params0,
                                                    negated(run.ledger)))}
    check_separates("replay pallas ledger vs live", same, controls)
    ledger = os.path.join(OUT, "train-pallas", "ledger.mzl")
    del run, params0

    t0 = time.time()
    run = train_launcher.main(train_argv(
        "train-fzoo", "--backend", "pallas", "--estimator", "fzoo",
        "--batch-seeds", "8", arch=arch))
    check_run("pallas fzoo(8)", run, t0)
    check(run.optimizer.backend.interpret is (jax.default_backend() == "cpu"),
          "fzoo pallas backend interpret mode")
    del run
    return ledger


def phase_serve(ledger: str, arch=ARCH) -> None:
    tokens = {}
    for gather in ("xla", "pallas"):
        t0 = time.time()
        out = serve_launcher.main([*arch, "--ledger", ledger,
                                   "--requests", "4", "--new-tokens", "8",
                                   "--gather", gather])
        check(out.engine.paged, "qwen2-0.5b must serve on the paged path")
        check(out.engine._gather_pallas is (gather == "pallas"),
              f"engine gather is not {gather}")
        tokens[gather] = [r.out_ids for r in out.requests]
        check(all(len(t) == 8 for t in tokens[gather]), tokens[gather])
        say(f"serve {gather} gather", tokens=tokens[gather],
            seconds_incl_compile=time.time() - t0)
    check(tokens["xla"] == tokens["pallas"], tokens)


# one HLO instruction per line: "%name = <shape or tuple> <collective>("
_COLLECTIVE = re.compile(
    r"=\s*(.+?)\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?:-start)?\(")


def _largest_array(shape_text: str) -> int:
    """Elements of the largest array in an HLO shape (tuples included)."""
    return max(math.prod(int(d) for d in dims.split(",") if d)
               for dims in re.findall(r"\[([\d,]*)\]", shape_text))


def phase_seed_parallel(n: int = 4, arch=ARCH) -> None:
    devices = jax.devices()
    check(len(devices) >= n, f"needs {n} devices, found {len(devices)}")
    t0 = time.time()
    mesh_run = train_launcher.main(train_argv(
        "sp-mesh", "--backend", "pallas", "--exec-plan", "seed_parallel",
        "--n-groups", str(n), "--lr", str(LR), arch=arch))
    check_run(f"seed_parallel({n}) on a data={n} mesh", mesh_run, t0)
    program = mesh_run.optimizer
    mesh = program.plan.mesh
    check(mesh is not None and mesh.devices.size == n, mesh)

    # each device holds its own batch slice
    batch = mesh_run.pipeline.batch(0)
    state = program.init(mesh_run.result.params, seed=0)
    pshard, sshard, bshard = program.shardings(mesh_run.result.params,
                                               batch, state)
    toks = jax.device_put(batch["tokens"], bshard["tokens"])
    shards = {s.device: s.data.shape for s in toks.addressable_shards}
    per = batch["tokens"].shape[0] // n
    check(set(shards) == set(mesh.devices.flat), shards)
    check(all(s[0] == per for s in shards.values()), shards)

    # the step's only cross-device traffic is the per-group scalars
    b = mesh_run.bundle
    params = jax.eval_shape(lambda: b.init(jax.random.PRNGKey(0)))
    hlo = jax.jit(program.step_fn(b.loss_fn()),
                  in_shardings=(pshard, sshard, bshard),
                  donate_argnums=(0,)).lower(params, state, batch) \
        .compile().as_text()
    # XLA:TPU may combine the per-group all-gathers into one all-reduce
    colls = [(m.group(2), _largest_array(m.group(1)))
             for m in _COLLECTIVE.finditer(hlo)]
    check(colls, "no cross-device collective in the mesh step")
    check(all(e <= n * program.batch_seeds for _, e in colls), colls)
    say("seed_parallel collectives", collectives=colls,
        batch_shard_rows={str(d): s[0] for d, s in shards.items()},
        peak_bytes_per_device=[peak_bytes(d) for d in mesh.devices.flat])

    # every device applied all n groups' updates: the mesh θ against its
    # ledger replayed on one device (one group per device rounds 3 + n times
    # a step, the replay n times)
    t0 = time.time()
    one_device = zexec.StepProgram(program.opt, zexec.seed_parallel(n))
    params0 = b.init(jax.random.PRNGKey(0))
    live = jax.device_get(mesh_run.result.params)
    gap = functools.partial(tree_gap, live, roundings=STEPS * (3 + 2 * n),
                            reach=reach(mesh_run.ledger, n))
    same = gap(one_device.replay(params0, mesh_run.ledger))
    controls = {"initial params": gap(params0),
                "negated grads": gap(one_device.replay(
                    params0, negated(mesh_run.ledger)))}
    check_separates(f"seed_parallel({n}) mesh ledger replay vs mesh", same,
                    controls)

    # the same program on one device, from the same θ0 and batches
    ref_ledger = TrajectoryLedger(base_seed=0, grad_dtype="float32")
    ref = train(b.loss_fn(), params0, one_device, mesh_run.pipeline,
                total_steps=STEPS, ledger=ref_ledger, seed=0, log_every=1)
    ref_losses = [loss for _, loss in ref.losses]
    mesh_losses = [loss for _, loss in mesh_run.result.losses]
    g_mesh = np.asarray(mesh_run.ledger.grads, np.float64).reshape(STEPS, n)
    g_ref = np.asarray(ref_ledger.grads, np.float64).reshape(STEPS, n)
    # per step, Σ|Δg| with the one-device groups rotated by k = 0 … n-1
    g_miss = [[float(np.sum(np.abs(g_mesh[t] - np.roll(g_ref[t], k))))
               for k in range(n)] for t in range(STEPS)]
    loss_gap = [abs(a - c) / abs(c) for a, c in zip(mesh_losses, ref_losses)]
    say(f"seed_parallel({n}) mesh vs one device", mesh_losses=mesh_losses,
        one_device_losses=ref_losses, loss_rel_gap=loss_gap,
        group_grad_gap_by_step_and_rotation=g_miss,
        seconds_incl_compile=time.time() - t0)
    # Step 0 starts both runs from the same θ: its losses agree to the
    # forward's bf16 noise, and device g evaluated group g on slice g — its
    # grads match the one-device run's in place, and under no rotation.
    # Later steps are not compared: a per-group g is a loss difference over
    # 2ε, so the forward's noise moves it by several units, and at this
    # learning rate those differences steer the two trajectories apart.
    check(loss_gap[0] <= 1e-3, (mesh_losses, ref_losses))
    check(all(g_miss[0][0] < m for m in g_miss[0][1:]), g_miss[0])
    # Over all steps the params still differ by no more than the roundings
    # (one group per device: 3 + n; n groups in turn: 3n + n) and what the
    # differing coefficients (η/n)·g can move.
    lrs = np.asarray(ref_ledger.lrs, np.float64).reshape(-1, 1)
    same = tree_gap(live, jax.device_get(ref.params),
                    roundings=STEPS * (3 + n + 4 * n),
                    reach=max(reach(ref_ledger, n), reach(mesh_run.ledger, n)),
                    slack=Z_MAX * float(np.sum(lrs / n
                                               * np.abs(g_mesh - g_ref))))
    say(f"seed_parallel({n}) params, mesh vs one device", **same)
    check(same["max_units"] <= same["bound_units"], same)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: device, train and serve phases; 4: only the "
                         "seed-parallel phase across the host's four chips")
    args = ap.parse_args(argv)
    enable_compile_cache()

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {d0.platform!r}, "
              f"{len(devices)} {d0.device_kind} device(s))", file=sys.stderr)
        return 1
    say("device", platform=d0.platform, kind=d0.device_kind,
        count=len(devices), jax=jax.__version__)
    check(interpret_default() is False, "kernels would interpret on the TPU")

    shutil.rmtree(OUT, ignore_errors=True)
    t0 = time.time()
    if args.chips == 4:
        phase_seed_parallel(4)
    else:
        ledger = phase_train()
        phase_serve(ledger)
    say("total", seconds=time.time() - t0, peak_bytes_device0=peak_bytes(d0))
    shutil.rmtree(OUT, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
