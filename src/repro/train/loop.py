"""Checkpointed training loop with a failure-injection hook.

Responsibilities:
  * jit + donate the optimizer step once;
  * pure step-indexed data (restart-exact);
  * full checkpoints every K steps + per-step ZO scalar ledger;
  * resume: newest full ckpt, then *ledger replay* of the tail — the
    replacement worker rejoins bitwise-identically without data access;
  * ``FailureInjector`` drives the chaos tests.

The loop is execution-engine-aware but optimizer-agnostic: ``optimizer`` is a
``repro.exec.StepProgram`` (any ``repro.zo`` composition lowered onto any
execution plan — local, seed_parallel, ...) or a bare ``repro.zo.Optimizer``
protocol conformer, which is wrapped onto the local plan.  That covers the ZO
compositions (``zo.mezo(...)``, ``zo.fzoo(...)``, the deprecated
``MeZO``/``MeZOAdam``/``MeZOVariant`` shims) and the backprop baselines
(``train.adam.Adam``) alike.  There is no optimizer-type dispatch here:
resume bookkeeping goes through the protocol's ``restore``, and ledger
recording/recovery is enabled purely by passing a ``ledger`` (which requires
an optimizer whose metrics expose ``projected_grad``/``lr`` — i.e. a ZO one).

Every artifact is stamped with the program's seed-schedule coordinates
(``perturb_backend``, ``batch_seeds``, ``exec_plan``, ``n_groups``); resuming
under mismatched coordinates refuses (``BackendMismatchError`` /
``PlanMismatchError``) instead of silently re-pairing recorded scalars with
different z streams.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core.trajectory import TrajectoryLedger
from repro.data.pipeline import Pipeline
from repro.exec import as_step_program, check_replay_plan
from repro.perturb import check_replay_backend
from repro.select import check_replay_selection
from repro.tree_utils import PyTree


class FailureInjector:
    """Test hook: raise at a chosen step to simulate a node crash."""

    def __init__(self, fail_at_step: Optional[int] = None):
        self.fail_at_step = fail_at_step

    def check(self, step: int) -> None:
        if self.fail_at_step is not None and step == self.fail_at_step:
            raise RuntimeError(f"injected failure at step {step}")


@dataclasses.dataclass
class TrainResult:
    params: PyTree
    opt_state: Any
    losses: list
    steps_run: int
    resumed_from: int


def train(loss_fn: Callable, params: PyTree, optimizer, pipeline: Pipeline,
          total_steps: int, ckpt: Optional[CheckpointManager] = None,
          ledger: Optional[TrajectoryLedger] = None,
          injector: Optional[FailureInjector] = None,
          log_every: int = 50, donate: bool = True,
          eval_fn: Optional[Callable] = None, eval_every: int = 0,
          verbose: bool = False, seed: int = 0) -> TrainResult:
    """Run (or resume) a training job.  ``optimizer`` is a
    ``repro.exec.StepProgram`` or any ``repro.zo.Optimizer`` protocol
    conformer (wrapped onto the local execution plan)."""
    program = as_step_program(optimizer)
    opt_state = program.init(params, seed=seed)

    # the program's seed-schedule coordinates (None for non-ZO optimizers)
    # are stamped into every artifact so replay under the wrong backend or
    # execution plan — which would regenerate *different* z or re-pair the
    # recorded scalars with different streams — fails loudly
    meta = program.meta
    backend_name = meta["perturb_backend"]
    if ledger is not None and backend_name is not None:
        if len(ledger) == 0:
            ledger.backend = backend_name
            ledger.batch_seeds = int(meta["batch_seeds"])
            ledger.exec_plan = meta["exec_plan"]
            ledger.n_groups = int(meta["n_groups"])
            ledger.selection = meta["selection"] or "full"
            ledger.sel_phase = int(meta["sel_phase"] or 0)
        else:
            check_replay_backend(ledger.backend, backend_name,
                                 "the provided trajectory ledger")
            check_replay_plan(ledger.n_groups, meta["n_groups"],
                              "the provided trajectory ledger",
                              recorded_kind=ledger.exec_plan,
                              active_kind=meta["exec_plan"])
            check_replay_selection(getattr(ledger, "selection", None),
                                   meta["selection"],
                                   "the provided trajectory ledger",
                                   getattr(ledger, "sel_phase", 0),
                                   meta["sel_phase"])

    start_step = 0
    # ---- resume ---------------------------------------------------------- #
    if ckpt is not None:
        restored = ckpt.restore_latest(params, opt_state)
        if restored is not None:
            check_replay_backend(restored["meta"].get("perturb_backend"),
                                 backend_name, "checkpoint")
            ckpt_bs = restored["meta"].get("batch_seeds")
            if ckpt_bs is not None and meta["batch_seeds"] is not None \
                    and int(ckpt_bs) != int(meta["batch_seeds"]):
                raise ValueError(
                    f"checkpoint was written by an optimizer with "
                    f"batch_seeds={ckpt_bs} but the active optimizer uses "
                    f"batch_seeds={meta['batch_seeds']}; the seed fold "
                    "schedule (and the ledger's per-step record shape) "
                    "differ — resume with a matching fzoo(batch_seeds=...) "
                    "composition")
            check_replay_plan(restored["meta"].get("n_groups"),
                              meta["n_groups"], "checkpoint",
                              recorded_kind=restored["meta"].get("exec_plan"),
                              active_kind=meta["exec_plan"])
            check_replay_selection(restored["meta"].get("selection"),
                                   meta["selection"], "checkpoint",
                                   restored["meta"].get("sel_phase"),
                                   meta["sel_phase"])
            params = restored["params"]
            opt_state = restored["opt_state"] if restored["opt_state"] is not None else opt_state
            start_step = restored["step"]
            if ledger is not None:
                saved = ckpt.load_ledger()
                if saved is not None and len(saved) and saved.steps[-1] >= start_step:
                    # ledger replay advances params past the tensor ckpt;
                    # recovery consumes the execution engine directly
                    params, start_step = ckpt.recover_via_ledger(
                        params, start_step, program)
                    ledger.steps = saved.steps
                    ledger.grads = saved.grads
                    ledger.lrs = saved.lrs
                    ledger.batch_seeds = saved.batch_seeds
                    ledger.exec_plan = saved.exec_plan
                    ledger.n_groups = saved.n_groups
                    ledger.selection = saved.selection
                    ledger.sel_phase = saved.sel_phase
            # realign the optimizer's step counter (seed source + lr index)
            # with wherever resume landed — the protocol's resume hook
            opt_state = program.restore(opt_state, start_step)

    jit_kw = {}
    if program.plan.mesh is not None:
        # a mesh plan (seed_parallel over devices): θ and the state
        # replicated, each device's batch slice resident on it
        pshard, sshard, bshard = program.shardings(
            params, pipeline.batch(start_step), opt_state)
        params = jax.device_put(params, pshard)
        opt_state = jax.device_put(opt_state, sshard)
        jit_kw["in_shardings"] = (pshard, sshard, bshard)
    step_fn = jax.jit(program.step_fn(loss_fn),
                      donate_argnums=(0,) if donate else (), **jit_kw)
    losses = []
    t0 = time.time()
    for step in range(start_step, total_steps):
        if injector is not None:
            injector.check(step)
        batch = pipeline.batch(step)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if ledger is not None:
            if "projected_grad" not in metrics:
                raise ValueError(
                    "ledger recording requires a ZO optimizer whose step "
                    "metrics expose 'projected_grad'/'lr'; "
                    f"{type(optimizer).__name__} does not")
            # multi-stream steps (batched seeds, seed-parallel groups,
            # interleaved n-SPSA) expose the per-stream vector — record it so
            # replay can refold the rank-1 updates stream by stream
            g_rec = metrics.get("projected_grads")
            if g_rec is None:
                g_rec = float(metrics["projected_grad"])
            else:
                g_rec = np.asarray(g_rec)
            ledger.append(step, g_rec, float(metrics["lr"]))
            if ckpt is not None:
                ckpt.save_ledger(ledger)
        if ckpt is not None:
            ckpt.maybe_save(step + 1, params, opt_state, meta=meta)
        if step % log_every == 0 or step == total_steps - 1:
            losses.append((step, float(metrics["loss"])))
            if verbose:
                print(f"step {step:6d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time()-t0):.1f}s)", flush=True)
        if eval_fn is not None and eval_every and (step + 1) % eval_every == 0:
            eval_fn(step + 1, params)

    if ckpt is not None:
        ckpt.maybe_save(total_steps, params, opt_state, meta=meta, force=True)
    return TrainResult(params, opt_state, losses, total_steps - start_step,
                       start_step)
