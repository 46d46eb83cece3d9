"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Single-host entry point wiring the full stack: registry model, MeZO (or
backprop-Adam baseline), step-indexed data, checkpoint manager + scalar
ledger, heartbeat.  On a real cluster each host runs this with
``jax.distributed.initialize`` handled by the scheduler; the step function
and data pipeline are already multi-host-safe (pure step-indexed batches,
pjit-ready shardings from repro.distributed).  ``--exec-plan seed_parallel``
on a host with several devices puts group g's batch slice on device g of a
``data`` mesh (``StepProgram.shardings``).

``main(argv)`` can be driven in-process and returns a :class:`TrainRun`.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh

from repro import exec as zexec
from repro import zo
from repro.checkpoint.manager import CheckpointManager
from repro.core import TrajectoryLedger
from repro.data.pipeline import DataSpec, Pipeline
from repro.launch.cache import enable_compile_cache
from repro.models import FAMILY_ARCHS, OBJECTIVES, all_archs, bundle
from repro.train.adam import Adam, AdamConfig
from repro.train.loop import TrainResult, train
from repro.tree_utils import tree_size


@dataclasses.dataclass
class TrainRun:
    """What ``main`` built and ran: enough to replay the ledger onto the
    initial parameters (``bundle.init(PRNGKey(seed))``) and compare."""
    cfg: Any
    bundle: Any
    optimizer: Any                # a StepProgram or a bare optimizer
    pipeline: Pipeline
    ledger: Optional[TrajectoryLedger]
    result: TrainResult


def group_mesh(n_groups: int) -> Optional[Mesh]:
    """A ``data`` mesh holding one seed group per device, or ``None`` when
    the host has fewer devices than groups (or one group): the groups then
    run in turn on one device."""
    devices = jax.devices()
    if n_groups == 1 or n_groups > len(devices):
        return None
    return Mesh(np.array(devices[:n_groups]).reshape(n_groups, 1),
                ("data", "model"))


def main(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--model-family", default=None,
                    choices=sorted(FAMILY_ARCHS),
                    help="architecture-family quickstart: picks the "
                         "representative registry arch for the family "
                         "(overrides --arch); e.g. --model-family moe "
                         "--select auto runs mixtral with expert-wise "
                         "selection")
    ap.add_argument("--optimizer", default="mezo",
                    choices=["mezo", "mezo-adam", "adam", "sgd"])
    ap.add_argument("--estimator", default="spsa",
                    choices=["spsa", "one_point", "fzoo"],
                    help="gradient estimator for --optimizer mezo; 'fzoo' is "
                         "the batched-seed one-sided estimator "
                         "(--batch-seeds streams per step, one vmapped "
                         "forward, loss-diff-std step normalization)")
    ap.add_argument("--batch-seeds", type=int, default=8,
                    help="seed streams per step for --estimator fzoo")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--eps", type=float, default=1e-3)
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "pallas-interpret"],
                    help="perturbation backend (repro.perturb): xla threefry "
                         "or the VMEM-fused pallas kernel")
    ap.add_argument("--select", default="full",
                    help="parameter selection (repro.select) for the ZO "
                         "optimizers: 'full', 'leaves(<regex>)', "
                         "'block_cyclic(<k>)' (rotating leaf blocks, ~1/k of "
                         "the tree perturbed per step), "
                         "'peft(lora|prefix)' for a merged PEFT tree, "
                         "'moe_experts(<G>)' (router frozen, one expert "
                         "group per step; needs --expert-groups G), or "
                         "'auto' for the registry's per-family default; "
                         "recorded in ckpt meta + the MZOL5 ledger header")
    ap.add_argument("--objective", default="ce", choices=list(OBJECTIVES),
                    help="training objective: 'ce' (cross-entropy) or the "
                         "non-differentiable 'accuracy'/'f1' metrics (paper "
                         "§3.3) — zero gradient a.e., so they require a ZO "
                         "optimizer (--optimizer mezo)")
    ap.add_argument("--expert-groups", type=int, default=None,
                    help="MoE only: split the expert tensors into G leaf "
                         "groups (cfg.expert_groups) so moe_experts(G) "
                         "selection can cycle one group per step")
    ap.add_argument("--scan-mode", default=None,
                    choices=["chunk", "fused_recurrent"],
                    help="ssm/hybrid forward mode: 'chunk' (chunked-matmul, "
                         "default) or 'fused_recurrent' (exact per-token "
                         "recurrence; parity-tested oracle)")
    ap.add_argument("--exec-plan", default="local",
                    choices=["local", "seed_parallel"],
                    help="execution plan (repro.exec): 'local' is the "
                         "jit+donate loop step; 'seed_parallel' splits the "
                         "batch into --n-groups slices, evaluates seed "
                         "group g on slice g at the step's center, and "
                         "averages the n rank-1 directions (cross-device "
                         "traffic: loss scalars only)")
    ap.add_argument("--n-groups", type=int, default=1,
                    help="seed groups per step for --exec-plan seed_parallel")
    ap.add_argument("--seed-parallel", type=int, default=None,
                    help="DEPRECATED alias for "
                         "--exec-plan seed_parallel --n-groups N")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=100)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.model_family is not None:
        args.arch = FAMILY_ARCHS[args.model_family]
    arch = all_archs()[args.arch]
    cfg = arch.smoke_cfg if args.smoke else arch.cfg
    if args.expert_groups is not None:
        if not cfg.n_experts:
            raise SystemExit(f"--expert-groups needs an MoE arch "
                             f"(got {args.arch!r}, family {cfg.family!r})")
        cfg = cfg.replace(expert_groups=args.expert_groups)
    if args.scan_mode is not None:
        cfg = cfg.replace(scan_mode=args.scan_mode)
    b = bundle(cfg)
    if args.select == "auto":
        # the registry's per-family default (MoE: expert-wise cycling with
        # the router frozen; everything else: full)
        args.select = b.default_selection()
        print(f"[train] --select auto -> {args.select!r}")
    params = b.init(jax.random.PRNGKey(args.seed))
    print(f"[train] {cfg.name}: {tree_size(params)/1e6:.1f} M params, "
          f"optimizer={args.optimizer}")

    pipe = Pipeline(DataSpec("lm", batch=args.batch, seq=args.seq,
                             vocab=cfg.vocab_size, seed=args.seed))
    if args.objective != "ce" and args.optimizer not in ("mezo", "mezo-adam"):
        # argmax metrics have zero gradient a.e. — backprop would "train"
        # without ever changing the loss; refuse instead of silently stalling
        raise SystemExit(f"--objective {args.objective!r} is "
                         "non-differentiable and needs a ZO optimizer "
                         f"(--optimizer mezo); got {args.optimizer!r}")
    if args.select != "full" and args.optimizer != "mezo":
        # fail loudly: every other branch would silently train the full tree
        # (adam/sgd have no selection support; mezo-adam's applier transform
        # refuses selections at composition time)
        raise SystemExit(f"--select {args.select!r} requires --optimizer mezo "
                         f"(got {args.optimizer!r})")
    ledger = None
    if args.optimizer == "mezo":
        if args.estimator == "fzoo":
            opt = zo.fzoo(lr=args.lr or 1e-6, eps=args.eps,
                          batch_seeds=args.batch_seeds, backend=args.backend,
                          selection=args.select)
        else:
            opt = zo.mezo(lr=args.lr or 1e-5, eps=args.eps,
                          estimator=args.estimator, backend=args.backend,
                          selection=args.select)
        if args.select != "full":
            print(f"[train] parameter selection: {opt.selection_spec}")
        ledger = TrajectoryLedger(base_seed=args.seed, grad_dtype="float32",
                                  backend=opt.backend_name,
                                  batch_seeds=opt.batch_seeds,
                                  selection=opt.selection_spec,
                                  sel_phase=opt.selection_phase)
    elif args.optimizer == "mezo-adam":
        opt = zo.mezo_adam(lr=args.lr or 1e-4, eps=args.eps,
                           backend=args.backend)
    elif args.optimizer == "adam":
        opt = Adam(AdamConfig(lr=args.lr or 1e-4, total_steps=args.steps))
    else:
        opt = Adam(AdamConfig(lr=args.lr or 1e-3, sgd=True,
                              total_steps=args.steps))

    if args.seed_parallel is not None:       # deprecated alias
        print("[train] --seed-parallel is deprecated; use "
              "--exec-plan seed_parallel --n-groups N")
        args.exec_plan, args.n_groups = "seed_parallel", args.seed_parallel
    if args.exec_plan == "seed_parallel":
        if args.optimizer != "mezo":
            raise SystemExit("--exec-plan seed_parallel needs a "
                             "seed-replayable ZO optimizer (--optimizer mezo,"
                             " any --estimator)")
        if args.batch % args.n_groups:
            raise SystemExit(f"--batch {args.batch} must divide evenly into "
                             f"--n-groups {args.n_groups} slices")
        mesh = group_mesh(args.n_groups)
        opt = zexec.StepProgram(opt, zexec.seed_parallel(args.n_groups,
                                                         mesh=mesh))
        print(f"[train] exec plan: seed_parallel(n_groups={args.n_groups})"
              + (f" on a data={args.n_groups} mesh" if mesh else ""))

    ckpt = (CheckpointManager(args.ckpt_dir, interval=args.ckpt_interval)
            if args.ckpt_dir else None)
    res = train(b.loss_fn(objective=args.objective), params, opt, pipe,
                total_steps=args.steps, ckpt=ckpt, ledger=ledger,
                log_every=max(args.steps // 10, 1), verbose=True,
                seed=args.seed)
    print(f"[train] done: {res.steps_run} steps "
          f"(resumed from {res.resumed_from}); "
          f"final loss {res.losses[-1][1]:.4f}")
    if ledger is not None:
        print(f"[train] ledger: {len(ledger)} entries, "
              f"{ledger.nbytes()} bytes")
    return TrainRun(cfg, b, opt, pipe, ledger, res)


if __name__ == "__main__":
    main()
