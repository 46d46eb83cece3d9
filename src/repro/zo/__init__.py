"""``repro.zo`` — composable zeroth-order optimization (estimator × transforms).

The paper's key structural insight ("Fine-Tuning Language Models with Just
Forward Passes", Malladi et al., NeurIPS 2023) is that a MeZO update is fully
determined by scalar pairs ``(seed, projected_grad)``.  This package turns
that insight into an optax-style composition layer: estimators produce the
scalar, transforms rewrite the scalar ledger, and one facade speaks a uniform
protocol to the training loop, checkpoint recovery, and distributed paths.

Mapping onto the paper
----------------------
=====================================  =======================================
Paper                                  Component
=====================================  =======================================
Algorithm 1 (MeZO)                     ``estimators.spsa(eps)`` — lines 3–8:
                                       the sequential perturb → ℓ+ → perturb
                                       → ℓ− chain with one fused
                                       restore+descent pass (4 z-regens → 3).
Algorithm 1's descent loop             ``updates.apply_rank1`` — the single
                                       θ ← (1−ηλ)θ − η·g·z(seed) primitive
                                       shared by steps, ledger replay, and
                                       async application.
Algorithm 2 (n-SPSA)                   ``estimators.n_spsa(n, eps)`` — n
                                       folded seed keys, updates interleaved
                                       at η/n per seed; plus
                                       ``transforms.scale_by_schedule``'s
                                       per-seed η/n scaling.
Definition 6 (variance-modified,       ``estimators.rescaled_spsa(...)`` —
unbiased: perturb ε·d⁻¹⊙z, update       block-diagonal D-tree (one scalar per
along D·z)                             leaf) from parameter norms or
                                       Proposition-1 ZO grad-norm probes.
Definition 7 (expectation-modified,    ``estimators.rescaled_spsa(
biased normalized-gradient: update       modify_expectation=True)`` — same
along z, not D·z)                      perturbation, identity update scaling.
Definition 8 (one-point residual       ``estimators.one_point(eps)`` — one
feedback)                              forward pass/step, previous perturbed
                                       loss carried as estimator state.
§2.1 storage trick (seed + scalar      ``ZOOptimizer.replay_update`` consumed
ledger reconstructs the run)           by ``core.trajectory.replay`` and
                                       ``checkpoint.manager`` recovery.
§2.2 / App. B.2 (MeZO-Adam from the    ``transforms.scale_by_zo_adam`` —
scalar history)                        ring-buffer recomputed mode (O(window)
                                       scalars) or materialized m/v oracle;
                                       ``transforms.trace`` is the
                                       momentum-only special case.
=====================================  =======================================

Quick start
-----------
>>> from repro import zo
>>> opt = zo.mezo(lr=1e-6, eps=1e-3)                 # Algorithm 1
>>> opt = zo.mezo(lr=1e-6, eps=1e-3, backend="pallas")   # z in VMEM, not HBM
>>> opt = zo.mezo(lr=1e-6, selection="block_cyclic(4)")  # repro.select: ~1/4
...     # of the tree perturbed per step (zero z generation for the rest)
>>> opt = zo.fzoo(lr=1e-6, eps=1e-3, batch_seeds=8)  # FZOO: B batched
...     # one-sided seed streams per step, one vmapped forward, step size
...     # normalized by the std of the B loss differences
>>> # ...or compose by hand:
>>> opt = zo.ZOOptimizer(
...     zo.estimators.spsa(eps=1e-3),
...     zo.chain(zo.transforms.clip_projected_grad(1.0),
...              zo.transforms.scale_by_schedule(1e-6, "linear", 10_000),
...              zo.transforms.add_weight_decay(0.01)))
>>> state = opt.init(params, seed=0)
>>> step = jax.jit(opt.step_fn(loss_fn), donate_argnums=(0,))
>>> params, state, metrics = step(params, state, batch)
>>> state = opt.restore(state, 5_000)                # resume bookkeeping

New estimators (MeZO-SVRG-style variance reduction; FZOO's batched seeds
landed exactly this way: ``estimators.fzoo`` + ``transforms.scale_by_fzoo_std``)
and new update rules plug in as components — one ``ZOEstimator`` or one
``ZOTransform``, not a new monolithic optimizer class.  Every composition
takes a ``backend=`` kwarg selecting the z-generation strategy
(:mod:`repro.perturb`): ``"xla"`` threefry (default) or ``"pallas"`` — the
fused kernel generating z inside VMEM, in interpret mode on the CPU.
The choice is recorded in checkpoint/ledger metadata; replay under the wrong
backend raises ``BackendMismatchError`` instead of silently diverging.
"""
from repro.zo import estimators, transforms
from repro.zo.base import (Optimizer, TransformCtx, Updates, ZOEstimate,
                           ZOEstimator, ZOLossFn, ZOOptimizer, ZOState,
                           ZOTransform, chain, identity)
from repro.zo.presets import (as_zo_optimizer, from_config, fzoo, mezo,
                              mezo_adam, mezo_rescaled)
from repro.zo.updates import apply_rank1, apply_rank1_batch

__all__ = [
    # protocol
    "Optimizer", "ZOOptimizer", "ZOState", "ZOEstimator", "ZOEstimate",
    "ZOTransform", "TransformCtx", "Updates", "ZOLossFn",
    # composition
    "chain", "identity", "estimators", "transforms",
    # primitives
    "apply_rank1", "apply_rank1_batch",
    # presets / interop
    "mezo", "fzoo", "mezo_adam", "mezo_rescaled", "from_config",
    "as_zo_optimizer",
]
