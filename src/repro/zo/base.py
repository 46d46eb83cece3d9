"""Protocol types and the ``ZOOptimizer`` facade.

Three pieces, optax-style but specialized to the scalar structure of
zeroth-order updates (a MeZO step is fully determined by ``(seed, g)`` pairs):

* ``ZOEstimator`` — produces the scalar projected gradient from forward
  passes only.  ``estimate`` returns a ``ZOEstimate`` whose ``apply_update``
  and ``restore`` closures preserve the estimator's own perturbation chain
  (for sequential SPSA that is the donation-friendly in-place chain of
  ``core/mezo.py``: the closure continues from θ−εz with one fused pass).
* ``ZOTransform`` — rewrites the scalar ledger entry (clip, η-scale, decay)
  or, for preconditioners like ZO-Adam, takes over the whole update via
  ``Updates.final_params``.  State is O(window) scalars by construction.
* ``ZOOptimizer`` — the single facade every consumer talks to:
  ``init(params, *, seed)`` / ``step_fn(loss_fn)`` / ``restore(state, step)``
  plus ``replay_update`` for scalar-ledger replay (checkpoint recovery,
  async straggler application).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.perturb import PerturbBackend, StreamRef, get_backend, step_key
from repro.tree_utils import PyTree
from repro.zo import phases

ZOLossFn = Callable[[PyTree, Any], jnp.ndarray]


# --------------------------------------------------------------------------- #
# Estimator protocol
# --------------------------------------------------------------------------- #
class ZOEstimate(NamedTuple):
    """One seed's worth of estimation, plus how to act on it.

    ``apply_update(coeff, decay_term)`` applies θ ← (1−decay)·θ − coeff·z
    continuing from wherever the estimator left the parameter tree (fused
    restore+update for the sequential chain).  ``restore()`` returns the
    un-perturbed center parameters — used when a transform materializes its
    own update (ZO-Adam) instead of the default rank-1 form.
    """
    projected_grad: jnp.ndarray            # scalar g (pre-transform)
    loss: jnp.ndarray                      # scalar loss estimate for logging
    apply_update: Callable[[Any, Any], PyTree]
    restore: Callable[[], PyTree]
    est_state: Any                         # carry (e.g. one-point residual)
    aux: dict                              # extra metrics, merged into step's


class ZOEstimator(NamedTuple):
    """Factory-produced estimator: ``init(params, key) -> state`` and
    ``estimate(loss_fn, params, batch, key, state) -> ZOEstimate``.

    ``n_seeds > 1`` asks the facade to run the estimator once per folded
    seed key, interleaving updates (Algorithm 2's sequential n-SPSA).

    ``replayable`` declares that the estimator's update is the plain rank-1
    θ ← (1−ηλ)θ − η·g·z(seed) — i.e. a ledger's (seed, g, lr) triple alone
    reproduces it.  Definition-6 rescaled updates (along D·z) are not.

    ``backend`` is the resolved ``repro.perturb.PerturbBackend`` the
    estimator's perturbation chain runs through (``None`` → the default
    ``xla``); the facade exposes it for metadata recording and routes
    ``replay_update`` through the same backend.

    ``batch_seeds > 1`` declares a batched-seed estimator (FZOO): one
    ``estimate`` call evaluates B perturbations and its ``projected_grad`` is
    a (B,)-vector of per-seed scalars rather than a scalar.  The transform
    chain applies elementwise, the facade exposes the vector as the
    ``projected_grads`` metric for per-seed ledger recording, and
    ``replay_update`` replays the B folded rank-1 updates.

    ``selection`` is the resolved ``repro.select.Selection`` scoping the
    estimator's perturbations to a parameter subset (``None`` = full tree —
    the zero-overhead default).  When the selection carries a block schedule
    (``n_phases > 1``), ``estimate`` must accept a static ``phase=`` kwarg
    and the facade dispatches the step over phases."""
    init: Callable[[Optional[PyTree], jax.Array], Any]
    estimate: Callable[..., ZOEstimate]
    n_seeds: int = 1
    eps: float = 1e-3
    dist: str = "gaussian"
    name: str = "spsa"
    replayable: bool = True
    backend: Optional[PerturbBackend] = None
    batch_seeds: int = 1
    selection: Any = None


# --------------------------------------------------------------------------- #
# Transform protocol
# --------------------------------------------------------------------------- #
class Updates(NamedTuple):
    """The value threaded through a transform chain, per seed.

    ``g`` is the ledger scalar (what gets recorded/averaged); ``coeff`` the
    η-scaled update coefficient; ``lr`` the schedule's learning rate (set by
    ``scale_by_schedule`` so later transforms — weight decay, Adam — can see
    it); ``decay`` the decoupled weight-decay term η·λ; ``final_params``
    short-circuits the default rank-1 application when a transform has
    materialized the whole update itself.
    """
    g: jnp.ndarray
    coeff: Optional[jnp.ndarray] = None
    lr: Optional[jnp.ndarray] = None
    decay: Any = 0.0
    final_params: Optional[PyTree] = None


class TransformCtx(NamedTuple):
    """Read-only step context handed to every transform."""
    step: jnp.ndarray                      # int32 step counter
    base_key: jax.Array                    # run seed (for window replay)
    key: jax.Array                         # this seed's perturbation key
    seed_index: int                        # python int, 0..n_seeds-1
    n_seeds: int
    eps: float
    dist: str
    restore: Callable[[], PyTree]          # center params, estimator-specific
    backend: Any = None                    # the run's PerturbBackend


class ZOTransform(NamedTuple):
    """``init(params) -> state`` / ``update(updates, state, ctx)``.

    ``info`` carries static metadata the facade introspects: ``lr_at`` (the
    schedule), ``weight_decay`` (for ledger replay), ``applier: True`` for
    transforms that set ``final_params`` (these keep per-step state and are
    incompatible with interleaved n-SPSA)."""
    init: Callable[[Optional[PyTree]], Any]
    update: Callable[[Updates, Any, TransformCtx], tuple[Updates, Any]]
    info: dict


def identity() -> ZOTransform:
    """The do-nothing transform (coeff = g, no decay)."""
    return ZOTransform(lambda params: (),
                       lambda u, state, ctx: (u, state),
                       {})


def chain(*transforms: ZOTransform) -> ZOTransform:
    """Compose transforms left-to-right, optax-style.

    Ordering matters exactly as in optax: ``clip_projected_grad`` operates on
    the raw scalar so it precedes ``scale_by_schedule``; ``add_weight_decay``
    and ``scale_by_zo_adam`` read ``Updates.lr`` so they follow it.
    """
    if len(transforms) == 1:
        return transforms[0]

    def init(params):
        return tuple(t.init(params) for t in transforms)

    def update(u, state, ctx):
        new_state = []
        for t, s in zip(transforms, state):
            u, s = t.update(u, s, ctx)
            new_state.append(s)
        return u, tuple(new_state)

    info: dict = {}
    for t in transforms:
        info.update(t.info)
    return ZOTransform(init, update, info)


# --------------------------------------------------------------------------- #
# Optimizer protocol + facade
# --------------------------------------------------------------------------- #
@runtime_checkable
class Optimizer(Protocol):
    """The uniform optimizer surface every consumer programs against —
    ZO compositions and backprop baselines alike.  No isinstance dispatch:
    the training loop, checkpoint recovery, and distributed paths only ever
    call these three methods."""

    def init(self, params: Optional[PyTree], *, seed: int = 0) -> Any: ...

    def step_fn(self, loss_fn: ZOLossFn) -> Callable: ...

    def restore(self, state: Any, step: int) -> Any: ...


class ZOState(NamedTuple):
    """Uniform optimizer state: a step counter, the run seed, and whatever
    scalar carry the estimator/transforms declared.  Checkpointable as a
    plain pytree; resumable via ``ZOOptimizer.restore``."""
    step: jnp.ndarray
    base_key: jax.Array
    est_state: Any
    tf_state: Any
    last_projected_grad: jnp.ndarray


class ZOOptimizer:
    """estimator × transform-chain behind the uniform protocol.

    >>> opt = ZOOptimizer(estimators.spsa(eps=1e-3),
    ...                   chain(transforms.clip_projected_grad(1.0),
    ...                         transforms.scale_by_schedule(1e-6),
    ...                         transforms.add_weight_decay(0.01)))
    >>> state = opt.init(params, seed=0)
    >>> step = jax.jit(opt.step_fn(loss_fn), donate_argnums=(0,))
    >>> params, state, metrics = step(params, state, batch)
    """

    def __init__(self, estimator: ZOEstimator,
                 transform: Optional[ZOTransform] = None,
                 name: Optional[str] = None):
        self.estimator = estimator
        self.transform = transform if transform is not None else identity()
        self.name = name or estimator.name
        if estimator.n_seeds > 1 and self.transform.info.get("applier"):
            raise ValueError(
                "stateful applier transforms (scale_by_zo_adam / trace) keep "
                "one ledger entry per step and cannot run under interleaved "
                "n-SPSA; use n_seeds=1")
        if getattr(estimator, "batch_seeds", 1) > 1 and \
                self.transform.info.get("applier"):
            raise ValueError(
                "applier transforms (scale_by_zo_adam / trace) reconstruct "
                "their update from one scalar per step and cannot consume a "
                "batched-seed estimator's per-seed g vector; use "
                "batch_seeds=1 or a scalar transform chain")
        if self.transform.info.get("applier") and \
                self.transform.info.get("scalar_decay"):
            raise ValueError(
                "add_weight_decay sets the scalar decay slot, which applier "
                "transforms (scale_by_zo_adam / trace) bypass — pass "
                "weight_decay= to the applier transform instead")
        if getattr(estimator, "selection", None) is not None and \
                self.transform.info.get("applier"):
            raise ValueError(
                "applier transforms (scale_by_zo_adam / trace) materialize "
                "their update over the FULL tree from the g-history, which "
                "would write unselected leaves; parameter selections "
                "(repro.select) compose with rank-1 scalar chains only")

    # -- introspection (used for ledger replay and by distributed paths) ---- #
    @property
    def info(self) -> dict:
        return self.transform.info

    @property
    def backend(self) -> "PerturbBackend":
        """The perturbation backend this composition runs through."""
        return get_backend(self.estimator.backend)

    @property
    def backend_name(self) -> str:
        """Identity recorded in checkpoint/ledger metadata — the backend's
        ``stream_id`` (name plus z-generator version suffix) — so replay
        under a different backend OR an artifact from a since-revised
        z generator fails loudly instead of silently diverging."""
        return self.backend.stream_id

    @property
    def batch_seeds(self) -> int:
        """Seed streams evaluated per step by a batched estimator (FZOO);
        1 for everything else.  Recorded in checkpoint/ledger metadata."""
        return int(getattr(self.estimator, "batch_seeds", 1))

    @property
    def selection(self):
        """The resolved ``repro.select.Selection`` scoping this composition's
        perturbations (``None`` = full tree)."""
        return getattr(self.estimator, "selection", None)

    @property
    def selection_spec(self) -> str:
        """Canonical selection spec recorded in checkpoint/ledger metadata
        (``"full"`` when no selection is set) — replay under a different
        selection fails loudly (``SelectionMismatchError``) instead of
        applying the recorded scalars to a different parameter support."""
        sel = self.selection
        return "full" if sel is None else sel.spec

    @property
    def selection_phase(self) -> int:
        """The selection's block-schedule phase offset (0 when unscheduled);
        recorded alongside the spec — phase(t) = (t + offset) mod n_phases."""
        sel = self.selection
        return 0 if sel is None else int(sel.phase_offset)

    @property
    def weight_decay(self) -> float:
        return self.info.get("weight_decay", 0.0)

    def lr_at(self, step) -> jnp.ndarray:
        fn = self.info.get("lr_at")
        return fn(step) if fn is not None else jnp.float32(1.0)

    # -- protocol ----------------------------------------------------------- #
    def init(self, params: Optional[PyTree] = None, *, seed: int = 0) -> ZOState:
        base_key = jax.random.PRNGKey(seed)
        return ZOState(step=jnp.int32(0), base_key=base_key,
                       est_state=self.estimator.init(params, base_key),
                       tf_state=self.transform.init(params),
                       last_projected_grad=jnp.float32(0.0))

    def restore(self, state: ZOState, step: int) -> ZOState:
        """Resume bookkeeping: after ledger replay advanced the parameters
        past a tensor checkpoint, realign the step counter (the seed source
        and lr index) — the protocol form of what used to be an ad-hoc
        ``_replace(step=...)`` in the training loop."""
        return state._replace(step=jnp.int32(step))

    def replay_update(self, params: PyTree, skey: jax.Array, g, lr,
                      phase: int = 0) -> PyTree:
        """Apply one scalar-ledger entry: θ ← (1−η·λ)·θ − η·g·z(skey).
        Used by trajectory replay and checkpoint recovery — no forward
        passes, no data access (paper §2.1).  ``phase`` is the static
        block-schedule phase of the replayed step (0 for unscheduled
        selections) — the caller derives it from the step index exactly as
        the live step did.

        Only rank-1 compositions are replayable from (seed, g, lr) triples:
        an applier transform's step (ZO-Adam / trace) also depends on its
        g-history window, and a Definition-6 rescaled step on its D-tree —
        neither of which the ledger alone can reconstruct."""
        if self.info.get("applier"):
            raise ValueError(
                f"{self.name}: scalar-ledger replay cannot reproduce applier "
                "transforms (scale_by_zo_adam / trace); resume from a full "
                "state checkpoint instead of a ledger tail")
        if not self.estimator.replayable:
            raise ValueError(
                f"{self.name}: the {self.estimator.name!r} estimator updates "
                "along D·z (Definition 6), which a (seed, g, lr) ledger entry "
                "cannot reproduce; resume from a full state checkpoint")
        sel = self.selection
        with jax.named_scope(phases.UPDATE):
            if self.batch_seeds > 1:
                # batched-seed (FZOO) entry: g is the (B,) per-seed vector
                # and the step was B folded rank-1 applications — replay
                # them identically
                from repro.zo.updates import apply_rank1_batch
                return apply_rank1_batch(params, skey, lr * jnp.asarray(g),
                                         lr * self.weight_decay,
                                         dist=self.estimator.dist,
                                         backend=self.backend,
                                         selection=sel, phase=phase)
            ref = StreamRef(skey)
            if sel is not None:
                ref = ref.with_selection(sel, phase)
            return self.backend.apply_rank1(params, ref, lr * g,
                                            lr * self.weight_decay,
                                            self.estimator.dist)

    def step_fn(self, loss_fn: ZOLossFn) -> Callable[
            [PyTree, ZOState, Any], tuple[PyTree, ZOState, dict]]:
        est = self.estimator
        tf = self.transform
        n = est.n_seeds
        backend = self.backend
        sel = self.selection
        n_phases = 1 if sel is None else int(sel.n_phases)

        def body(params: PyTree, state: ZOState, batch, phase: int):
            skey0 = step_key(state.base_key, state.step)
            p = params
            est_state, tf_state = state.est_state, state.tf_state
            gs, losses = [], []
            aux: dict = {}
            lr_metric = None
            for j in range(n):
                skey = jax.random.fold_in(skey0, j) if n > 1 else skey0
                e = phases.estimate(est, loss_fn, p, batch, skey, est_state,
                                    phase if n_phases > 1 else None)
                est_state = e.est_state
                ctx = TransformCtx(step=state.step, base_key=state.base_key,
                                   key=skey, seed_index=j, n_seeds=n,
                                   eps=est.eps, dist=est.dist,
                                   restore=e.restore, backend=backend)
                with jax.named_scope(phases.UPDATE):
                    u, tf_state = tf.update(Updates(g=e.projected_grad),
                                            tf_state, ctx)
                    if u.final_params is not None:
                        p = u.final_params
                    else:
                        coeff = u.coeff if u.coeff is not None else u.g
                        p = e.apply_update(coeff, u.decay)
                gs.append(u.g)
                losses.append(e.loss)
                if e.aux:
                    aux.update(e.aux)
                lr_metric = u.lr
            g_mean = jnp.mean(jnp.stack(gs))
            loss = jnp.mean(jnp.stack(losses))
            if lr_metric is None:
                lr_metric = jnp.float32(1.0)
            new_state = ZOState(state.step + 1, state.base_key,
                                est_state, tf_state, g_mean)
            metrics = {"loss": loss, "projected_grad": g_mean,
                       "lr": lr_metric, **aux}
            if n > 1:
                # interleaved n-SPSA: expose the per-seed scalars (fold
                # schedule fold(skey0, j)) so the ledger records what the
                # engine's group replay needs — one g per stream, flattened
                # to the ledger's (n_groups·batch_seeds,) record shape
                metrics["projected_grads"] = jnp.stack(gs).reshape(-1)
            elif jnp.ndim(gs[0]) > 0:
                # batched-seed estimator: expose the per-seed scalars so the
                # ledger records what replay_update needs (one g per stream)
                metrics["projected_grads"] = gs[0]
            return p, new_state, metrics

        if n_phases == 1:
            def step(params: PyTree, state: ZOState, batch):
                return body(params, state, batch, 0)
        else:
            # block-scheduled selection: the active leaf block is a STATIC
            # trace-time property (skipped leaves cost zero z generation), so
            # the step dispatches over the n_phases static bodies with
            # lax.switch on phase(t) = (t + offset) mod n_phases — a pure
            # function of the step counter, hence identical under every
            # execution plan
            branches = [functools.partial(body, phase=ph)
                        for ph in range(n_phases)]

            def step(params: PyTree, state: ZOState, batch):
                return jax.lax.switch(sel.phase_at(state.step), branches,
                                      params, state, batch)

        return step
