"""``StepProgram`` — one mesh-aware ZO step engine for every execution plan.

The repo used to hand-roll four step builders (the facade's local loop,
seed-parallel collectives, the async gossip worker, ledger replay), and the
scaling-critical ones bypassed the perturbation-backend layer entirely.  The
engine collapses them: a ``StepProgram`` lowers any ``repro.zo`` optimizer
(spsa, n_spsa, one_point, rescaled_spsa, fzoo, plus any transform chain) onto
an :mod:`repro.exec.plan` and routes **every** parameter write through
``PerturbBackend`` (``perturb`` / ``perturb_many`` / ``apply_rank1``) — never
through raw key chains.

The one seed schedule (``group_key``): stream g of step t is
``fold_in(step_key(base, t), g)`` when ``n_groups > 1``, the unfolded step
key when ``n_groups == 1``.  This is exactly the local facade's per-seed fold,
so:

* ``seed_parallel(1)`` is **bitwise-identical** to ``local`` (test-enforced
  for spsa and fzoo on the xla backend);
* a local n-SPSA run, a seed-parallel run, and an async staleness-0 round
  with the same ``n_groups`` record interchangeable ledger entries;
* ``apply_group_update`` is the ONE write path shared by the live
  seed-parallel step, async contribution application, and ledger replay —
  identical floats by construction, which is what makes a ledger written
  under any plan replay under ``replay()``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.exec import plan as plan_mod
from repro.exec.plan import ExecPlan, check_replay_plan
from repro.perturb import StreamRef, check_replay_backend, step_key
from repro.select import check_replay_selection
from repro.tree_utils import PyTree
from repro.zo import phases
from repro.zo.base import TransformCtx, Updates, ZOState
from repro.zo.presets import as_zo_optimizer
from repro.zo.updates import apply_rank1_batch


# --------------------------------------------------------------------------- #
# The one seed schedule
# --------------------------------------------------------------------------- #
def group_key(skey0: jax.Array, group: int, n_groups: int) -> jax.Array:
    """Stream ``group`` of a step: fold when there are several streams, the
    unfolded step key when there is one (== the local facade's schedule)."""
    return jax.random.fold_in(skey0, group) if n_groups > 1 else skey0


def group_stream_key(base_key: jax.Array, step, group: int,
                     n_groups: int) -> jax.Array:
    """run key → step t → group g, composed from the canonical folds."""
    return group_key(step_key(base_key, step), group, n_groups)


# --------------------------------------------------------------------------- #
# The one write path (live seed-parallel step == async apply == replay)
# --------------------------------------------------------------------------- #
def apply_group_update(params: PyTree, skey0: jax.Array, group: int,
                       n_groups: int, coeff, decay_term, batch_seeds: int,
                       dist: str, backend, selection=None,
                       phase: int = 0) -> PyTree:
    """Apply one group's rank-1 update(s) through the backend primitive.

    ``coeff`` is the fully η-scaled coefficient — a scalar, or the (B,)
    per-stream vector of a batched-seed estimator (``apply_rank1_batch``
    divides by B and folds the per-stream keys itself).  ``selection`` /
    ``phase`` scope the update to the step's selected leaves — the phase is
    the STEP's (a pure function of t), shared by every group of the step."""
    gkey = group_key(skey0, group, n_groups)
    with jax.named_scope(phases.UPDATE):
        if batch_seeds == 1:
            ref = StreamRef(gkey)
            if selection is not None:
                ref = ref.with_selection(selection, phase)
            return backend.apply_rank1(params, ref, coeff, decay_term, dist)
        return apply_rank1_batch(params, gkey, coeff, decay_term, dist,
                                 backend=backend, selection=selection,
                                 phase=phase)


def apply_group_updates(params: PyTree, skey0: jax.Array, coeffs: Sequence,
                        decay_term, n_groups: int, batch_seeds: int,
                        dist: str, backend, selection=None,
                        phase: int = 0) -> PyTree:
    """All groups of one step, in group order; decoupled decay applied once,
    on group 0 (matching ``add_weight_decay``'s seed-0 rule).

    The whole step's n_groups × batch_seeds streams are flattened — in the
    exact order the per-group sequential fold applies them — into ONE
    ``backend.affine_many`` call: on xla that call IS the sequential
    ``apply_rank1`` fold (bitwise the pre-fusion path), on pallas it is the
    fused chain kernel, θ round-tripping HBM once for the entire step's
    update chain instead of once per stream."""
    refs, cs, ds = [], [], []
    for g in range(n_groups):
        gkey = group_key(skey0, g, n_groups)
        decay_g = decay_term if g == 0 else 0.0
        if batch_seeds == 1:
            ref = StreamRef(gkey)
            if selection is not None:
                ref = ref.with_selection(selection, phase)
            refs.append(ref)
            cs.append(coeffs[g])
            ds.append(decay_g)
        else:
            cvec = jnp.asarray(coeffs[g])
            for j in range(batch_seeds):
                ref = StreamRef(jax.random.fold_in(gkey, j))
                if selection is not None:
                    ref = ref.with_selection(selection, phase)
                refs.append(ref)
                cs.append(cvec[j] / batch_seeds)
                ds.append(decay_g if j == 0 else 0.0)
    with jax.named_scope(phases.UPDATE):
        return backend.affine_many(params, refs, cs, ds, dist)


def slice_group(batch, group: int, n_groups: int):
    """Slice ``group``'s shard of the global batch (leading-dim split);
    identity when there is a single group (bitwise parity with local).
    Leading dims must divide evenly — shapes are known at trace time, and
    silently dropping trailing rows would train on truncated data."""
    if n_groups == 1 or batch is None:
        return batch

    def cut(x):
        if jnp.ndim(x) == 0:
            return x                      # scalar leaves ride along unsliced
        if x.shape[0] % n_groups:
            raise ValueError(
                f"batch leading dim {x.shape[0]} does not divide into "
                f"n_groups={n_groups} slices; {x.shape[0] % n_groups} "
                "trailing row(s) would silently never be evaluated — pad or "
                "resize the batch")
        per = x.shape[0] // n_groups
        return jax.lax.dynamic_slice_in_dim(x, group * per, per, axis=0)

    return jax.tree_util.tree_map(cut, batch)


# --------------------------------------------------------------------------- #
# The engine
# --------------------------------------------------------------------------- #
class StepProgram:
    """Lower a ``repro.zo`` optimizer onto an execution plan.

    >>> prog = StepProgram(zo.fzoo(lr=1e-6, batch_seeds=8),
    ...                    exec.seed_parallel(4))
    >>> state = prog.init(params, seed=0)
    >>> step = jax.jit(prog.step_fn(loss_fn), donate_argnums=(0,))
    >>> params, state, metrics = step(params, state, batch)

    Non-ZO optimizers (the backprop baselines) are accepted for the ``local``
    plan only and pass straight through (``meta`` reports no plan
    coordinates, matching their absent seed schedule).
    """

    def __init__(self, optimizer, plan: Optional[ExecPlan] = None):
        self.plan = plan if plan is not None else plan_mod.local()
        if callable(getattr(optimizer, "replay_update", None)) or \
                getattr(optimizer, "estimator", None) is not None or \
                (hasattr(optimizer, "eps") and hasattr(optimizer, "dist")):
            self.opt = as_zo_optimizer(optimizer)
            self.is_zo = True
        else:
            self.opt = optimizer
            self.is_zo = False
            if self.plan.kind != "local":
                raise ValueError(
                    f"{type(optimizer).__name__} is not a seed-replayable ZO "
                    f"optimizer; only the local plan can run it "
                    f"(got {self.plan.kind!r})")
            return
        est = self.opt.estimator
        n = self.plan.n_groups
        if self.plan.kind in ("seed_parallel", "async_worker"):
            if est.n_seeds not in (1, n):
                raise ValueError(
                    f"estimator {est.name!r} declares n_seeds={est.n_seeds} "
                    f"but the {self.plan.kind} plan runs n_groups={n}; the "
                    "plan's groups ARE the seed streams — use n_seeds=1 or "
                    f"n_seeds={n}")
            if self.opt.info.get("applier") and \
                    not (self.plan.kind == "seed_parallel" and n == 1):
                raise ValueError(
                    "applier transforms (scale_by_zo_adam / trace) "
                    "materialize their update from the live tree and "
                    "g-history; group updates are wire-replayable rank-1 "
                    "applications — run appliers under the local plan")
            if not est.replayable and \
                    not (self.plan.kind == "seed_parallel" and n == 1):
                raise ValueError(
                    f"the {est.name!r} estimator updates along D·z "
                    "(Definition 6), which the plan's rank-1 group updates "
                    "cannot reproduce; use modify_expectation=True or the "
                    "local plan")
            if n > 1 and self.opt.info.get("lr_at") is None:
                # group plans (and their ledger/wire replay) reconstruct the
                # update coefficient as (η/n)·g from the recorded schedule;
                # a chain without scale_by_schedule records no η, so the
                # live coefficient (raw g) would silently diverge from the
                # reconstructed one
                raise ValueError(
                    f"the {self.plan.kind} plan needs a transform chain with "
                    "scale_by_schedule (its group updates and their replay "
                    "reconstruct coefficients as (η/n)·g from the recorded "
                    "learning rate); compose via zo.mezo/zo.fzoo or add "
                    "transforms.scale_by_schedule to the chain")

    # -- identity ----------------------------------------------------------- #
    @property
    def n_groups(self) -> Optional[int]:
        """Independent seed streams folded per step at the group level: the
        plan's groups, or — under the local plan — the estimator's
        interleaved n_seeds (same fold schedule, so the artifacts are
        interchangeable)."""
        if not self.is_zo:
            return None
        if self.plan.kind == "local":
            return int(self.opt.estimator.n_seeds)
        return int(self.plan.n_groups)

    @property
    def batch_seeds(self) -> Optional[int]:
        return self.opt.batch_seeds if self.is_zo else None

    @property
    def backend_name(self) -> Optional[str]:
        return self.opt.backend_name if self.is_zo else None

    @property
    def selection(self):
        """The composition's ``repro.select.Selection`` (None = full tree /
        non-ZO).  Every plan carries it: the schedule phase is a pure
        function of the step counter, so it is plan-invariant."""
        return self.opt.selection if self.is_zo else None

    @property
    def meta(self) -> dict:
        """The artifact stamp: everything a resume/replay needs to re-derive
        (or refuse to re-derive) the run's seed schedule."""
        return {"perturb_backend": self.backend_name,
                "batch_seeds": self.batch_seeds,
                "exec_plan": self.plan.kind if self.is_zo else None,
                "n_groups": self.n_groups,
                "selection": self.opt.selection_spec if self.is_zo else None,
                "sel_phase": self.opt.selection_phase if self.is_zo else None}

    # -- protocol delegation ------------------------------------------------ #
    def init(self, params: Optional[PyTree] = None, *, seed: int = 0):
        return self.opt.init(params, seed=seed)

    def restore(self, state, step: int):
        return self.opt.restore(state, step)

    def step_fn(self, loss_fn) -> Callable:
        if not self.is_zo or self.plan.kind == "local":
            return self.opt.step_fn(loss_fn)
        if self.plan.kind == "seed_parallel":
            if self.plan.n_groups == 1:
                # one group == one unfolded seed stream == the local plan;
                # delegating makes the bitwise guarantee true by construction
                return self.opt.step_fn(loss_fn)
            return self._seed_parallel_step_fn(loss_fn)
        if self.plan.kind == "async_worker":
            raise ValueError(
                "the async_worker plan has no monolithic step function — "
                "drive it through repro.distributed.async_zo.AsyncZOWorker "
                "(contribution_eval_fn / apply_contribution)")
        raise ValueError(
            "the replay plan is ledger-driven (no forward passes): call "
            "StepProgram.replay(params0, ledger) instead of step_fn")

    def compiled_step_fn(self, loss_fn, donate: bool = True) -> Callable:
        """``step_fn`` jitted with the parameter buffer DONATED (matching
        ``train.loop``'s jit): θ, the perturbed views, and θ_new alias one
        HBM allocation across the perturb → loss → update chain instead of
        holding a second parameter-sized buffer live per step — the paper's
        inference-memory property, and the fix for the seed-parallel
        CPU-mesh overhead measured in benchmarks/bench_exec.py.  Callers
        must treat the passed params as consumed and continue from the
        returned tree (``params, state, metrics = step(params, ...)``)."""
        return jax.jit(self.step_fn(loss_fn),
                       donate_argnums=(0,) if donate else ())

    # -- seed-parallel lowering (n_groups > 1; n == 1 delegates to local) --- #
    def _group_eval(self, loss_fn, params, batch, state, skey, est_state,
                    tf_state, seed_index: int, phase):
        """One group of a seed-parallel step: its estimate at ``params`` on
        its batch slice, and its transform output.  ``phase`` is None when
        the selection schedule has a single phase."""
        est, tf = self.opt.estimator, self.opt.transform
        e = phases.estimate(est, loss_fn, params, batch, skey, est_state,
                            phase)
        ctx = TransformCtx(step=state.step, base_key=state.base_key,
                           key=skey, seed_index=seed_index,
                           n_seeds=self.plan.n_groups, eps=est.eps,
                           dist=est.dist, restore=e.restore,
                           backend=self.opt.backend)
        with jax.named_scope(phases.UPDATE):
            u, tf_state = tf.update(Updates(g=e.projected_grad), tf_state,
                                    ctx)
        if u.final_params is not None:
            # unreachable behind the __init__ applier guard; loud (not
            # silently dropped) if that guard is ever relaxed
            raise ValueError(
                "a transform materialized final_params under a multi-group "
                "plan; group updates are rank-1 applications and cannot "
                "honor it")
        return e, u, tf_state

    @staticmethod
    def _group_result(state: ZOState, est_state, tf_state, gs, losses, lr,
                      aux: dict):
        """The step's new state and metrics from the stacked per-group
        projected grads and losses."""
        g_mean = jnp.mean(gs)
        new_state = ZOState(state.step + 1, state.base_key, est_state,
                            tf_state, g_mean)
        metrics = {"loss": jnp.mean(losses), "projected_grad": g_mean,
                   "lr": lr if lr is not None else jnp.float32(1.0), **aux,
                   "projected_grads": gs.reshape(-1)}
        return new_state, metrics

    def _group_mesh_axis(self):
        """The mesh axis (or axes) that holds one seed group per device, or
        None when the plan's mesh is not purely that group mesh (its batch
        axes hold n_groups devices and every other axis one)."""
        from repro.distributed.sharding import axis_size, batch_axes
        mesh = self.plan.mesh
        if mesh is None:
            return None
        ba = batch_axes(mesh)
        if not ba or axis_size(mesh, ba) != self.plan.n_groups:
            return None
        if any(size != 1 for a, size in mesh.shape.items() if a not in ba):
            return None
        return ba if len(ba) > 1 else ba[0]

    def _seed_parallel_step_fn(self, loss_fn) -> Callable:
        opt = self.opt
        est = opt.estimator
        n = self.plan.n_groups
        backend = opt.backend
        batch_seeds = opt.batch_seeds
        sel = opt.selection
        n_phases = 1 if sel is None else int(sel.n_phases)
        group_axis = self._group_mesh_axis()

        def sequential(params: PyTree, state: ZOState, batch, phase):
            """The groups in turn; under a mesh, GSPMD lays them out."""
            skey0 = step_key(state.base_key, state.step)
            p = params
            est_state, tf_state = state.est_state, state.tf_state
            gs, losses, coeffs = [], [], []
            aux: dict = {}
            lr_metric = None
            decay0 = 0.0
            for g in range(n):
                e, u, tf_state = self._group_eval(
                    loss_fn, p, slice_group(batch, g, n), state,
                    group_key(skey0, g, n), est_state, tf_state, g, phase)
                est_state = e.est_state
                # evaluations stay at the step's center; directions are
                # averaged afterwards through the shared write path
                with jax.named_scope(phases.UPDATE):
                    p = e.restore()
                coeffs.append(u.coeff if u.coeff is not None else u.g)
                if g == 0:
                    decay0 = u.decay
                gs.append(u.g)
                losses.append(e.loss)
                if e.aux:
                    aux.update(e.aux)
                lr_metric = u.lr
            p = apply_group_updates(p, skey0, coeffs, decay0, n,
                                    batch_seeds, est.dist, backend,
                                    selection=sel, phase=phase)
            new_state, metrics = self._group_result(
                state, est_state, tf_state, jnp.stack(gs), jnp.stack(losses),
                lr_metric, aux)
            return p, new_state, metrics

        def per_device(params: PyTree, state: ZOState, batch, phase):
            """Device g of the group mesh: group g on its batch slice, the
            per-group scalars all-gathered (the step's only cross-device
            traffic), then all n rank-1 updates, so θ stays replicated."""
            skey0 = step_key(state.base_key, state.step)
            # seed_index 0: the decay slot is group 0's (add_weight_decay
            # keys it on the index, which is traced here); every other field
            # is this device's group
            e, u, tf_state = self._group_eval(
                loss_fn, params, batch, state,
                group_key(skey0, jax.lax.axis_index(group_axis), n),
                state.est_state, state.tf_state, 0, phase)
            gather = functools.partial(jax.lax.all_gather,
                                       axis_name=group_axis)
            coeffs = gather(u.coeff if u.coeff is not None else u.g)
            aux = {k: gather(v)[n - 1] for k, v in (e.aux or {}).items()}
            with jax.named_scope(phases.UPDATE):
                center = e.restore()
            p = apply_group_updates(center, skey0,
                                    [coeffs[i] for i in range(n)], u.decay,
                                    n, batch_seeds, est.dist, backend,
                                    selection=sel, phase=phase)
            new_state, metrics = self._group_result(
                state, e.est_state, tf_state, gather(u.g), gather(e.loss),
                u.lr, aux)
            return p, new_state, metrics

        def body(params: PyTree, state: ZOState, batch, phase: int):
            phase = phase if n_phases > 1 else None
            # a stateful estimator or chain threads its state group to
            # group, which independent devices cannot reproduce: it keeps
            # the sequential body, as does any mesh with other axes
            if group_axis is None or jax.tree_util.tree_leaves(
                    (state.est_state, state.tf_state)):
                return sequential(params, state, batch, phase)
            bspec = jax.tree_util.tree_map(
                lambda x: P(group_axis) if jnp.ndim(x) else P(), batch)
            return jax.shard_map(
                functools.partial(per_device, phase=phase),
                mesh=self.plan.mesh, in_specs=(P(), P(), bspec),
                out_specs=P(), check_vma=False)(params, state, batch)

        if n_phases == 1:
            def step(params: PyTree, state: ZOState, batch):
                return body(params, state, batch, 0)
        else:
            # block schedule: same static-phase lax.switch dispatch as the
            # local facade — phase(t) is a pure function of the step counter,
            # so the selection schedule is identical under every plan
            branches = [functools.partial(body, phase=ph)
                        for ph in range(n_phases)]

            def step(params: PyTree, state: ZOState, batch):
                return jax.lax.switch(sel.phase_at(state.step), branches,
                                      params, state, batch)

        return step

    def shardings(self, params_like: PyTree, batch_like=None,
                  state_like=None):
        """(params, state, batch) ``in_shardings`` for jitting the step under
        the plan's mesh: parameters through the ``sharding.py`` rule engine,
        optimizer state replicated when ``state_like`` is given (``None`` is
        returned otherwise — GSPMD then picks the layout; the state is a few
        scalars, so either is safe), batch leaves split on their leading axis
        over the mesh's batch axes — MeZO's cross-device traffic stays the
        loss scalars."""
        mesh = self.plan.mesh
        if mesh is None:
            raise ValueError("this plan carries no mesh; construct it as "
                             "exec.seed_parallel(n, mesh=...)")
        from jax.sharding import NamedSharding

        from repro.distributed.sharding import batch_axes, param_shardings
        pshard = param_shardings(params_like, mesh)
        sshard = None
        if state_like is not None:
            sshard = jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P()), state_like)
        ba = batch_axes(mesh) or None
        if batch_like is None:
            bshard = None
        else:
            bshard = jax.tree_util.tree_map(
                lambda x: NamedSharding(
                    mesh, P(ba if ba and len(ba) > 1 else (ba[0] if ba else None))
                    if jnp.ndim(x) else P()),
                batch_like)
        return pshard, sshard, bshard

    # -- async building blocks (consumed by distributed.async_zo) ----------- #
    def contribution_eval_fn(self, loss_fn, worker: int,
                             est_state=None) -> Callable:
        """jit-able ``fn(params, base_key, step, batch, phase=0) ->
        (g, lr, loss)``: evaluate this worker's seed group of one step
        through the estimator and the scalar transform chain (what goes on
        the wire is the post-transform g — the same scalar a seed-parallel
        step records).  ``phase`` is the step's static block-schedule phase
        (jit it with ``static_argnames=("phase",)``); the worker derives it
        from its step counter — the same t-pure function every plan uses."""
        opt = self.opt
        est, tf = opt.estimator, opt.transform
        n = self.plan.n_groups
        sel = opt.selection

        def fn(params, base_key, step, batch, phase=0):
            skey = group_stream_key(base_key, step, worker, n)
            e_state = (est_state if est_state is not None
                       else est.init(None, base_key))
            e = phases.estimate(est, loss_fn, params, batch, skey, e_state,
                                None if sel is None else phase)
            ctx = TransformCtx(step=step, base_key=base_key, key=skey,
                               seed_index=worker, n_seeds=n, eps=est.eps,
                               dist=est.dist, restore=e.restore,
                               backend=opt.backend)
            with jax.named_scope(phases.UPDATE):
                u, _ = tf.update(Updates(g=e.projected_grad), tf.init(None),
                                 ctx)
            lr = u.lr if u.lr is not None else jnp.float32(1.0)
            return u.g, lr, e.loss

        return fn

    def apply_contribution_fn(self) -> Callable:
        """jit-able ``fn(params, skey0, group, g, lr, decay_on, phase=0) ->
        params`` applying one group's contribution for the step whose key is
        ``skey0`` — the identical floats a ledger replay of that group
        performs.  ``group`` stays a DYNAMIC (traced) argument: it only feeds
        the ``fold_in`` inside ``group_key``, so one compiled apply kernel
        serves every worker id (baking it static would retrace once per
        peer).  ``phase`` IS static (it selects which leaves the update
        touches — jit with ``static_argnames=("phase",)``): one compiled
        kernel per schedule phase, not per peer."""
        opt = self.opt
        n = self.plan.n_groups
        batch_seeds = opt.batch_seeds
        dist = opt.estimator.dist
        backend = opt.backend
        wd = opt.weight_decay
        sel = opt.selection

        def fn(params, skey0, group, g, lr, decay_on, phase=0):
            coeff = (lr / n) * g
            decay = (lr * wd) * decay_on
            return apply_group_update(params, skey0, group, n, coeff, decay,
                                      batch_seeds, dist, backend,
                                      selection=sel, phase=phase)

        return fn

    # -- ledger replay ------------------------------------------------------ #
    def replay(self, params0: PyTree, ledger, from_idx: int = 0,
               to_idx: Optional[int] = None) -> PyTree:
        """Reconstruct parameters from a scalar ledger — no forward passes,
        no data (paper §2.1), under ANY plan's records.

        Ledger-coordinate checks mirror the artifact stamps: backend
        (``BackendMismatchError``), batch_seeds, and n_groups
        (``PlanMismatchError``).  A program built on the ``replay()`` plan is
        ledger-driven and adopts the ledger's n_groups; any other plan must
        match it (that is the resume path, where training continues under the
        active schedule)."""
        opt = self.opt
        check_replay_backend(getattr(ledger, "backend", None),
                             self.backend_name, "trajectory ledger")
        check_replay_selection(getattr(ledger, "selection", None),
                               opt.selection_spec, "trajectory ledger",
                               getattr(ledger, "sel_phase", 0),
                               opt.selection_phase)
        led_bs = int(getattr(ledger, "batch_seeds", 1))
        if len(ledger.steps) and led_bs != int(opt.batch_seeds):
            raise ValueError(
                f"trajectory ledger records {led_bs} seed scalar(s) per "
                f"group but the optimizer evaluates batch_seeds="
                f"{opt.batch_seeds}; the seed fold schedule (and the "
                "per-step g shape) differ, so replay would misapply the "
                "updates — replay with a matching fzoo(batch_seeds=...) "
                "composition")
        n = led_n = int(getattr(ledger, "n_groups", 1))
        if self.plan.kind != "replay":    # the replay plan is ledger-driven
            check_replay_plan(led_n, self.n_groups, "trajectory ledger",
                              recorded_kind=getattr(ledger, "exec_plan", None),
                              active_kind=self.plan.kind)
        if n > 1:
            if opt.info.get("applier"):
                raise ValueError(
                    f"{opt.name}: scalar-ledger replay cannot reproduce "
                    "applier transforms (scale_by_zo_adam / trace); resume "
                    "from a full state checkpoint instead of a ledger tail")
            if not opt.estimator.replayable:
                raise ValueError(
                    f"{opt.name}: the {opt.estimator.name!r} estimator "
                    "updates along D·z (Definition 6), which a (seed, g, lr) "
                    "ledger entry cannot reproduce; resume from a full state "
                    "checkpoint")
            if opt.info.get("lr_at") is None:
                raise ValueError(
                    f"{opt.name}: multi-group replay reconstructs "
                    "coefficients as (η/n)·g from the recorded learning "
                    "rate, but this transform chain has no "
                    "scale_by_schedule — the live step applied raw g, which "
                    "a (seed, g, lr) entry cannot re-scale; resume from a "
                    "full state checkpoint")
        base_key = jax.random.PRNGKey(ledger.base_seed)
        to_idx = len(ledger.steps) if to_idx is None else to_idx
        batch_seeds = int(opt.batch_seeds)
        sel = opt.selection
        dist = opt.estimator.dist if n > 1 else None
        backend = opt.backend if n > 1 else None
        wd = opt.weight_decay if n > 1 else None

        # the block-schedule phase is static (it decides WHICH leaves the
        # rank-1 update touches), so it is a static jit argument: replay
        # compiles one kernel per phase, exactly as the live step's
        # lax.switch carries one branch per phase
        @functools.partial(jax.jit, static_argnames=("phase",))
        def one(params, step, g, lr, phase=0):
            skey0 = step_key(base_key, step)
            if n == 1:
                # single-stream entries: the optimizer's own replay primitive
                # (bitwise with the local and seed_parallel(1) plans)
                if sel is None:
                    return opt.replay_update(params, skey0, g, lr)
                return opt.replay_update(params, skey0, g, lr, phase=phase)
            g_mat = jnp.reshape(jnp.asarray(g), (n, batch_seeds))
            coeffs = [(lr / n) * (g_mat[i] if batch_seeds > 1
                                  else g_mat[i, 0]) for i in range(n)]
            return apply_group_updates(params, skey0, coeffs, lr * wd, n,
                                       batch_seeds, dist, backend,
                                       selection=sel, phase=phase)

        p = params0
        for i in range(from_idx, to_idx):
            ph = 0 if sel is None else int(sel.phase_at(int(ledger.steps[i])))
            p = one(p, jnp.int32(ledger.steps[i]),
                    jnp.float32(ledger.grads[i]), jnp.float32(ledger.lrs[i]),
                    phase=ph)
        return p

    def replay_update(self, params, skey, g, lr):
        """Single-entry delegation (kept for protocol compatibility)."""
        return self.opt.replay_update(params, skey, g, lr)


def as_step_program(optimizer, plan: Optional[ExecPlan] = None) -> StepProgram:
    """Accept a ``StepProgram`` or anything ``as_zo_optimizer`` accepts (a
    protocol conformer, a legacy config, a backprop baseline) — the
    compatibility seam that lets the training loop, checkpoint recovery, and
    trajectory replay consume the engine while old call sites still pass
    bare optimizers."""
    if isinstance(optimizer, StepProgram):
        if plan is not None and plan != optimizer.plan:
            raise ValueError("optimizer is already a StepProgram with a "
                             f"{optimizer.plan.kind!r} plan; cannot re-plan "
                             f"it as {plan.kind!r} — build a new StepProgram")
        return optimizer
    return StepProgram(optimizer, plan)
