"""``PerturbBackend`` — the interface every z-generation strategy implements.

One backend = one way of materializing (or *not* materializing) the
perturbation direction z for a parameter tree, given a ``StreamRef``.  The
estimators, the transform chain, trajectory replay, checkpoint recovery, and
the distributed paths all write parameters exclusively through these methods,
so swapping the backend swaps the memory/compute strategy of *every* existing
estimator × transform composition at once.

Supported distribution matrix (see the package docstring for the memory
story):

    ==============  ========  ==========  ========
    backend         gaussian  rademacher  sphere
    ==============  ========  ==========  ========
    ``xla``         yes       yes         yes
    ``pallas``      yes       yes         yes [1]
    ==============  ========  ==========  ========

    [1] sphere on pallas is the kernel-fused two-pass rescale: pass 1
        accumulates ‖z‖² tile-by-tile with the ``zo_sqnorm`` kernel (z is
        measured, never materialized), pass 2 folds sqrt(d)/‖z‖ into the
        affine b coefficient — the gaussian/rademacher counter streams are
        untouched, so no ``stream_id`` bump.

Unsupported combinations raise ``NotImplementedError`` at backend-resolution
or call time with the matrix above spelled out.
"""
from __future__ import annotations

import os
from typing import Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.perturb.stream import StreamRef
from repro.tree_utils import PyTree


class BackendMismatchError(RuntimeError):
    """A seed-replay artifact (ledger / checkpoint) was produced under one
    perturbation backend and is being replayed under another.  The two
    backends generate *different* z for the same StreamRef, so continuing
    would silently reconstruct different parameters — refuse instead."""


def check_replay_backend(recorded: Optional[str], active: Optional[str],
                         what: str) -> None:
    """Raise ``BackendMismatchError`` if a recorded artifact's backend does
    not match the active optimizer's.  ``None`` on either side (a pre-backend
    artifact, or a non-ZO optimizer) skips the check.

    Recorded identities are the backend's ``stream_id`` — the registry name
    plus a ``+zN`` suffix whenever the backend's z-generator arithmetic has
    been revised (same name, different bits).  A same-name different-version
    mismatch gets its own message: selecting another backend cannot fix it."""
    if recorded is None or active is None:
        return
    if recorded != active:
        if recorded.partition("+z")[0] == active.partition("+z")[0]:
            raise BackendMismatchError(
                f"{what} was recorded under z-stream {recorded!r} but this "
                f"build's {active.partition('+z')[0]!r} backend generates "
                f"{active!r}: the backend's z-generator arithmetic changed "
                "between versions, so replay would silently reconstruct "
                "different parameters.  Resume from a full tensor checkpoint "
                "(or re-run) instead of replaying this artifact.")
        raise BackendMismatchError(
            f"{what} was recorded under the {recorded!r} perturbation backend "
            f"but is being replayed under {active!r}; the backends generate "
            "different z streams for the same seed, so replay would silently "
            "reconstruct different parameters.  Re-create the optimizer with "
            f"backend={recorded!r} (e.g. zo.mezo(..., backend={recorded!r})).")


def per_stream_scales(scale, n_refs: int):
    """Normalize ``perturb_many``'s ``scale`` argument: ``None`` for a shared
    scalar (the historical contract — backends keep their original batched
    graph for it), else the per-stream list.  A 1-D sequence/array must have
    one entry per ref."""
    if isinstance(scale, (tuple, list)):
        per = list(scale)
    elif jnp.ndim(scale) == 1:
        per = [scale[j] for j in range(scale.shape[0])]
    else:
        return None
    if len(per) != n_refs:
        raise ValueError(
            f"per-stream scale has {len(per)} entries for {n_refs} refs")
    return per


class PerturbBackend:
    """Interface.  All parameter-writing methods take a ``StreamRef`` and
    regenerate z internally — z is never part of any signature.

    ``dists`` declares the supported distribution set; ``check_dist`` is the
    loud-failure gate (see the matrix in the module docstring).
    """

    name: str = "?"
    dists: frozenset = frozenset()
    # bump when the backend's z-generator arithmetic changes (same name,
    # different bits): artifacts record stream_id, and replay of an
    # older-version artifact refuses instead of silently diverging
    stream_version: int = 1

    @property
    def stream_id(self) -> str:
        """Identity recorded in ledger/checkpoint metadata: the registry name
        plus ``+zN`` for revised z-generator arithmetic (v1 stays bare so
        existing artifacts keep their recorded identity)."""
        return (self.name if self.stream_version == 1
                else f"{self.name}+z{self.stream_version}")

    def check_dist(self, dist: str) -> None:
        if dist not in self.dists:
            raise NotImplementedError(
                f"perturbation backend {self.name!r} does not implement "
                f"dist={dist!r} (supported: {sorted(self.dists)}).  "
                "Distribution matrix — xla: gaussian/rademacher/sphere; "
                "pallas: gaussian/rademacher/sphere (kernel-fused two-pass "
                "rescale).  Use backend='xla' for this dist.")

    # -- core tree operations ----------------------------------------------- #
    def perturb(self, params: PyTree, ref: StreamRef, scale,
                dist: str = "gaussian") -> PyTree:
        """θ + scale · z(ref) — the paper's ``PerturbParameters``."""
        raise NotImplementedError

    def fused_restore_update(self, params_minus: PyTree, ref: StreamRef, eps,
                             lr_g, weight_decay=0.0,
                             dist: str = "gaussian") -> PyTree:
        """From θ − εz produce (1 − η·λ)·θ − η·g·z in one pass (the fusion of
        Algorithm 1's reset and descent loops).  ``weight_decay`` is the
        decoupled decay *term* η·λ."""
        raise NotImplementedError

    def apply_rank1(self, params: PyTree, ref: StreamRef, coeff,
                    decay_term=0.0, dist: str = "gaussian",
                    d_tree: Optional[PyTree] = None) -> PyTree:
        """θ ← (1 − decay_term)·θ − coeff·z(ref)  [z optionally ⊙ d per leaf].
        The single primitive shared by live steps, ledger replay, and async
        application — one implementation per backend keeps all three
        bitwise-consistent."""
        raise NotImplementedError

    def leaf_z(self, ref: StreamRef, leaf_index: int, like: jnp.ndarray,
               dist: str = "gaussian") -> jnp.ndarray:
        """Materialize one leaf's z (shape/dtype of ``like``).  Escape hatch
        for consumers that combine z non-affinely (rescaled-SPSA's d⁻¹⊙z
        perturbation, the materializing ZO-Adam path)."""
        raise NotImplementedError

    # -- batched multi-seed entry point (FZOO-style estimators) ------------- #
    def perturb_many(self, params: PyTree, refs: Sequence[StreamRef], scale,
                     dist: str = "gaussian") -> PyTree:
        """θ + scale_j · z(ref_j) for each ref, stacked on a new leading axis:
        each leaf of the result has shape ``(len(refs), *leaf.shape)``.
        ``scale`` is a shared scalar, or a length-``len(refs)`` sequence of
        per-stream scalars (the ±ε antithetic fan-out of two-point SPSA).

        Default implementation stacks per-ref ``perturb`` calls — bitwise
        identical to the sequential path by construction.  Both shipped
        backends override it with genuinely vectorized generation (``xla``:
        vmapped threefry over stacked keys; ``pallas``: the batched-seed /
        fused-multi kernel, B z-streams per VMEM tile) under the contract
        that the result stays bitwise-equal to stacked singles — the
        extension point batched-seed estimators (``zo.fzoo``; FZOO, Dang
        et al., 2025) build on."""
        self.check_dist(dist)
        if not refs:
            raise ValueError("perturb_many needs at least one StreamRef")
        per = per_stream_scales(scale, len(refs))
        cols = [self.perturb(params, r, scale if per is None else per[j],
                             dist) for j, r in enumerate(refs)]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *cols)

    # -- fused multi-stream write path (one pass, B chained rank-1s) -------- #
    def affine_many(self, params: PyTree, refs: Sequence[StreamRef],
                    coeffs: Sequence, decay_terms: Sequence,
                    dist: str = "gaussian") -> PyTree:
        """The chained multi-stream rank-1 update — the one multi-seed write
        path:

            for j in stream order:
                θ ← (1 − decay_terms[j]) · θ − coeffs[j] · z(ref_j)

        One contract serves FZOO's B folded per-seed updates
        (``zo.updates.apply_rank1_batch``), the seed-parallel engine's
        whole-step group-update chain (``exec.engine.apply_group_updates``),
        and batched ledger replay — all three delegate here.

        This default implementation IS the ``xla`` fallback: a literal
        sequential ``apply_rank1`` fold, bitwise-identical to the pre-fusion
        write path by construction.  The ``pallas`` backend overrides it with
        the fused chain kernel (all B streams folded per resident VMEM tile —
        one HBM round-trip of θ instead of B) under the contract that the
        result stays bitwise-equal to this sequential fold."""
        self.check_dist(dist)
        if not refs:
            raise ValueError("affine_many needs at least one StreamRef")
        if not (len(refs) == len(coeffs) == len(decay_terms)):
            raise ValueError(
                f"affine_many needs one coefficient and one decay term per "
                f"stream; got {len(refs)} refs, {len(coeffs)} coeffs, "
                f"{len(decay_terms)} decay terms")
        p = params
        for ref, coeff, decay in zip(refs, coeffs, decay_terms):
            p = self.apply_rank1(p, ref, coeff, decay, dist)
        return p


# --------------------------------------------------------------------------- #
# Registry
# --------------------------------------------------------------------------- #
_FACTORIES: Dict[str, Callable[[], PerturbBackend]] = {}
_INSTANCES: Dict[str, PerturbBackend] = {}

BackendSpec = Union[None, str, PerturbBackend]


def register_backend(name: str, factory: Callable[[], PerturbBackend]) -> None:
    _FACTORIES[name] = factory
    _INSTANCES.pop(name, None)


def available_backends() -> list:
    return sorted(_FACTORIES)


def get_backend(spec: BackendSpec = None) -> PerturbBackend:
    """Resolve a backend: ``None`` → the session default (the
    ``REPRO_BACKEND`` environment variable, falling back to ``"xla"``); a
    string → the registry (``"xla"``, ``"pallas"``, ``"pallas-interpret"``);
    an instance → itself.  Instances are cached so every consumer of
    ``"xla"`` shares one object.

    The env hook exists for the CI matrix: ``REPRO_BACKEND=pallas pytest``
    runs every composition that didn't pin a backend through the fused
    kernel (interpret mode on the CPU), so the non-default backend is exercised
    on every push without a parallel test tree."""
    if spec is None:
        spec = os.environ.get("REPRO_BACKEND") or "xla"
    if isinstance(spec, PerturbBackend):
        return spec
    if spec not in _FACTORIES:
        raise KeyError(f"unknown perturbation backend {spec!r}; "
                       f"available: {available_backends()}")
    if spec not in _INSTANCES:
        _INSTANCES[spec] = _FACTORIES[spec]()
    return _INSTANCES[spec]
