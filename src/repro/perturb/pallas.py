"""``pallas`` backend: z generated tile-by-tile inside VMEM by the fused
Pallas kernel — the paper's in-place trick taken one level further down the
memory hierarchy (z never exists in HBM on TPU).

Promoted from the legacy-only ``kernels/zo_fused/ops.py`` path (which was
reachable only through ``mezo_step_kernel``) to a first-class backend: every
estimator × transform composition in ``repro.zo`` can now run HBM-free by
selecting ``backend="pallas"``.

RNG: murmur3-finalizer counter hash + Box–Muller (32-bit ops only, TPU
native), seeded per leaf from ``StreamRef.leaf_seed`` — position-stable, so
the same (StreamRef, leaf) always yields the same z regardless of how the
tree around it changes or how leaves are padded to the kernel's blocked view.
The pure-jnp oracle in ``kernels/zo_fused/ref.py`` implements the identical
arithmetic bit-for-bit.

Interpret mode: on the CPU the kernel runs under
``pallas_call(..., interpret=True)`` (exact same arithmetic, evaluated with
jnp ops), so CPU CI and laptops exercise the real backend semantics.
``get_backend("pallas")`` compiles on a TPU, interprets on the CPU and raises
on any other platform (``repro.kernels.interpret_default``);
``get_backend("pallas-interpret")`` forces interpret mode by name.

Supported distributions: gaussian (Box–Muller), rademacher (the sign of one
counter stream, generated in-kernel), and sphere — the kernel-fused two-pass
rescale: pass 1 measures ‖z‖² tile-by-tile with the ``zo_sqnorm`` kernel (z
is generated in VMEM and reduced, never materialized), pass 2 folds
sqrt(d)/‖z‖ into the affine b coefficient of any affine kernel.  The sphere
direction IS the gaussian counter stream (same salt-1/2 reads), so adding it
changes no gaussian/rademacher bits and needs no ``stream_id`` bump.

Multi-seed work goes through the fused-multi kernels
(``kernels/zo_fused/multi.py``): ``perturb_many`` fans B perturbed views out
of one HBM read of x per tile, and ``affine_many`` folds B chained rank-1
updates into one HBM round-trip of θ — both under bitwise stacked/sequential
-singles contracts.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.zo_fused.kernel import (BLOCK_COLS, BLOCK_ROWS,
                                           zo_affine_2d, zo_affine_2d_batched)
from repro.kernels.zo_fused.multi import (zo_affine_chain_2d,
                                          zo_affine_multi_2d, zo_sqnorm_2d)
from repro.kernels.zo_fused.rows import (tile_plan, zo_affine_2d_rows,
                                         zo_affine_chain_2d_rows,
                                         zo_affine_multi_2d_rows,
                                         zo_sqnorm_2d_rows)
from repro.perturb.base import PerturbBackend, per_stream_scales
from repro.perturb.stream import _LEAF_STRIDE, StreamRef
from repro.tree_utils import PyTree, tree_map_with_index


def _blocked_view(x: jnp.ndarray) -> tuple:
    """Pad/reshape an arbitrary-shape leaf to the kernel's 2-D blocked view.
    The padding tail consumes counter indices but its z values are discarded
    (the counter stream is position-stable, so the same (leaf, seed) always
    yields the same z regardless of how the tree around it changes).  One
    implementation for the single-seed and batched wrappers — the blocking
    scheme is part of the bitwise batched == singles contract."""
    n = x.size
    width = BLOCK_ROWS * BLOCK_COLS
    n_pad = ((n + width - 1) // width) * width
    return jnp.pad(x.reshape(-1), (0, n_pad - n)).reshape(-1, BLOCK_COLS), n


def _rows_plan(n: int, blocks) -> Optional[tuple]:
    """Static tile plan ``(sel_tiles, masked)`` of a *partial* sub-leaf
    ``RowBlocks``, or ``None`` for whole-leaf semantics (no plan, or every
    block selected — ``rows(..., k=1)`` must route through the unmodified
    full kernel so it stays bitwise ≡ ``full``)."""
    if blocks is None or blocks.all_selected:
        return None
    sel, pure = tile_plan(n, blocks.block_elems, blocks.k, blocks.phase)
    return sel, not pure


@functools.partial(jax.jit, static_argnames=("interpret", "dist", "blocks"))
def zo_affine(x: jnp.ndarray, seed, a, b, interpret: bool = True,
              dist: str = "gaussian", blocks=None) -> jnp.ndarray:
    """y = a·x + b·z(seed) for an arbitrary-shape leaf (blocked view, see
    ``_blocked_view``).  A partial ``blocks`` plan (``repro.select.RowBlocks``,
    static) launches only the tiles covering selected row-blocks — unselected
    rows are never read, never written, and generate no z."""
    flat2d, n = _blocked_view(x)
    plan = _rows_plan(n, blocks)
    if plan is None:
        y = zo_affine_2d(flat2d, jnp.asarray(seed, jnp.int32), a, b,
                         interpret=interpret, dist=dist)
    else:
        sel, masked = plan
        y = zo_affine_2d_rows(flat2d, jnp.asarray(seed, jnp.int32), a, b,
                              sel, blocks.block_elems, blocks.k,
                              blocks.phase, masked, interpret=interpret,
                              dist=dist)
    return y.reshape(-1)[:n].reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("interpret", "dist", "blocks"))
def zo_affine_batched(x: jnp.ndarray, seeds: jnp.ndarray, a, b,
                      interpret: bool = True,
                      dist: str = "gaussian", blocks=None) -> jnp.ndarray:
    """y[j] = a·x + b·z(seeds[j]) for an arbitrary-shape leaf, one launch.

    Same blocked/padded view as :func:`zo_affine`; the kernel's batch grid
    axis generates one z-stream per seed against each resident x tile, so the
    result's batch slices are bitwise-equal to B separate ``zo_affine`` calls
    while x is read once per tile instead of B times.  A partial ``blocks``
    plan routes through the multi-rows kernel with the shared (a, b)
    broadcast per stream — the per-tile arithmetic is the same
    ``_tile_affine`` on the same scalar values, so batch slices stay
    bitwise-equal to rows singles.
    """
    flat2d, n = _blocked_view(x)
    plan = _rows_plan(n, blocks)
    seeds = jnp.asarray(seeds, jnp.int32)
    if plan is None:
        y = zo_affine_2d_batched(flat2d, seeds, a, b,
                                 interpret=interpret, dist=dist)
    else:
        sel, masked = plan
        (batch,) = seeds.shape
        a_vec = jnp.broadcast_to(jnp.asarray(a, jnp.float32), (batch,))
        b_vec = jnp.broadcast_to(jnp.asarray(b, jnp.float32), (batch,))
        y = zo_affine_multi_2d_rows(flat2d, seeds, a_vec, b_vec, sel,
                                    blocks.block_elems, blocks.k,
                                    blocks.phase, masked,
                                    interpret=interpret, dist=dist)
    batch = y.shape[0]
    return y.reshape(batch, -1)[:, :n].reshape((batch,) + x.shape)


@functools.partial(jax.jit, static_argnames=("interpret", "dist", "blocks"))
def zo_affine_multi(x: jnp.ndarray, seeds: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, interpret: bool = True,
                    dist: str = "gaussian", blocks=None) -> jnp.ndarray:
    """y[j] = a_j·x + b_j·z(seeds[j]) for an arbitrary-shape leaf, one
    launch — :func:`zo_affine_batched` generalized to per-stream affine
    coefficients (the fused-multi fan-out kernel).  Batch slices are
    bitwise-equal to per-stream ``zo_affine`` singles, sub-leaf plans
    included."""
    flat2d, n = _blocked_view(x)
    plan = _rows_plan(n, blocks)
    if plan is None:
        y = zo_affine_multi_2d(flat2d, jnp.asarray(seeds, jnp.int32), a, b,
                               interpret=interpret, dist=dist)
    else:
        sel, masked = plan
        y = zo_affine_multi_2d_rows(flat2d, jnp.asarray(seeds, jnp.int32),
                                    a, b, sel, blocks.block_elems, blocks.k,
                                    blocks.phase, masked,
                                    interpret=interpret, dist=dist)
    batch = y.shape[0]
    return y.reshape(batch, -1)[:, :n].reshape((batch,) + x.shape)


@functools.partial(jax.jit, static_argnames=("interpret", "dist", "blocks"))
def zo_affine_chain(x: jnp.ndarray, seeds: jnp.ndarray, a: jnp.ndarray,
                    b: jnp.ndarray, interpret: bool = True,
                    dist: str = "gaussian", blocks=None) -> jnp.ndarray:
    """Chained y = fold_j (a_j·y + b_j·z(seeds[j])) for an arbitrary-shape
    leaf in ONE launch — bitwise-equal to the sequential per-stream
    ``zo_affine`` chain while x round-trips HBM once instead of B times.
    Under a partial ``blocks`` plan only selected tiles fold; unselected
    rows keep their bits."""
    flat2d, n = _blocked_view(x)
    plan = _rows_plan(n, blocks)
    if plan is None:
        y = zo_affine_chain_2d(flat2d, jnp.asarray(seeds, jnp.int32), a, b,
                               interpret=interpret, dist=dist)
    else:
        sel, masked = plan
        y = zo_affine_chain_2d_rows(flat2d, jnp.asarray(seeds, jnp.int32),
                                    a, b, sel, blocks.block_elems, blocks.k,
                                    blocks.phase, masked,
                                    interpret=interpret, dist=dist)
    return y.reshape(-1)[:n].reshape(x.shape)


def leaf_seed(seed: int, leaf_idx: int) -> jnp.ndarray:
    """Legacy per-leaf counter-seed schedule (kept bit-compatible; the same
    stride now lives in ``StreamRef.leaf_seed``)."""
    return jnp.asarray(seed, jnp.int32) + jnp.int32(_LEAF_STRIDE) * jnp.int32(leaf_idx)


def perturb_tree(params: PyTree, seed, scale, interpret: bool = True) -> PyTree:
    """θ + scale·z over a pytree (kernel-backed analogue of the xla perturb)."""
    return tree_map_with_index(
        lambda i, p: zo_affine(p, leaf_seed(seed, i), 1.0, scale,
                               interpret=interpret)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)


def update_tree(params: PyTree, seed, projected_grad, lr,
                weight_decay: float = 0.0, interpret: bool = True) -> PyTree:
    """θ·(1−ηλ) − η·g·z over a pytree (Algorithm 1's descent loop)."""
    a = 1.0 - lr * weight_decay
    return tree_map_with_index(
        lambda i, p: zo_affine(p, leaf_seed(seed, i), a, -lr * projected_grad,
                               interpret=interpret)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)


def mezo_step_kernel(loss_fn, params: PyTree, batch, seed: int, eps: float,
                     lr: float, weight_decay: float = 0.0,
                     interpret: bool = True):
    """One full MeZO step with every perturbation running through the Pallas
    kernel.  Legacy entry point — new code composes ``zo.mezo(...,
    backend="pallas")`` instead, which routes the same kernel through the
    estimator × transform protocol."""
    p_plus = perturb_tree(params, seed, eps, interpret)
    l_plus = loss_fn(p_plus, batch)
    p_minus = perturb_tree(p_plus, seed, -2.0 * eps, interpret)
    l_minus = loss_fn(p_minus, batch)
    g = (l_plus - l_minus) / (2.0 * eps)
    restored = perturb_tree(p_minus, seed, eps, interpret)
    new_params = update_tree(restored, seed, g, lr, weight_decay, interpret)
    return new_params, g, 0.5 * (l_plus + l_minus)


# --------------------------------------------------------------------------- #
# Backend adapter
# --------------------------------------------------------------------------- #
class PallasBackend(PerturbBackend):
    """Fused-kernel z streams: VMEM generation on TPU, interpret mode on CPU.

    Selection-aware: a ``StreamRef`` carrying a ``repro.select.Selection``
    scopes every method to the selected leaves — unselected leaves get no
    kernel launch at all (zero z generation, zero writes)."""

    name = "pallas"
    dists = frozenset({"gaussian", "rademacher", "sphere"})
    # z2: transcendental-free polynomial Box–Muller (deterministic across
    # jitted graphs).  z1 artifacts (jnp.log/cos bits) refuse to replay.
    # (The in-kernel rademacher stream landed under z2 — a new dist adds a
    # stream, it does not change the gaussian bits, so no bump.  sphere is
    # the gaussian stream × a wrapper-level sqrt(d)/‖z‖ scalar — the counter
    # reads are identical, so again no bump.)
    stream_version = 2

    def __init__(self, interpret: Optional[bool] = None):
        if interpret is None:
            interpret = interpret_default()
        self.interpret = bool(interpret)

    def _pin_scalars(self, *vals):
        """Pin the affine coefficients' rounding under interpret mode.

        The scalar algebra feeding the kernel (a = 1 − η·λ, b = decay·ε − η·g)
        contains mul-feeding-add/sub patterns that XLA may or may not contract
        into FMAs depending on the surrounding graph — a 1-ulp difference in
        ``a`` shifts every parameter by ~ulp(θ), breaking the bitwise
        live-step == ledger-replay contract.  Barriering the operands forces
        the separately-rounded form in every graph (see kernel.py's ``_pin``).
        """
        vals = tuple(jnp.asarray(v, jnp.float32) for v in vals)
        if not self.interpret:
            return vals
        return jax.lax.optimization_barrier(vals)

    @staticmethod
    def _leaf_blocks(blocks, i: int):
        """Static sub-leaf plan of leaf ``i``, or ``None`` for whole-leaf
        semantics (no ``rows`` selection, or every block selected — the
        route that keeps ``rows(..., k=1)`` bitwise ≡ ``full``)."""
        if blocks is None:
            return None
        rb = blocks[i]
        if rb is None or rb.all_selected:
            return None
        return rb

    def _map(self, params: PyTree, ref: StreamRef, fn) -> PyTree:
        seed = ref.counter_seed()
        mask = ref.selection_mask(params)
        blocks = ref.selection_blocks(params)
        return tree_map_with_index(
            lambda i, p: fn(p, leaf_seed(seed, i), i,
                            self._leaf_blocks(blocks, i))
            if jnp.issubdtype(p.dtype, jnp.floating)
            and (mask is None or mask[i]) else p, params)

    def _sphere_scale(self, params: PyTree, ref: StreamRef) -> jnp.ndarray:
        """sqrt(d)/‖z(ref)‖ over the selected floating leaves — pass 1 of the
        kernel-fused two-pass sphere rescale.  ‖z‖² is accumulated leaf by
        leaf by the ``zo_sqnorm`` kernel on the SAME per-leaf counter streams
        the affine kernels read (z is generated in VMEM and reduced, never
        materialized); d counts the same subspace.  Under a sub-leaf plan the
        sphere lives in the selected row-blocks: the ``zo_sqnorm_rows``
        kernel visits only selected tiles, and d counts selected elements.
        Every float stage is pinned so the scalar rounds identically in
        every consuming graph (perturb / fused restore / rank-1 / the fused
        multi passes) — the live == replay bitwise contract extends to
        sphere."""
        seed = ref.counter_seed()
        mask = ref.selection_mask(params)
        blocks = ref.selection_blocks(params)
        d = 0
        sq = None
        for i, p in enumerate(jax.tree_util.tree_leaves(params)):
            if not jnp.issubdtype(p.dtype, jnp.floating):
                continue
            if mask is not None and not mask[i]:
                continue
            rb = self._leaf_blocks(blocks, i)
            if rb is None:
                d += int(p.size)
                part = zo_sqnorm_2d(int(p.size), leaf_seed(seed, i),
                                    interpret=self.interpret)
            else:
                sel, _ = tile_plan(int(p.size), rb.block_elems, rb.k,
                                   rb.phase)
                d += rb.selected_elems()
                part = zo_sqnorm_2d_rows(int(p.size), leaf_seed(seed, i),
                                         sel, rb.block_elems, rb.k, rb.phase,
                                         interpret=self.interpret)
            sq = part if sq is None else self._pin_scalars(sq + part)[0]
        if sq is None:
            raise ValueError(
                "sphere perturbation needs at least one selected floating "
                "leaf (the sqrt(d)/‖z‖ rescale is undefined on an empty "
                "subspace)")
        (ratio,) = self._pin_scalars(jnp.float32(d) / sq)
        return self._pin_scalars(jnp.sqrt(ratio))[0]

    def perturb(self, params: PyTree, ref: StreamRef, scale,
                dist: str = "gaussian") -> PyTree:
        self.check_dist(dist)
        if dist == "sphere":
            # pass 2: the global rescale rides the affine b coefficient of
            # the plain gaussian-stream kernel — one extra scalar mul, no
            # second z generation
            (b,) = self._pin_scalars(
                jnp.asarray(scale, jnp.float32) *
                self._sphere_scale(params, ref))
            return self._map(params, ref,
                             lambda p, s, i, rb: zo_affine(
                                 p, s, 1.0, b, interpret=self.interpret,
                                 dist="gaussian", blocks=rb))
        return self._map(params, ref,
                         lambda p, s, i, rb: zo_affine(
                             p, s, 1.0, scale, interpret=self.interpret,
                             dist=dist, blocks=rb))

    def fused_restore_update(self, params_minus: PyTree, ref: StreamRef, eps,
                             lr_g, weight_decay=0.0,
                             dist: str = "gaussian") -> PyTree:
        # decay·(θ − εz + εz) − η·g·z  =  decay·θ_minus + (decay·ε − η·g)·z:
        # restore AND descent collapse into a single kernel pass per leaf
        # (one z regeneration, never in HBM) — one fewer pass than the xla
        # backend needs for the same fusion.  Unselected leaves were never
        # perturbed and pass through completely (decay included).
        self.check_dist(dist)
        eps_, lr_g_, wd_ = self._pin_scalars(eps, lr_g, weight_decay)
        decay = 1.0 - wd_
        (de,) = self._pin_scalars(decay * eps_)
        b = de - lr_g_
        kdist = dist
        if dist == "sphere":
            (b,) = self._pin_scalars(
                b * self._sphere_scale(params_minus, ref))
            kdist = "gaussian"
        return self._map(params_minus, ref,
                         lambda p, s, i, rb: zo_affine(
                             p, s, decay, b, interpret=self.interpret,
                             dist=kdist, blocks=rb))

    def apply_rank1(self, params: PyTree, ref: StreamRef, coeff,
                    decay_term=0.0, dist: str = "gaussian",
                    d_tree: Optional[PyTree] = None) -> PyTree:
        self.check_dist(dist)
        coeff_, decay_ = self._pin_scalars(coeff, decay_term)
        a = 1.0 - decay_
        d_leaves = (jax.tree_util.tree_leaves(d_tree)
                    if d_tree is not None else None)
        # unlike xla's apply_rank1 (whose sphere callers pre-scale the
        # coefficient), the pallas primitive applies the sphere rescale
        # itself — live steps, affine_many, and ledger replay all route
        # through here, so the scalar is folded identically everywhere
        sph = self._sphere_scale(params, ref) if dist == "sphere" else None
        kdist = "gaussian" if dist == "sphere" else dist

        def one(p, s, i, rb):
            b = -coeff_ if d_leaves is None else -coeff_ * d_leaves[i]
            if sph is not None:
                (b,) = self._pin_scalars(b * sph)
            return zo_affine(p, s, a, b, interpret=self.interpret, dist=kdist,
                             blocks=rb)

        return self._map(params, ref, one)

    def leaf_z(self, ref: StreamRef, leaf_index: int, like: jnp.ndarray,
               dist: str = "gaussian") -> jnp.ndarray:
        self.check_dist(dist)
        zeros = jnp.zeros(like.shape, like.dtype if
                          jnp.issubdtype(like.dtype, jnp.floating)
                          else jnp.float32)
        # sphere: direction only, like the xla backend — the global
        # sqrt(d)/‖z‖ rescale needs the full tree and is applied by callers
        kdist = "gaussian" if dist == "sphere" else dist
        return zo_affine(zeros, ref.leaf_seed(leaf_index), 0.0, 1.0,
                         interpret=self.interpret, dist=kdist)

    def perturb_many(self, params: PyTree, refs: Sequence[StreamRef], scale,
                     dist: str = "gaussian") -> PyTree:
        """Genuinely batched θ + scale_j·z(ref_j): one kernel launch per leaf
        generates B z-streams per VMEM tile (x read once per tile) —
        bitwise-equal to stacking per-ref ``perturb`` calls, contract-tested
        in tests/test_perturb_backend.py.  A shared scalar ``scale`` runs the
        original batched kernel; per-stream scales (and sphere, whose
        per-stream ‖z_j‖ rescales differ) run the fused-multi fan-out with
        per-stream b_j.  Unselected leaves get no launch — they ride along
        as a copy-free broadcast view, bitwise what stacking masked singles
        yields."""
        self.check_dist(dist)
        if not refs:
            raise ValueError("perturb_many needs at least one StreamRef")
        mask = refs[0].selection_mask(params)
        blocks = refs[0].selection_blocks(params)
        seeds0 = jnp.stack([r.counter_seed() for r in refs])
        per = per_stream_scales(scale, len(refs))
        kdist = dist
        if dist == "sphere":
            base = [scale] * len(refs) if per is None else per
            per = [self._pin_scalars(jnp.asarray(s, jnp.float32) *
                                     self._sphere_scale(params, r))[0]
                   for s, r in zip(base, refs)]
            kdist = "gaussian"
        if per is not None:
            b_vec = jnp.stack([jnp.asarray(s, jnp.float32) for s in per])
            a_vec = jnp.ones_like(b_vec)

        def one(i, p):
            if not jnp.issubdtype(p.dtype, jnp.floating) or \
                    (mask is not None and not mask[i]):
                return jnp.broadcast_to(p, (len(refs),) + p.shape)
            seeds = seeds0 + jnp.int32(_LEAF_STRIDE) * jnp.int32(i)
            rb = self._leaf_blocks(blocks, i)
            if rb is not None:
                # partial sub-leaf plan: stack per-stream single-rows
                # launches — the EXACT graph ``perturb`` runs per stream, so
                # the bitwise many ≡ stacked-singles contract holds by
                # construction.  (The multi-rows kernel is bitwise against
                # the full multi kernel, but pairing it with the single-rows
                # graph trips the cross-graph FMA-contraction caveat in
                # kernel.py's ``_pin`` — ~1 ulp on rare elements — so the
                # fan-out fusion is not used here.)  Tiles are still
                # trace-time skipped: B × selected bytes, never B × leaf.
                bs = ([jnp.asarray(scale, jnp.float32)] * len(refs)
                      if per is None else
                      [b_vec[j] for j in range(len(refs))])
                return jnp.stack([
                    zo_affine(p, seeds[j], 1.0, bs[j],
                              interpret=self.interpret, dist=kdist,
                              blocks=rb)
                    for j in range(len(refs))])
            if per is None:
                return zo_affine_batched(p, seeds, 1.0, scale,
                                         interpret=self.interpret, dist=kdist)
            return zo_affine_multi(p, seeds, a_vec, b_vec,
                                   interpret=self.interpret, dist=kdist)

        return tree_map_with_index(one, params)

    def affine_many(self, params: PyTree, refs: Sequence[StreamRef],
                    coeffs: Sequence, decay_terms: Sequence,
                    dist: str = "gaussian") -> PyTree:
        """The fused chain kernel: all B streams of the multi-seed update
        chain folded per resident VMEM tile — θ round-trips HBM once instead
        of B times.  Bitwise-equal to the base class's sequential
        ``apply_rank1`` fold (contract-tested): per-stream scalars are pinned
        exactly as ``apply_rank1`` pins them, and the chain kernel casts to
        the leaf dtype between streams, reproducing the write/read rounding
        boundary of B separate launches."""
        self.check_dist(dist)
        if not refs:
            raise ValueError("affine_many needs at least one StreamRef")
        if not (len(refs) == len(coeffs) == len(decay_terms)):
            raise ValueError(
                f"affine_many needs one coefficient and one decay term per "
                f"stream; got {len(refs)} refs, {len(coeffs)} coeffs, "
                f"{len(decay_terms)} decay terms")
        mask = refs[0].selection_mask(params)
        blocks = refs[0].selection_blocks(params)
        seeds0 = jnp.stack([r.counter_seed() for r in refs])
        kdist = "gaussian" if dist == "sphere" else dist
        a_list, b_list = [], []
        for j, ref in enumerate(refs):
            coeff_, decay_ = self._pin_scalars(coeffs[j], decay_terms[j])
            a = 1.0 - decay_
            b = -coeff_
            if dist == "sphere":
                # ‖z_j‖ depends only on (seed_j, leaf sizes, mask), never on
                # the evolving θ — the chained fold sees the exact scalars
                # the sequential one would
                (b,) = self._pin_scalars(b * self._sphere_scale(params, ref))
            a_list.append(jnp.asarray(a, jnp.float32))
            b_list.append(jnp.asarray(b, jnp.float32))
        a_vec, b_vec = jnp.stack(a_list), jnp.stack(b_list)

        def one(i, p):
            if not jnp.issubdtype(p.dtype, jnp.floating) or \
                    (mask is not None and not mask[i]):
                return p
            seeds = seeds0 + jnp.int32(_LEAF_STRIDE) * jnp.int32(i)
            return zo_affine_chain(p, seeds, a_vec, b_vec,
                                   interpret=self.interpret, dist=kdist,
                                   blocks=self._leaf_blocks(blocks, i))

        return tree_map_with_index(one, params)
