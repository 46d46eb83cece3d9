"""``zo_fused_multi`` — one VMEM pass serving every multi-seed affine need.

The single-seed kernel in ``kernel.py`` computes y = a·x + b·z(seed) one
stream at a time: every additional stream re-reads every parameter tile from
HBM.  But all multi-seed work in the repo — FZOO's B eval perturbations, the
seed-parallel engine's per-group restore/update chain, batched ledger replay
— shares one shape: *several affine ops against the same resident x*.  This
module generates all B z-streams per resident tile from a single HBM read of
x, in two lowerings:

``zo_affine_multi_2d``  (fan-out)
    y[j] = a_j·x + b_j·z(seed_j), stacked — the batched-seed kernel of PR 3
    generalized from shared (a, b) scalars to per-stream coefficients.  Grid
    is (row_blocks, B) with the row-block axis OUTER, so the x tile stays in
    VMEM while the inner batch axis emits B outputs against it.

``zo_affine_chain_2d``  (chained)
    y = fold_j (a_j·y + b_j·z(seed_j)) — the sequential per-seed update chain
    (B rank-1 applications = B kernel launches = B HBM round-trips of θ)
    collapsed into ONE launch: per resident tile the B streams are generated
    and folded in-register, with the intermediate cast to the output dtype
    between streams so the fold is **bitwise-identical** to B separate
    ``zo_affine_2d`` calls (each single-seed call writes y in x's dtype and
    the next call re-reads it; the in-register cast reproduces exactly that
    rounding boundary).

``zo_sqnorm_2d``  (sphere pass 1)
    Tile-by-tile accumulation of ‖z(seed)‖² over a leaf's real (un-padded)
    elements — the first pass of the two-pass sphere rescale.  Pass 2 is any
    affine kernel with b scaled by sqrt(d)/‖z‖ (the backend folds the scale
    into the affine coefficient, so sphere costs one extra scalar mul per
    stream, never a materialized z).

All three share ``_tile_affine`` / ``z_from_counter`` with the single-seed
kernel — the bitwise fused ≡ stacked-singles contract is those functions
being the only implementation of the per-tile arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.zo_fused.kernel import (BLOCK_COLS, BLOCK_ROWS, SMEM_SPEC,
                                           _pin, _tile_affine, z_from_counter)


# --------------------------------------------------------------------------- #
# Fan-out: B outputs, per-stream coefficients, one x read per tile
# --------------------------------------------------------------------------- #
def _zo_affine_multi_kernel(x_ref, seed_ref, a_ref, b_ref, o_ref, *,
                            cols: int, interpret: bool, dist: str):
    # Grid is (row_blocks, batch): row-block axis OUTER, so the x tile for
    # row-block i stays resident while the inner batch axis walks the B
    # (seed_j, a_j, b_j) triples against it.  Same structure as PR 3's
    # batched kernel; the per-stream a/b reads are the generalization.
    i, j = pl.program_id(0), pl.program_id(1)
    seed = seed_ref[j].astype(jnp.uint32)
    y = _tile_affine(x_ref[...], i, cols, seed, a_ref[j], b_ref[j],
                     interpret, dist)
    o_ref[0, ...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "dist"))
def zo_affine_multi_2d(x: jnp.ndarray, seeds: jnp.ndarray, a: jnp.ndarray,
                       b: jnp.ndarray, interpret: bool = True,
                       dist: str = "gaussian") -> jnp.ndarray:
    """y[j] = a_j·x + b_j·z(seeds[j]) for all j in one launch.

    ``x`` is the (R·BLOCK_ROWS, BLOCK_COLS) blocked view; ``seeds``/``a``/``b``
    are (B,) per-stream vectors.  Each batch slice of the (B, rows, cols)
    result is bitwise-equal to ``zo_affine_2d(x, seeds[j], a[j], b[j])``.
    """
    rows, cols = x.shape
    (batch,) = seeds.shape
    assert rows % BLOCK_ROWS == 0 and cols == BLOCK_COLS, (rows, cols)
    grid = (rows // BLOCK_ROWS, batch)
    return pl.pallas_call(
        functools.partial(_zo_affine_multi_kernel, cols=cols,
                          interpret=interpret, dist=dist),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, cols), lambda i, j: (i, 0)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((1, BLOCK_ROWS, cols), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, rows, cols), x.dtype),
        interpret=interpret,
    )(x, seeds.astype(jnp.int32), jnp.asarray(a, jnp.float32).reshape(-1),
      jnp.asarray(b, jnp.float32).reshape(-1))


# --------------------------------------------------------------------------- #
# Chained: B affine folds per resident tile, one output, one x round-trip
# --------------------------------------------------------------------------- #
def _zo_affine_chain_kernel(x_ref, seed_ref, a_ref, b_ref, o_ref, *,
                            cols: int, n_streams: int, interpret: bool,
                            dist: str):
    # One resident tile, n_streams sequential affine folds.  The cast back to
    # the I/O dtype between streams is load-bearing: a separate single-seed
    # launch writes its y in x's dtype and the next launch re-reads it — the
    # in-register fold must reproduce that rounding boundary to stay bitwise
    # with the per-seed chain.  (The padding tail diverges — the chain keeps
    # b_j·z values there where re-padding would zero them — but padding never
    # feeds a real element: the ops are elementwise.)
    i = pl.program_id(0)
    y = x_ref[...]
    for j in range(n_streams):
        seed = seed_ref[j].astype(jnp.uint32)
        y = _tile_affine(y, i, cols, seed, a_ref[j], b_ref[j],
                         interpret, dist).astype(x_ref.dtype)
    o_ref[...] = y


@functools.partial(jax.jit, static_argnames=("interpret", "dist"))
def zo_affine_chain_2d(x: jnp.ndarray, seeds: jnp.ndarray, a: jnp.ndarray,
                       b: jnp.ndarray, interpret: bool = True,
                       dist: str = "gaussian") -> jnp.ndarray:
    """y = fold over j of (a_j·y + b_j·z(seeds[j])), one launch.

    Bitwise-identical to the sequential per-seed chain
    ``for j: x = zo_affine_2d(x, seeds[j], a[j], b[j])`` on the real (un-
    padded) elements, while reading and writing x through HBM exactly once
    instead of B times — the multi-seed update chain (FZOO's B folded rank-1
    applications, the seed-parallel engine's per-group updates, batched
    ledger replay) at the memory cost of a single rank-1 apply.
    """
    rows, cols = x.shape
    (batch,) = seeds.shape
    assert rows % BLOCK_ROWS == 0 and cols == BLOCK_COLS, (rows, cols)
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_zo_affine_chain_kernel, cols=cols,
                          n_streams=int(batch), interpret=interpret,
                          dist=dist),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, seeds.astype(jnp.int32), jnp.asarray(a, jnp.float32).reshape(-1),
      jnp.asarray(b, jnp.float32).reshape(-1))


# --------------------------------------------------------------------------- #
# Sphere pass 1: ‖z‖² accumulated tile-by-tile (padding masked out)
# --------------------------------------------------------------------------- #
def _sqnorm_tile(row_block, cols: int, seed: jnp.ndarray, n: int,
                 dist: str, pin: bool) -> jnp.ndarray:
    """One tile's Σ z², padding masked (idx ≥ n contributes exactly 0).
    Shared by the kernel body and the ref oracle — the bitwise kernel ==
    oracle contract is this being the only implementation."""
    base = jnp.uint32(row_block * BLOCK_ROWS * cols)
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, cols), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, cols), 1)
    idx = base + row_ids * jnp.uint32(cols) + col_ids
    z = z_from_counter(idx, seed, dist, pin=pin)
    z = _pin(jnp.where(idx < jnp.uint32(n), z, jnp.float32(0.0)), pin)
    return _pin(jnp.sum(_pin(z * z, pin), dtype=jnp.float32), pin)


def _zo_sqnorm_kernel(seed_ref, o_ref, *, cols: int, n: int,
                      interpret: bool, dist: str):
    i = pl.program_id(0)
    seed = seed_ref[0].astype(jnp.uint32)
    part = _sqnorm_tile(i, cols, seed, n, dist, pin=interpret)

    @pl.when(i == 0)
    def _init():
        o_ref[0] = part

    @pl.when(i > 0)
    def _acc():
        o_ref[0] = o_ref[0] + part


@functools.partial(jax.jit, static_argnames=("n", "interpret", "dist"))
def zo_sqnorm_2d(n: int, seed, interpret: bool = True,
                 dist: str = "gaussian") -> jnp.ndarray:
    """‖z(seed)[0:n]‖² as one f32 scalar: pass 1 of the two-pass sphere
    rescale.  The z stream is generated tile-by-tile (never materialized in
    HBM) and the per-tile partial sums accumulate across sequential grid
    steps into a single SMEM scalar, in tile order — the counter indices are
    the same global element positions the affine kernels use, so pass 2
    rescales exactly the z this pass measured.  ``n`` (static) masks the
    padding tail of the blocked view out of the norm."""
    width = BLOCK_ROWS * BLOCK_COLS
    blocks = max(1, -(-int(n) // width))
    return pl.pallas_call(
        functools.partial(_zo_sqnorm_kernel, cols=BLOCK_COLS, n=int(n),
                          interpret=interpret, dist=dist),
        grid=(blocks,),
        in_specs=[SMEM_SPEC],
        out_specs=SMEM_SPEC,
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(seed, jnp.int32).reshape(1))[0]


@functools.partial(jax.jit, static_argnames=("n", "dist"))
def zo_sqnorm_ref(n: int, seed, dist: str = "gaussian") -> jnp.ndarray:
    """Pure-jnp oracle for ``zo_sqnorm_2d``: the same per-tile sums
    (``_sqnorm_tile``) folded in the same sequential order, pinned like the
    interpret-mode kernel — bitwise-equal by construction."""
    width = BLOCK_ROWS * BLOCK_COLS
    blocks = max(1, -(-int(n) // width))
    seed_u = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    acc = _sqnorm_tile(0, BLOCK_COLS, seed_u, int(n), dist, pin=True)
    for i in range(1, blocks):
        acc = acc + _sqnorm_tile(i, BLOCK_COLS, seed_u, int(n), dist,
                                 pin=True)
    return acc
