"""``zo_fused_rows`` — sub-leaf tile skipping for the fused affine kernels.

The affine kernels in ``kernel.py`` / ``multi.py`` grid over *every* row-block
tile of a leaf's (rows, 512) blocked view.  Under a ``rows(block=R, k=K)``
selection only ~1/K of each leaf's row-blocks is perturbed per step, so a
full-grid launch would read, generate z for, and write K× more bytes than the
step touches.  This module launches **only the tiles covering selected
blocks**:

* the static tile plan (``tile_plan``) intersects the kernel's fixed
  131072-element tiles with the selection's ``block_elems``-sized row-blocks
  at trace time — unselected tiles are never gathered, never read by the
  kernel, and generate no z (the trace-time skip of PR 5's leaf semantics,
  one level down);
* selected tiles are gathered into a compact (n_sel·256, 512) operand, the
  kernel grids over the *compact* axis, and each grid step reads its
  original tile index from an SMEM tile-id vector — ``_tile_affine`` derives
  counter indices from the **global** element position exactly as the full
  kernel does, so a selected tile's z bits are identical whether the leaf is
  perturbed whole or block-by-block (the blocked StreamRef index contract);
* tiles that straddle a block boundary (``block_elems`` not a multiple of the
  tile size) apply the modular block predicate in-register *after* the output
  dtype cast — unselected elements keep their x bits exactly;
* the compact result is stitched back over x with static
  ``dynamic_update_slice`` row bands (no gather/scatter).

Why a compact gather instead of a scalar-prefetch index map: the
``PrefetchScalarGridSpec`` machinery changes the inlined interpret-mode graph
shape around the z generator, and (as ``_pin``'s docstring warns) LLVM-level
FMA contraction after barrier erasure then breaks the 1-ulp bitwise contract
against the full kernel.  The compact form reuses the exact BlockSpec
machinery of ``kernel.py`` (x tiles in VMEM, tile ids / seeds / coefficients
as whole SMEM vectors) — bitwise equality is structural.

All variants share ``_tile_affine`` / ``z_from_counter`` with the full
kernels; the bitwise selected-tiles ≡ full-kernel contract is those functions
being the only implementation of the per-tile arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.zo_fused.kernel import (BLOCK_COLS, BLOCK_ROWS, SMEM_SPEC,
                                           _pin, _tile_affine, z_from_counter)

TILE_ELEMS = BLOCK_ROWS * BLOCK_COLS


# --------------------------------------------------------------------------- #
# Static tile plan
# --------------------------------------------------------------------------- #
def tile_plan(n: int, block_elems: int, k: int, phase: int) -> tuple:
    """Intersect the kernel's fixed tiles with a row-block selection.

    ``n`` is the leaf's real (un-padded) element count; row-block ``b``
    covers flat elements ``[b*block_elems, (b+1)*block_elems)`` and is
    selected iff ``b % k == phase``.  Returns ``(sel_tiles, pure)`` — the
    tuple of tile indices containing at least one selected element, and
    whether every launched tile is *purely* selected (no in-kernel mask
    needed).  Pure Python on static ints: the plan is trace-time data.
    """
    n = int(n)
    be, k, phase = int(block_elems), int(k), int(phase) % int(k)
    sel, pure = [], True
    for t in range(-(-n // TILE_ELEMS)):
        lo = t * TILE_ELEMS
        hi = min(lo + TILE_ELEMS, n)
        b0, b1 = lo // be, (hi - 1) // be
        # first selected block at or after b0
        first = b0 + (phase - b0) % k
        if first > b1:
            continue
        sel.append(t)
        pure = pure and (k == 1 or (b0 == b1))
    if not sel:
        raise ValueError(
            f"rows plan selects no tiles of a {n}-element leaf "
            f"(block_elems={be}, k={k}, phase={phase}); the selection layer "
            "should have excluded this leaf from the phase")
    return tuple(sel), pure


def _tile_sel_mask(row_block, cols: int, block_elems: int, k: int,
                   phase: int) -> jnp.ndarray:
    """Selected-element predicate of one tile, from the same global counter
    indices ``_tile_affine`` generates z with: element e is in row-block
    ``e // block_elems``, selected iff ``≡ phase (mod k)``."""
    base = jnp.uint32(row_block * BLOCK_ROWS * cols)
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, cols), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, cols), 1)
    idx = base + row_ids * jnp.uint32(cols) + col_ids
    blk = idx // jnp.uint32(block_elems)
    return (blk % jnp.uint32(k)) == jnp.uint32(phase)


def _gather_tiles(x: jnp.ndarray, sel: tuple) -> jnp.ndarray:
    """Compact (n_sel·BLOCK_ROWS, cols) operand from static row-band
    slices — the only rows the kernel ever reads."""
    if len(sel) == 1:
        t = sel[0]
        return x[t * BLOCK_ROWS:(t + 1) * BLOCK_ROWS]
    return jnp.concatenate(
        [x[t * BLOCK_ROWS:(t + 1) * BLOCK_ROWS] for t in sel], axis=0)


def _scatter_tiles(x: jnp.ndarray, y: jnp.ndarray, sel: tuple) -> jnp.ndarray:
    """Stitch the compact kernel output back over x: one static
    ``dynamic_update_slice`` row band per selected tile."""
    out = x
    for j, t in enumerate(sel):
        out = jax.lax.dynamic_update_slice(
            out, y[j * BLOCK_ROWS:(j + 1) * BLOCK_ROWS],
            (t * BLOCK_ROWS, 0))
    return out


def _tiles_input(sel: tuple) -> jnp.ndarray:
    return jnp.asarray(sel, jnp.int32)


# --------------------------------------------------------------------------- #
# Single stream: y = a·x + b·z on selected tiles only
# --------------------------------------------------------------------------- #
def _zo_affine_rows_kernel(x_ref, tile_ref, seed_ref, a_ref, b_ref, o_ref, *,
                           cols: int, block_elems: int, k: int, phase: int,
                           masked: bool, interpret: bool, dist: str):
    # the grid walks the COMPACT tile axis; the original tile index arrives
    # as data, so _tile_affine's global counter base — and therefore the z
    # bits — match the full-grid kernel exactly
    t = tile_ref[pl.program_id(0)]
    seed = seed_ref[0].astype(jnp.uint32)
    x = x_ref[...]
    y = _tile_affine(x, t, cols, seed, a_ref[0], b_ref[0],
                     interpret, dist).astype(o_ref.dtype)
    if masked:
        y = jnp.where(_tile_sel_mask(t, cols, block_elems, k, phase), y, x)
    o_ref[...] = y


@functools.partial(jax.jit, static_argnames=("sel", "block_elems", "k",
                                             "phase", "masked", "interpret",
                                             "dist"))
def zo_affine_2d_rows(x: jnp.ndarray, seed: jnp.ndarray, a: jnp.ndarray,
                      b: jnp.ndarray, sel: tuple, block_elems: int, k: int,
                      phase: int, masked: bool, interpret: bool = True,
                      dist: str = "gaussian") -> jnp.ndarray:
    """``zo_affine_2d`` restricted to the selected tiles of a rows plan.

    Selected rows are bitwise-equal to the full kernel's output (same
    ``_tile_affine`` on the same global counter base); unselected rows keep
    x's bits exactly.  Only ``len(sel)`` tiles are read, generated, and
    written — perturbed bytes scale with the selected fraction.
    """
    rows, cols = x.shape
    assert rows % BLOCK_ROWS == 0 and cols == BLOCK_COLS, (rows, cols)
    n_sel = len(sel)
    xs = _gather_tiles(x, sel)
    y = pl.pallas_call(
        functools.partial(_zo_affine_rows_kernel, cols=cols,
                          block_elems=int(block_elems), k=int(k),
                          phase=int(phase), masked=masked,
                          interpret=interpret, dist=dist),
        grid=(n_sel,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xs.shape, x.dtype),
        interpret=interpret,
    )(xs, _tiles_input(sel), seed.reshape(1).astype(jnp.int32),
      jnp.asarray(a, jnp.float32).reshape(1),
      jnp.asarray(b, jnp.float32).reshape(1))
    return _scatter_tiles(x, y, sel)


# --------------------------------------------------------------------------- #
# Fan-out: B streams, per-stream coefficients, selected tiles only
# --------------------------------------------------------------------------- #
def _zo_affine_multi_rows_kernel(x_ref, tile_ref, seed_ref, a_ref, b_ref,
                                 o_ref, *, cols: int, block_elems: int,
                                 k: int, phase: int, masked: bool,
                                 interpret: bool, dist: str):
    # grid (n_sel, batch): compact tile axis OUTER so the x tile stays
    # resident while the inner batch axis walks the B streams against it —
    # the multi.py structure over the compact operand
    t = tile_ref[pl.program_id(0)]
    j = pl.program_id(1)
    seed = seed_ref[j].astype(jnp.uint32)
    x = x_ref[...]
    y = _tile_affine(x, t, cols, seed, a_ref[j], b_ref[j],
                     interpret, dist).astype(o_ref.dtype)
    if masked:
        y = jnp.where(_tile_sel_mask(t, cols, block_elems, k, phase), y, x)
    o_ref[0, ...] = y


@functools.partial(jax.jit, static_argnames=("sel", "block_elems", "k",
                                             "phase", "masked", "interpret",
                                             "dist"))
def zo_affine_multi_2d_rows(x: jnp.ndarray, seeds: jnp.ndarray,
                            a: jnp.ndarray, b: jnp.ndarray, sel: tuple,
                            block_elems: int, k: int, phase: int,
                            masked: bool, interpret: bool = True,
                            dist: str = "gaussian") -> jnp.ndarray:
    """``zo_affine_multi_2d`` on selected tiles: y[j] = a_j·x + b_j·z_j on
    selected rows, x's bits elsewhere.  Result is (B, rows, cols); each batch
    slice's selected rows are bitwise-equal to the full multi kernel's."""
    rows, cols = x.shape
    (batch,) = seeds.shape
    assert rows % BLOCK_ROWS == 0 and cols == BLOCK_COLS, (rows, cols)
    n_sel = len(sel)
    xs = _gather_tiles(x, sel)
    y = pl.pallas_call(
        functools.partial(_zo_affine_multi_rows_kernel, cols=cols,
                          block_elems=int(block_elems), k=int(k),
                          phase=int(phase), masked=masked,
                          interpret=interpret, dist=dist),
        grid=(n_sel, batch),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, cols), lambda i, j: (i, 0)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((1, BLOCK_ROWS, cols), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, n_sel * BLOCK_ROWS, cols),
                                       x.dtype),
        interpret=interpret,
    )(xs, _tiles_input(sel), seeds.astype(jnp.int32),
      jnp.asarray(a, jnp.float32).reshape(-1),
      jnp.asarray(b, jnp.float32).reshape(-1))
    out = jnp.broadcast_to(x, (batch,) + x.shape)
    for j, t in enumerate(sel):
        out = jax.lax.dynamic_update_slice(
            out, y[:, j * BLOCK_ROWS:(j + 1) * BLOCK_ROWS, :],
            (0, t * BLOCK_ROWS, 0))
    return out


# --------------------------------------------------------------------------- #
# Chained: B affine folds per resident selected tile
# --------------------------------------------------------------------------- #
def _zo_affine_chain_rows_kernel(x_ref, tile_ref, seed_ref, a_ref, b_ref,
                                 o_ref, *, cols: int, n_streams: int,
                                 block_elems: int, k: int, phase: int,
                                 masked: bool, interpret: bool, dist: str):
    # the fold runs on the whole tile (every op is elementwise, so selected
    # elements' values never depend on unselected neighbours) and the block
    # predicate restores x's bits once at the end — equivalent to masking
    # every fold step, at one select instead of n_streams
    t = tile_ref[pl.program_id(0)]
    x = x_ref[...]
    y = x
    for j in range(n_streams):
        seed = seed_ref[j].astype(jnp.uint32)
        y = _tile_affine(y, t, cols, seed, a_ref[j], b_ref[j],
                         interpret, dist).astype(x_ref.dtype)
    if masked:
        y = jnp.where(_tile_sel_mask(t, cols, block_elems, k, phase), y, x)
    o_ref[...] = y


@functools.partial(jax.jit, static_argnames=("sel", "block_elems", "k",
                                             "phase", "masked", "interpret",
                                             "dist"))
def zo_affine_chain_2d_rows(x: jnp.ndarray, seeds: jnp.ndarray,
                            a: jnp.ndarray, b: jnp.ndarray, sel: tuple,
                            block_elems: int, k: int, phase: int,
                            masked: bool, interpret: bool = True,
                            dist: str = "gaussian") -> jnp.ndarray:
    """``zo_affine_chain_2d`` on selected tiles: the B-fold update chain
    applied to selected rows in one launch, x's bits elsewhere — selected
    rows bitwise-equal to the full chain kernel (same in-register dtype-cast
    rounding boundary between streams)."""
    rows, cols = x.shape
    (batch,) = seeds.shape
    assert rows % BLOCK_ROWS == 0 and cols == BLOCK_COLS, (rows, cols)
    n_sel = len(sel)
    xs = _gather_tiles(x, sel)
    y = pl.pallas_call(
        functools.partial(_zo_affine_chain_rows_kernel, cols=cols,
                          n_streams=int(batch), block_elems=int(block_elems),
                          k=int(k), phase=int(phase), masked=masked,
                          interpret=interpret, dist=dist),
        grid=(n_sel,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xs.shape, x.dtype),
        interpret=interpret,
    )(xs, _tiles_input(sel), seeds.astype(jnp.int32),
      jnp.asarray(a, jnp.float32).reshape(-1),
      jnp.asarray(b, jnp.float32).reshape(-1))
    return _scatter_tiles(x, y, sel)


# --------------------------------------------------------------------------- #
# Sphere pass 1 over selected rows only
# --------------------------------------------------------------------------- #
def _sqnorm_rows_tile(row_block, cols: int, seed: jnp.ndarray, n: int,
                      block_elems: int, k: int, phase: int, dist: str,
                      pin: bool) -> jnp.ndarray:
    """One selected tile's Σ z² over its selected, real elements (padding
    and unselected blocks contribute exactly 0)."""
    base = jnp.uint32(row_block * BLOCK_ROWS * cols)
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, cols), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (BLOCK_ROWS, cols), 1)
    idx = base + row_ids * jnp.uint32(cols) + col_ids
    z = z_from_counter(idx, seed, dist, pin=pin)
    blk = idx // jnp.uint32(block_elems)
    keep = ((blk % jnp.uint32(k)) == jnp.uint32(phase)) & (idx < jnp.uint32(n))
    z = _pin(jnp.where(keep, z, jnp.float32(0.0)), pin)
    return _pin(jnp.sum(_pin(z * z, pin), dtype=jnp.float32), pin)


def _zo_sqnorm_rows_kernel(tile_ref, seed_ref, o_ref, *, cols: int, n: int,
                           block_elems: int, k: int, phase: int,
                           interpret: bool, dist: str):
    i = pl.program_id(0)
    t = tile_ref[i]
    seed = seed_ref[0].astype(jnp.uint32)
    part = _sqnorm_rows_tile(t, cols, seed, n, block_elems, k, phase, dist,
                             pin=interpret)

    @pl.when(i == 0)
    def _init():
        o_ref[0] = part

    @pl.when(i > 0)
    def _acc():
        o_ref[0] = o_ref[0] + part


@functools.partial(jax.jit, static_argnames=("n", "sel", "block_elems", "k",
                                             "phase", "interpret", "dist"))
def zo_sqnorm_2d_rows(n: int, seed, sel: tuple, block_elems: int, k: int,
                      phase: int, interpret: bool = True,
                      dist: str = "gaussian") -> jnp.ndarray:
    """‖z restricted to the selected row-blocks‖² — sphere pass 1 under a
    rows selection.  Only the selected tiles are visited; the modular block
    predicate (and the real-element bound ``n``) masks inside them, so pass 2
    rescales exactly the z the selected rows will consume."""
    return pl.pallas_call(
        functools.partial(_zo_sqnorm_rows_kernel, cols=BLOCK_COLS, n=int(n),
                          block_elems=int(block_elems), k=int(k),
                          phase=int(phase), interpret=interpret, dist=dist),
        grid=(len(sel),),
        in_specs=[SMEM_SPEC, SMEM_SPEC],
        out_specs=SMEM_SPEC,
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        interpret=interpret,
    )(_tiles_input(sel), jnp.asarray(seed, jnp.int32).reshape(1))[0]


@functools.partial(jax.jit, static_argnames=("n", "sel", "block_elems", "k",
                                             "phase", "dist"))
def zo_sqnorm_rows_ref(n: int, seed, sel: tuple, block_elems: int, k: int,
                       phase: int, dist: str = "gaussian") -> jnp.ndarray:
    """Pure-jnp oracle for ``zo_sqnorm_2d_rows``: the same per-tile sums in
    the same order, pinned like the interpret-mode kernel."""
    seed_u = jnp.asarray(seed, jnp.int32).astype(jnp.uint32)
    acc = _sqnorm_rows_tile(sel[0], BLOCK_COLS, seed_u, int(n),
                            int(block_elems), int(k), int(phase), dist,
                            pin=True)
    for t in sel[1:]:
        acc = acc + _sqnorm_rows_tile(t, BLOCK_COLS, seed_u, int(n),
                                      int(block_elems), int(k), int(phase),
                                      dist, pin=True)
    return acc
