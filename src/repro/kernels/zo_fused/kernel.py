"""Pallas TPU kernel for MeZO's fused perturb/update: y = a·x + b·z(seed).

This is the paper's in-place trick taken one level further down the memory
hierarchy: the Gaussian direction z is generated *inside VMEM*, tile by tile,
from a counter-based hash of (seed, global element index) — z never exists in
HBM at all.  One kernel serves all three uses in Algorithm 1 via the affine
scalars:

    perturb  +ε :  a = 1,        b = +ε
    perturb −2ε :  a = 1,        b = −2ε
    update      :  a = 1 − η·λ,  b = −η·g     (g = projected gradient)

RNG: a murmur3-finalizer counter hash (32-bit ops only — TPU native) feeding
a Box–Muller transform built from transcendental-free polynomial log/cos
(``_det_log`` / ``_det_cos2pi``) with per-stage rounding pins (``_pin``), so
every jitted graph — single-seed kernel, batched kernel, train step, ledger
replay, and the pure-jnp oracle in ref.py — generates bit-identical z.
``dist="rademacher"`` swaps Box–Muller for the sign of one counter stream
(``rademacher_from_counter``): comparison + select, no rounding at all.

Grid: 1-D over row-blocks of the (padded) 2-D view; BlockSpec keeps one
(block_rows × 128·lane_cols) tile of x and y in VMEM (~256 KB at f32).
Inside a grid step the kernel walks its resident tile in ``STRIP_ROWS``-row
strips (``_walk_strips``): the z generator on a whole tile is a live set of
hundreds of vregs, which the compiler spills to VMEM and fills back, while a
strip's live set fits the 64-vreg register file.  The counter index is the
element's global position whatever the strip height, so z is the same.
``zo_affine_2d_batched`` adds an inner batch grid axis: B z-streams are
generated against each resident x tile (the ``perturb_many`` entry point for
batched-seed estimators).  Seeds and affine coefficients are whole 1-D arrays
in SMEM (``SMEM_SPEC``), indexed by grid position inside the kernel: Mosaic
refuses a ``(1, 1)`` VMEM block over a ``(B, 1)`` array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256
BLOCK_COLS = 512          # multiple of 128 lanes
# rows computed at once inside a resident tile: one bf16 (16, 128) tile, two
# f32 (8, 128) tiles — a strip of z's intermediates stays in registers
STRIP_ROWS = 16
# Interpret mode has no register file to fit and takes the tile whole: XLA:CPU
# then compiles the graphs around the kernels as before the walk, and rounds
# the losses of a step that inlines them the same way.
INTERPRET_STRIP_ROWS = BLOCK_ROWS

# whole-array scalar operand (per-stream seeds, coefficients, tile ids)
SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _murmur_mix(h: jnp.ndarray) -> jnp.ndarray:
    """murmur3 32-bit finalizer (uint32)."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _pin(val, pin: bool):
    """Materialize ``val`` behind an optimization barrier when ``pin``.

    Interpret-mode kernels are inlined jnp, and XLA CPU's rounding for the
    "same" arithmetic can differ between differently-shaped graphs — the
    single-seed vs the batched kernel, the live train step vs the jitted
    ledger replay — because fusion decides cluster shapes and the clusters
    decide codegen.  The stage barriers keep each float stage in its own
    uniformly-shaped cluster, which (together with the transcendental-free z
    generator below) makes every JITTED graph produce identical z bits.
    Note the limits: LLVM-level FMA contraction happens after barriers are
    erased, so op-by-op EAGER execution (no patterns to contract) can still
    differ from jitted graphs by 1 ulp on rare elements — bitwise contracts
    therefore compare jitted computations only.  Mosaic TPU has no
    optimization_barrier lowering, so compiled kernels pass ``pin=False``
    (bitwise contracts are asserted under interpret mode only)."""
    return jax.lax.optimization_barrier(val) if pin else val


def counter_uniform(idx: jnp.ndarray, seed: jnp.ndarray, salt: int,
                    pin: bool = False) -> jnp.ndarray:
    """uint32 counter + seed + salt -> uniform f32 in (0, 1)."""
    h = idx * jnp.uint32(0x9E3779B1)                 # golden-ratio spread
    h = h ^ (seed * jnp.uint32(0x7FEB352D))
    h = h + jnp.uint32(salt) * jnp.uint32(0x846CA68B)
    h = _murmur_mix(h)
    # 24 mantissa-ish bits -> (0,1); +1 avoids exactly 0 for the log.  The
    # value is < 2^24, so the int32 hop is exact; Mosaic has no u32 -> f32.
    u = _pin((h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32), pin)
    return u * (1.0 / 16777216.0) + (0.5 / 16777216.0)


_LN2 = 0.6931471805599453


def _det_log(u: jnp.ndarray, pin: bool) -> jnp.ndarray:
    """Deterministic ln(u) for u in (0, 1) from basic float ops only.

    ``jnp.log``'s rounding on XLA:CPU depends on which codegen path the
    fusion cluster takes (vectorized polynomial vs scalar libm), so the same
    u can yield 1-ulp-different logs in two graphs — fatal for the bitwise
    live-step == ledger-replay contract.  Exponent/mantissa split by integer
    bitcast (exact), ln(m) by the atanh series in s = (m−1)/(m+1) with every
    mul/add pinned: deterministic in any graph, ~1e-7 absolute error (the
    N(0,1) law of z is insensitive at that scale).
    """
    bits = jax.lax.bitcast_convert_type(u, jnp.uint32)            # exact
    e = (bits >> jnp.uint32(23)).astype(jnp.int32) - 127
    m = jax.lax.bitcast_convert_type(
        (bits & jnp.uint32(0x007FFFFF)) | jnp.uint32(0x3F800000),
        jnp.float32)                                              # m ∈ [1, 2)
    s = _pin((m - 1.0) / _pin(m + 1.0, pin), pin)                 # s ∈ [0, ⅓)
    s2 = _pin(s * s, pin)
    p = jnp.float32(1.0 / 13.0)
    for c in (1.0 / 11.0, 1.0 / 9.0, 1.0 / 7.0, 1.0 / 5.0, 1.0 / 3.0, 1.0):
        p = _pin(_pin(p * s2, pin) + jnp.float32(c), pin)
    log_m = _pin(jnp.float32(2.0) * _pin(s * p, pin), pin)
    return _pin(log_m + _pin(e.astype(jnp.float32) * jnp.float32(_LN2), pin),
                pin)


# cos/sin Taylor coefficients (highest order first), evaluated by pinned
# Horner on φ² with φ ∈ [0, π/2): ~6e-9 absolute truncation error.
_COS_COEFFS = (-1.0 / 87178291200.0, 1.0 / 479001600.0, -1.0 / 3628800.0,
               1.0 / 40320.0, -1.0 / 720.0, 1.0 / 24.0, -1.0 / 2.0, 1.0)
_SIN_COEFFS = (1.0 / 6227020800.0, -1.0 / 39916800.0, 1.0 / 362880.0,
               -1.0 / 5040.0, 1.0 / 120.0, -1.0 / 6.0, 1.0)


def _det_cos2pi(t: jnp.ndarray, pin: bool) -> jnp.ndarray:
    """Deterministic cos(2π·t) for t in (0, 1): exact quadrant reduction
    (4t and 4t−k are exact float ops) + pinned-Horner sin/cos polynomials —
    same rationale as ``_det_log``."""
    t4 = t * 4.0                                 # exact: power-of-two scale
    k = jnp.floor(t4)                            # exact
    f = t4 - k                                   # exact (Sterbenz)
    phi = _pin(f * jnp.float32(jnp.pi / 2), pin)
    p2 = _pin(phi * phi, pin)
    c = jnp.float32(_COS_COEFFS[0])
    for coef in _COS_COEFFS[1:]:
        c = _pin(_pin(c * p2, pin) + jnp.float32(coef), pin)
    s = jnp.float32(_SIN_COEFFS[0])
    for coef in _SIN_COEFFS[1:]:
        s = _pin(_pin(s * p2, pin) + jnp.float32(coef), pin)
    s = _pin(phi * s, pin)
    ki = k.astype(jnp.int32) & 3                 # quadrant
    return _pin(jnp.where(ki == 0, c,
                          jnp.where(ki == 1, -s,
                                    jnp.where(ki == 2, -c, s))), pin)


def gaussian_from_counter(idx: jnp.ndarray, seed: jnp.ndarray,
                          pin: bool = False) -> jnp.ndarray:
    """Box–Muller on two independent counter streams, built exclusively from
    rounding-deterministic ops (see ``_det_log`` / ``_det_cos2pi``) so the
    same (idx, seed) yields bitwise-identical z in every graph — the single
    kernel, the batched kernel, the jitted train step, and the jitted ledger
    replay.  ``pin`` additionally barriers each float stage (interpret mode /
    the jnp oracle); compiled TPU kernels pass ``False``."""
    u1 = _pin(counter_uniform(idx, seed, 1, pin), pin)
    u2 = _pin(counter_uniform(idx, seed, 2, pin), pin)
    t = _pin(jnp.float32(-2.0) * _det_log(u1, pin), pin)
    # the polynomial log's ~1e-7 absolute error can push −2·ln(u) fractionally
    # below zero for u within an ulp of 1 — clamp instead of NaN-ing the sqrt
    r = _pin(jnp.sqrt(jnp.maximum(t, 0.0)), pin)
    c = _det_cos2pi(u2, pin)
    return _pin(r * c, pin)


def rademacher_from_counter(idx: jnp.ndarray, seed: jnp.ndarray,
                            pin: bool = False) -> jnp.ndarray:
    """±1 from the sign of ONE counter stream: u >= ½ → +1, else −1.  Uses
    the same salt-1 stream the gaussian path reads as u1 (a different dist is
    a different z law, not a different stream identity).  Comparison + select
    involve no rounding at all, so the rademacher stream is bitwise-
    deterministic in every graph without any of the gaussian path's
    polynomial machinery."""
    u = _pin(counter_uniform(idx, seed, 1, pin), pin)
    return _pin(jnp.where(u >= jnp.float32(0.5),
                          jnp.float32(1.0), jnp.float32(-1.0)), pin)


def z_from_counter(idx: jnp.ndarray, seed: jnp.ndarray, dist: str,
                   pin: bool = False) -> jnp.ndarray:
    """Dispatch the kernel's in-VMEM z generation by distribution."""
    if dist == "gaussian":
        return gaussian_from_counter(idx, seed, pin)
    if dist == "rademacher":
        return rademacher_from_counter(idx, seed, pin)
    raise NotImplementedError(
        f"zo_fused kernel has no in-kernel generator for dist={dist!r} "
        "(implemented: gaussian, rademacher).  sphere is a *scaled* gaussian "
        "stream: the backend measures ‖z‖ with the zo_sqnorm kernel "
        "(kernels/zo_fused/multi.py, pass 1) and folds sqrt(d)/‖z‖ into the "
        "affine b coefficient (pass 2) — call the affine kernels with "
        "dist='gaussian' and the rescaled b, as PallasBackend does")


def _affine_combine(x: jnp.ndarray, z: jnp.ndarray, a, b,
                    interpret: bool) -> jnp.ndarray:
    """a·x + b·z with rounding pinned under interpret mode (see ``_pin``):
    the barriers isolate the z cluster and force separately-rounded
    mul/mul/add in every graph that inlines this kernel."""
    if interpret:
        x, z = jax.lax.optimization_barrier((x, z))
    ax, bz = a * x, b * z
    if interpret:
        ax, bz = jax.lax.optimization_barrier((ax, bz))
    return ax + bz


def _tile_affine(x: jnp.ndarray, offset, cols: int, seed: jnp.ndarray, a, b,
                 interpret: bool, dist: str = "gaussian") -> jnp.ndarray:
    """y = a·x + b·z(seed) on a block of rows whose first element sits at
    global element position ``offset``: the counter index of element
    (r, c) is ``offset + r·cols + c``, so the stream is position-stable
    across padding, blocking and strip height.  Shared by every affine
    kernel — the bitwise batched == singles contract is this function being
    the only implementation."""
    rows = x.shape[0]
    row_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    idx = jnp.asarray(offset, jnp.uint32) + row_ids * jnp.uint32(cols) + col_ids
    z = z_from_counter(idx, seed, dist, pin=interpret)
    return _affine_combine(x.astype(jnp.float32), z, a, b, interpret)


def _walk_strips(x_ref, o_ref, tile, fn, interpret: bool):
    """Fill the resident tile ``tile`` of ``o_ref`` strip by strip:
    ``o[strip] = fn(x[strip], offset)``, where ``offset`` is the global
    element position of the strip's first element.  A leading axis of
    ``o_ref`` (the fan-out kernels' stream axis, block size 1) is index 0.
    Strips are ``STRIP_ROWS`` high when compiled, ``INTERPRET_STRIP_ROWS``
    under interpret mode."""
    strip_rows = INTERPRET_STRIP_ROWS if interpret else STRIP_ROWS
    cols = x_ref.shape[1]
    lead = (0,) * (len(o_ref.shape) - 2)
    base = jnp.asarray(tile * (x_ref.shape[0] * cols), jnp.uint32)

    def strip(s, carry):
        r0 = pl.multiple_of(s * strip_rows, strip_rows)
        rows = pl.ds(r0, strip_rows)
        offset = base + jnp.asarray(r0, jnp.uint32) * jnp.uint32(cols)
        o_ref[lead + (rows, slice(None))] = fn(
            x_ref[rows, :], offset).astype(o_ref.dtype)
        return carry

    n_strips = x_ref.shape[0] // strip_rows
    if n_strips == 1:               # no loop: the tile-as-one-value graph
        strip(0, 0)
    else:
        jax.lax.fori_loop(0, n_strips, strip, 0)


def _zo_affine_kernel(x_ref, seed_ref, a_ref, b_ref, o_ref, *, cols: int,
                      interpret: bool, dist: str):
    seed = seed_ref[0].astype(jnp.uint32)
    a, b = a_ref[0], b_ref[0]
    _walk_strips(x_ref, o_ref, pl.program_id(0),
                 lambda x, off: _tile_affine(x, off, cols, seed, a, b,
                                             interpret, dist), interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "dist"))
def zo_affine_2d(x: jnp.ndarray, seed: jnp.ndarray, a: jnp.ndarray,
                 b: jnp.ndarray, interpret: bool = True,
                 dist: str = "gaussian") -> jnp.ndarray:
    """y = a·x + b·z on a 2-D array whose shape is (R·BLOCK_ROWS, BLOCK_COLS)."""
    rows, cols = x.shape
    assert rows % BLOCK_ROWS == 0 and cols == BLOCK_COLS, (rows, cols)
    grid = (rows // BLOCK_ROWS,)
    return pl.pallas_call(
        functools.partial(_zo_affine_kernel, cols=cols, interpret=interpret,
                          dist=dist),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, seed.reshape(1).astype(jnp.int32),
      jnp.asarray(a, jnp.float32).reshape(1),
      jnp.asarray(b, jnp.float32).reshape(1))


def _zo_affine_batched_kernel(x_ref, seed_ref, a_ref, b_ref, o_ref, *,
                              cols: int, interpret: bool, dist: str):
    # Grid is (row_blocks, batch): the row-block axis is OUTER, so the x tile
    # for row-block i stays resident in VMEM while the inner batch axis
    # generates B z-streams against it (Pallas re-fetches a block only when
    # its index-map output changes between consecutive grid steps).  The tile
    # computation is _tile_affine — the same single implementation the
    # single-seed kernel runs, which is what makes the batched output
    # bitwise-equal to stacked single-seed calls.
    seed = seed_ref[pl.program_id(1)].astype(jnp.uint32)
    a, b = a_ref[0], b_ref[0]
    _walk_strips(x_ref, o_ref, pl.program_id(0),
                 lambda x, off: _tile_affine(x, off, cols, seed, a, b,
                                             interpret, dist), interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "dist"))
def zo_affine_2d_batched(x: jnp.ndarray, seeds: jnp.ndarray, a: jnp.ndarray,
                         b: jnp.ndarray, interpret: bool = True,
                         dist: str = "gaussian") -> jnp.ndarray:
    """y[j] = a·x + b·z(seeds[j]) for all j in one launch.

    ``x`` is the (R·BLOCK_ROWS, BLOCK_COLS) blocked view shared by every
    seed; ``seeds`` is a (B,) int32 vector of per-stream counter seeds.  The
    result has shape (B, rows, cols) and each batch slice is bitwise-equal to
    ``zo_affine_2d(x, seeds[j], a, b)`` — genuinely batched generation (B
    z-streams per VMEM tile of x), not B kernel launches.
    """
    rows, cols = x.shape
    (batch,) = seeds.shape
    assert rows % BLOCK_ROWS == 0 and cols == BLOCK_COLS, (rows, cols)
    grid = (rows // BLOCK_ROWS, batch)
    return pl.pallas_call(
        functools.partial(_zo_affine_batched_kernel, cols=cols,
                          interpret=interpret, dist=dist),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, cols), lambda i, j: (i, 0)),
            SMEM_SPEC, SMEM_SPEC, SMEM_SPEC,
        ],
        out_specs=pl.BlockSpec((1, BLOCK_ROWS, cols), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, rows, cols), x.dtype),
        interpret=interpret,
    )(x, seeds.astype(jnp.int32),
      jnp.asarray(a, jnp.float32).reshape(1),
      jnp.asarray(b, jnp.float32).reshape(1))
