"""``zo_fused_multi`` — the one-pass multi-seed kernels and their consumers.

Contracts (all in the jitted-computation regime the repo's bitwise
guarantees are scoped to — each kernel wrapper is its own jitted entry
point; see ``kernel._pin`` for why a single fused surrounding graph is
excluded):

  * fan-out: ``zo_affine_multi`` slice j ≡ ``zo_affine(seeds[j], a[j], b[j])``
    bitwise, B ∈ {1, 3, 8} × {gaussian, rademacher} × {f32, bf16, f16};
  * chained: ``zo_affine_chain`` ≡ the sequential per-seed ``zo_affine``
    fold bitwise (the in-register dtype cast reproduces each launch's
    rounding boundary);
  * sqnorm: ``zo_sqnorm_2d`` ≡ the pure-jnp oracle bitwise, and ≈ the
    directly-summed ‖z‖² of the affine kernel's stream;
  * backend: ``affine_many`` ≡ the sequential ``apply_rank1`` fold bitwise
    on BOTH backends for every dist (incl. the two-pass sphere rescale),
    ``perturb_many`` with per-stream scales ≡ stacked singles (the
    antithetic SPSA fan-out), and the full B × dist × dtype matrix;
  * ledger: a pre-PR-shaped batched (seed, g, lr) entry replays through
    ``affine_many`` bitwise-equal to the pre-fusion sequential
    ``apply_rank1`` loop — existing MZOL artifacts reproduce unchanged;
  * engine: ``apply_group_updates`` (the flattened one-call write path)
    ≡ the per-group ``apply_group_update`` fold.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.zo_fused import kernel as zo_kernel
from repro.kernels.zo_fused import multi as zo_multi
from repro.kernels.zo_fused import ref as zo_ref
from repro.kernels.zo_fused import rows as zo_rows
from repro.perturb import StreamRef, get_backend
from repro.perturb import pallas as pallas_mod

BACKENDS = ["xla", "pallas"]
DISTS = ["gaussian", "rademacher", "sphere"]
KERNEL_DISTS = ["gaussian", "rademacher"]        # sphere = rescaled gaussian
DTYPES = [jnp.float32, jnp.bfloat16, jnp.float16]
DTYPE_IDS = ["f32", "bf16", "f16"]


def leaf(dtype, shape=(300, 40)):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    return x.astype(dtype)


def tree_eq(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def mixed_tree():
    return {"w": leaf(jnp.float32),
            "b": jnp.ones((77,), jnp.bfloat16),
            "h": leaf(jnp.float16, (129,)),
            "n": jnp.arange(3)}                  # non-floating rides along


# --------------------------------------------------------------------------- #
# Fan-out kernel: one x read, B outputs, per-stream coefficients
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("dist", KERNEL_DISTS)
@pytest.mark.parametrize("B", [1, 3, 8])
def test_multi_fanout_bitwise_vs_singles(B, dist, dtype):
    x = leaf(dtype)
    seeds = jnp.arange(B, dtype=jnp.int32) * 7 + 11
    a = jnp.linspace(0.5, 1.5, B)
    b = jnp.linspace(-0.1, 0.1, B)
    out = pallas_mod.zo_affine_multi(x, seeds, a, b, interpret=True,
                                     dist=dist)
    assert out.shape == (B,) + x.shape and out.dtype == x.dtype
    for j in range(B):
        single = pallas_mod.zo_affine(x, int(seeds[j]), float(a[j]),
                                      float(b[j]), interpret=True, dist=dist)
        np.testing.assert_array_equal(np.asarray(out[j]), np.asarray(single))


def test_multi_fanout_matches_existing_batched_kernel():
    """Shared-coefficient fan-out must be bitwise the PR-3 batched kernel
    (same tile walk, same streams) — the generalization cannot move bits."""
    x = leaf(jnp.float32)
    seeds = jnp.asarray([5, 9, 123], jnp.int32)
    batched = pallas_mod.zo_affine_batched(x, seeds, 0.9, 0.05,
                                           interpret=True)
    multi = pallas_mod.zo_affine_multi(x, seeds, jnp.full((3,), 0.9),
                                       jnp.full((3,), 0.05), interpret=True)
    np.testing.assert_array_equal(np.asarray(batched), np.asarray(multi))


# --------------------------------------------------------------------------- #
# Chain kernel: B affine folds per resident tile, one θ round-trip
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("dist", KERNEL_DISTS)
@pytest.mark.parametrize("B", [1, 4])
def test_chain_bitwise_vs_sequential_singles(B, dist, dtype):
    x = leaf(dtype)
    seeds = jnp.arange(B, dtype=jnp.int32) * 13 + 3
    a = jnp.linspace(0.9, 1.0, B)
    b = jnp.linspace(-0.02, 0.02, B)
    fused = pallas_mod.zo_affine_chain(x, seeds, a, b, interpret=True,
                                       dist=dist)
    seq = x
    for j in range(B):
        seq = pallas_mod.zo_affine(seq, int(seeds[j]), float(a[j]),
                                   float(b[j]), interpret=True, dist=dist)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))


def test_chain_matches_ref_oracle():
    x = leaf(jnp.float32, (100,))
    seeds = jnp.asarray([5, 9], jnp.int32)
    a = jnp.asarray([0.99, 1.0])
    b = jnp.asarray([-0.01, 0.02])
    got = pallas_mod.zo_affine_chain(x, seeds, a, b, interpret=True)
    want = jax.jit(zo_ref.zo_affine_chain_ref, static_argnames=("dist",))(
        x, seeds, a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------- #
# Sphere pass 1: the in-kernel ‖z‖² accumulator
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n", [5, 131072, 262161])
def test_sqnorm_kernel_matches_ref_bitwise(n):
    got = zo_multi.zo_sqnorm_2d(n, 42, interpret=True)
    want = zo_multi.zo_sqnorm_ref(n, 42)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sqnorm_measures_the_affine_kernel_stream():
    """Pass 1 must measure exactly the z that pass 2 applies: ‖z‖² from the
    sqnorm kernel ≈ the directly-summed squares of the affine kernel's pure-z
    output (same seed, same counter positions; summation order differs so
    this is a tolerance check — the bitwise contract is vs the oracle)."""
    n = 12345
    z = pallas_mod.zo_affine(jnp.zeros((n,)), 42, 0.0, 1.0, interpret=True)
    direct = float(jnp.sum(jnp.asarray(z, jnp.float32) ** 2))
    got = float(zo_multi.zo_sqnorm_2d(n, 42, interpret=True))
    np.testing.assert_allclose(got, direct, rtol=1e-5)


# --------------------------------------------------------------------------- #
# Backend contract: affine_many ≡ sequential apply_rank1 fold
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_affine_many_bitwise_vs_sequential_fold(backend, dist):
    be = get_backend(backend)
    params = mixed_tree()
    refs = [StreamRef.derive(jax.random.PRNGKey(5), 9, j) for j in range(4)]
    coeffs = [0.01, -0.02, 0.003, 0.3]
    decays = [0.001, 0.0, 0.0, 0.0]
    fused = be.affine_many(params, refs, coeffs, decays, dist=dist)
    seq = params
    for r, c, d in zip(refs, coeffs, decays):
        seq = be.apply_rank1(seq, r, c, d, dist=dist)
    tree_eq(fused, seq)


@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_perturb_many_per_stream_scales_bitwise(backend, dist):
    """The antithetic SPSA fan-out: perturb_many with (ε, −ε) per-stream
    scales ≡ two single perturbs, bitwise — the contract behind evaluating
    θ+εz and θ−εz from one generation pass."""
    be = get_backend(backend)
    params = mixed_tree()
    ref = StreamRef.derive(jax.random.PRNGKey(2), 1)
    pair = be.perturb_many(params, [ref, ref], (1e-3, -1e-3), dist=dist)
    tree_eq(jax.tree_util.tree_map(lambda s: s[0], pair),
            be.perturb(params, ref, 1e-3, dist=dist))
    tree_eq(jax.tree_util.tree_map(lambda s: s[1], pair),
            be.perturb(params, ref, -1e-3, dist=dist))


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("dist", DISTS)
@pytest.mark.parametrize("B", [1, 3, 8])
def test_pallas_perturb_many_full_matrix_bitwise(B, dist, dtype):
    """The acceptance matrix: batched generation ≡ stacked singles across
    B × dist × dtype on the pallas backend (sphere included — the rescale
    is per-stream identical because every stream shares the StreamRef-level
    norm pass of its own counter stream)."""
    be = get_backend("pallas")
    params = {"w": leaf(dtype), "v": leaf(dtype, (129,))}
    refs = [StreamRef.derive(jax.random.PRNGKey(0), 4, j) for j in range(B)]
    many = be.perturb_many(params, refs, 1e-3, dist=dist)
    for j, r in enumerate(refs):
        tree_eq(jax.tree_util.tree_map(lambda x: x[j], many),
                be.perturb(params, r, 1e-3, dist=dist))


def test_affine_many_validates_lengths():
    be = get_backend("xla")
    refs = [StreamRef.derive(jax.random.PRNGKey(0), 0, j) for j in range(2)]
    with pytest.raises(ValueError, match="affine_many"):
        be.affine_many(mixed_tree(), refs, [0.1], [0.0, 0.0])


# --------------------------------------------------------------------------- #
# Ledger: pre-PR batched entries replay through the fused path unchanged
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_ledger_entry_replays_prefusion_arithmetic(backend):
    """``apply_rank1_batch`` (the replay path for batched (seed, g, lr)
    entries) now routes through ``affine_many`` — its output must stay
    bitwise the pre-fusion sequential loop it replaced:

        for j: θ ← (1 − [j==0]·decay)·θ − (coeff_j / B)·z(fold(skey, j))

    so every MZOL ledger recorded before this PR reproduces the same
    parameters, with no header or stream-id change."""
    from repro.zo.updates import apply_rank1_batch
    be = get_backend(backend)
    params = mixed_tree()
    skey = jax.random.PRNGKey(17)
    coeff_vec = jnp.asarray([0.02, -0.01, 0.005])
    got = apply_rank1_batch(params, skey, coeff_vec, 0.001, backend=be)
    want = params
    for j in range(3):
        ref = StreamRef(jax.random.fold_in(skey, j))
        want = be.apply_rank1(want, ref, coeff_vec[j] / 3,
                              0.001 if j == 0 else 0.0)
    tree_eq(got, want)


# --------------------------------------------------------------------------- #
# Engine: the flattened one-call write path ≡ the per-group fold
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("batch_seeds", [1, 2])
@pytest.mark.parametrize("backend", BACKENDS)
def test_apply_group_updates_bitwise_vs_per_group_fold(backend, batch_seeds):
    from repro.exec.engine import apply_group_update, apply_group_updates
    be = get_backend(backend)
    params = mixed_tree()
    skey0 = jax.random.PRNGKey(23)
    n_groups = 3
    if batch_seeds == 1:
        coeffs = [0.01, -0.02, 0.003]
    else:
        coeffs = [jnp.asarray([0.01, 0.02]), jnp.asarray([-0.01, 0.0]),
                  jnp.asarray([0.005, -0.005])]
    fused = apply_group_updates(params, skey0, coeffs, 0.001, n_groups,
                                batch_seeds, "gaussian", be)
    seq = params
    for g in range(n_groups):
        seq = apply_group_update(seq, skey0, g, n_groups, coeffs[g],
                                 0.001 if g == 0 else 0.0, batch_seeds,
                                 "gaussian", be)
    tree_eq(fused, seq)


# --------------------------------------------------------------------------- #
# Strip walk: every affine kernel computes its resident tile strip by strip
# --------------------------------------------------------------------------- #
STRIP_SEEDS = jnp.asarray([3, 16, 29], jnp.int32)
STRIP_A = jnp.asarray([0.999, 1.0, 0.95], jnp.float32)
STRIP_B = jnp.asarray([-0.02, 0.01, 0.005], jnp.float32)
# rows(block=100_000, k=2, phase=1) over the 3 tiles of a 280_000-element
# leaf: tiles 0 and 2 straddle selected and unselected blocks (masked)
STRIP_SEL = dict(block_elems=100_000, k=2, phase=1, sel=(0, 2), masked=True)
# block == tile, k=3, phase=2: tile 2 alone, purely selected (no mask)
STRIP_SEL_PURE = dict(block_elems=zo_kernel.BLOCK_ROWS * zo_kernel.BLOCK_COLS,
                      k=3, phase=2, sel=(2,), masked=False)


@functools.partial(jax.jit, static_argnames=("n_streams", "block_elems",
                                             "k", "phase", "masked"))
def _whole_tile_oracle(tile, offset, seeds, a, b, n_streams, block_elems=1,
                       k=1, phase=0, masked=False):
    """One WHOLE (BLOCK_ROWS, cols) tile at global ``offset``: the first
    ``n_streams`` affine folds of ``_tile_affine`` in the tile's dtype, x's
    bits kept at unselected elements when ``masked`` — the per-tile
    arithmetic as it was before the strip walk."""
    y = tile
    for j in range(n_streams):
        y = zo_kernel._tile_affine(y, offset, tile.shape[1],
                                   seeds[j].astype(jnp.uint32), a[j], b[j],
                                   True).astype(tile.dtype)
    if masked:
        keep = zo_rows._tile_sel_mask(offset, tile.shape, block_elems, k,
                                      phase)
        y = jnp.where(keep, y, tile)
    return y


def _oracle(x2d, seeds, a, b, n_streams=1, sel=None):
    """The oracle over the blocked view: every tile, or (``sel``) the
    selected tiles with x's own rows elsewhere."""
    r, cols = zo_kernel.BLOCK_ROWS, x2d.shape[1]
    out = np.array(x2d)
    opts = {} if sel is None else {k: v for k, v in sel.items() if k != "sel"}
    for t in (range(x2d.shape[0] // r) if sel is None else sel["sel"]):
        out[t * r:(t + 1) * r] = np.asarray(_whole_tile_oracle(
            x2d[t * r:(t + 1) * r], jnp.uint32(t * r * cols), seeds, a, b,
            n_streams, **opts))
    return out


def _strip_case(kind, x2d):
    """(kernel output, whole-tile oracle) for one strip-walked kernel."""
    S, A, B = STRIP_SEEDS, STRIP_A, STRIP_B
    one = lambda j: (S[j:j + 1], A[j:j + 1], B[j:j + 1])
    if kind in ("single", "strips_8", "strips_16"):
        return (zo_kernel.zo_affine_2d(x2d, S[0], A[0], B[0]),
                _oracle(x2d, *one(0)))
    if kind == "batched":
        return (zo_kernel.zo_affine_2d_batched(x2d, S, A[0], B[0]),
                np.stack([_oracle(x2d, S[j:j + 1], A[:1], B[:1])
                          for j in range(3)]))
    if kind == "multi":
        return (zo_multi.zo_affine_multi_2d(x2d, S, A, B),
                np.stack([_oracle(x2d, *one(j)) for j in range(3)]))
    if kind == "chain":
        return (zo_multi.zo_affine_chain_2d(x2d, S, A, B),
                _oracle(x2d, S, A, B, n_streams=3))
    if kind.startswith("rows"):
        sel = STRIP_SEL_PURE if kind.endswith("pure") else STRIP_SEL
        if kind.startswith("rows_multi"):
            return (zo_rows.zo_affine_multi_2d_rows(x2d, S, A, B, **sel),
                    np.stack([_oracle(x2d, *one(j), sel=sel)
                              for j in range(3)]))
        if kind.startswith("rows_chain"):
            return (zo_rows.zo_affine_chain_2d_rows(x2d, S, A, B, **sel),
                    _oracle(x2d, S, A, B, n_streams=3, sel=sel))
        return (zo_rows.zo_affine_2d_rows(x2d, S[0], A[0], B[0], **sel),
                _oracle(x2d, *one(0), sel=sel))
    raise ValueError(kind)


@pytest.fixture
def fresh_traces():
    """Every kernel traces anew (its strip height is read at trace time)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", [
    "single", "batched", "multi", "chain", "rows", "rows_pure",
    "rows_multi", "rows_chain", "strips_8", "strips_16"])
def test_strip_walk_bitwise_vs_whole_tile(kind, dtype, monkeypatch,
                                          fresh_traces):
    """Each affine kernel, walking its resident tile in the compiled
    kernels' ``STRIP_ROWS`` strips under interpret mode too, gives on a leaf
    of three tiles with a padded tail, bit for bit, ``_tile_affine`` applied
    to each whole tile at the same global offsets (the selected-tiles
    kernels: on the selected tiles, x elsewhere).  The ``strips_<h>`` cases
    run the single-seed kernel at heights 8 and 16: z at an element does not
    depend on the strip height."""
    height = int(kind[7:]) if kind.startswith("strips_") \
        else zo_kernel.STRIP_ROWS
    monkeypatch.setattr(zo_kernel, "INTERPRET_STRIP_ROWS", height)
    x = leaf(dtype, (700, 400))                       # 280_000 elements
    x2d, n = pallas_mod._blocked_view(x)
    assert x2d.shape[0] == 3 * zo_kernel.BLOCK_ROWS and n < x2d.size
    got, want = _strip_case(kind, x2d)
    np.testing.assert_array_equal(np.asarray(got), want)
