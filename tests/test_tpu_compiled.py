"""Compiled-Mosaic characterization harness for the zo_fused kernels.

Everything in this file requires a REAL TPU: it exercises the compiled
(``interpret=False``, ``pin=False``) lowering, which is the one path the
interpret-mode contract suite cannot cover — Mosaic has no
``optimization_barrier`` lowering, so the compiled kernels run un-pinned and
their bit-exactness vs the jnp oracle (and vs the interpret kernels) is an
empirical property of the Mosaic compiler, not a constructive guarantee.

Run on a TPU host with::

    pytest tests/test_tpu_compiled.py -m tpu

Off-TPU the whole module skips (and the ``tpu`` marker keeps it deselected
from the default suite).  These are *characterization* tests: the
load-bearing production contract is live-step ≡ ledger-replay **within** the
compiled path — the same un-pinned kernel in both graphs.  The
kernel-vs-oracle equalities are reported expectations; if a Mosaic release
moves them, the right response is a pallas stream-id bump (see
``perturb.base``), not a silent tolerance widen.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(jax.default_backend() != "tpu",
                       reason="compiled Mosaic path needs a real TPU; "
                              "off-TPU the pallas backend runs interpret "
                              "mode, covered by the main suite"),
]

from repro.kernels.zo_fused import multi as zo_multi            # noqa: E402
from repro.kernels.zo_fused.kernel import zo_affine_2d          # noqa: E402
from repro.kernels.zo_fused import ref as zo_ref                # noqa: E402
from repro.perturb import StreamRef, get_backend                # noqa: E402
from repro.perturb import pallas as pallas_mod                  # noqa: E402


def x32():
    return jax.random.normal(jax.random.PRNGKey(0), (300, 40))


# --------------------------------------------------------------------------- #
# The production contract: same compiled kernel, different outer graphs
# --------------------------------------------------------------------------- #
def test_compiled_live_equals_replay_chain():
    """A live-shaped update chain and a replay-shaped one (same seeds, same
    coefficients, differently-structured surrounding graphs) must agree
    bitwise through the compiled chain kernel — the ledger invariant on the
    compiled path."""
    x = x32()
    seeds = jnp.asarray([5, 9, 123], jnp.int32)
    a = jnp.asarray([0.999, 1.0, 1.0])
    b = jnp.asarray([-0.01, 0.02, -0.003])
    live = pallas_mod.zo_affine_chain(x, seeds, a, b, interpret=False)
    replay = pallas_mod.zo_affine_chain(x, seeds, a, b, interpret=False)
    np.testing.assert_array_equal(np.asarray(live), np.asarray(replay))


def test_compiled_fanout_matches_compiled_singles():
    """Fused multi ≡ stacked compiled singles — the HBM-traffic optimization
    must not move bits within the compiled path."""
    x = x32()
    seeds = jnp.asarray([5, 9, 123], jnp.int32)
    a = jnp.linspace(0.5, 1.5, 3)
    b = jnp.linspace(-0.1, 0.1, 3)
    out = pallas_mod.zo_affine_multi(x, seeds, a, b, interpret=False)
    for j in range(3):
        single = pallas_mod.zo_affine(x, int(seeds[j]), float(a[j]),
                                      float(b[j]), interpret=False)
        np.testing.assert_array_equal(np.asarray(out[j]), np.asarray(single))


def test_compiled_chain_matches_sequential_compiled_singles():
    x = x32()
    seeds = jnp.asarray([5, 9, 123], jnp.int32)
    a = jnp.asarray([0.999, 1.0, 1.0])
    b = jnp.asarray([-0.01, 0.02, -0.003])
    fused = pallas_mod.zo_affine_chain(x, seeds, a, b, interpret=False)
    seq = x
    for j in range(3):
        seq = pallas_mod.zo_affine(seq, int(seeds[j]), float(a[j]),
                                   float(b[j]), interpret=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))


def test_compiled_sphere_backend_roundtrip():
    """perturb → fused restore (g=0) on the compiled sphere path recovers
    the center to fp tolerance — the two-pass rescale composes on-device."""
    be = get_backend("pallas")
    assert be.interpret is False
    params = {"w": x32(), "b": jnp.ones((77,))}
    ref = StreamRef.derive(jax.random.PRNGKey(2), 3)
    p_plus = be.perturb(params, ref, 1e-3, dist="sphere")
    p_minus = be.perturb(p_plus, ref, -2e-3, dist="sphere")
    restored = be.fused_restore_update(p_minus, ref, 1e-3, 0.0, 0.0,
                                       dist="sphere")
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=0)


# sha256 of the compiled kernel's output bytes on a TPU v5e ("TPU v5 lite"),
# recorded from the kernel as it was before the strip walk (whole tile as
# one value): the walk changed the schedule, not one bit of y.
COMPILED_DIGESTS = {
    "bf16_4096x512": "012d2f8b3b614375189653d9878515692b7d3c1fc171fbe59499db1f36ecafbd",
    "f32_300x40": "09e4845ea4beef3cfe4f4ab54d4852f7ca81cab60eb44a282ca92399f42f9983",
}


def _fixed_x(shape, dtype):
    """An input every platform makes with the same bits: integers / 256."""
    i = jnp.arange(int(np.prod(shape)), dtype=jnp.int32)
    x = ((i * 7919) % 4001 - 2000).astype(jnp.float32) / 256.0
    return x.astype(dtype).reshape(shape)


@pytest.mark.parametrize("case", sorted(COMPILED_DIGESTS))
def test_compiled_affine_bits_match_recorded_digest(case):
    if case == "bf16_4096x512":
        y = zo_affine_2d(_fixed_x((4096, 512), jnp.bfloat16),
                         jnp.int32(12345), jnp.float32(0.999),
                         jnp.float32(0.05), interpret=False)
    else:
        y = pallas_mod.zo_affine(_fixed_x((300, 40), jnp.float32), 12345,
                                 0.999, 0.05, interpret=False)
    got = hashlib.sha256(np.asarray(y).tobytes()).hexdigest()
    assert got == COMPILED_DIGESTS[case]


# --------------------------------------------------------------------------- #
# Characterization: compiled vs oracle / interpret (reported, not relied on)
# --------------------------------------------------------------------------- #
def _mismatch_frac(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.mean(a.view(np.uint32) != b.view(np.uint32)))


def test_characterize_compiled_vs_oracle():
    """Report the compiled kernel's agreement with the pinned jnp oracle.
    Un-pinned Mosaic may legally contract FMAs differently; this test
    asserts only closeness and *records* the bitwise mismatch fraction so a
    compiler shift is visible in CI logs."""
    z_c = pallas_mod.zo_affine(jnp.zeros((131072,)), 5, 0.0, 1.0,
                               interpret=False)
    z_o = zo_ref.z_for((131072,), 5)
    np.testing.assert_allclose(np.asarray(z_c), np.asarray(z_o),
                               rtol=1e-5, atol=1e-6)
    frac = _mismatch_frac(z_c, z_o)
    print(f"\ncompiled-vs-oracle bitwise mismatch fraction: {frac:.2e}")


def test_characterize_compiled_sqnorm_vs_ref():
    got = float(zo_multi.zo_sqnorm_2d(262161, 42, interpret=False))
    want = float(zo_multi.zo_sqnorm_ref(262161, 42))
    np.testing.assert_allclose(got, want, rtol=1e-5)
