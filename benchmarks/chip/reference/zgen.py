"""The MeZO perturbation direction z, regenerated by the benchmark itself.

MeZO stores no direction: z for element ``e`` of parameter leaf ``i`` at step
``t`` is a pure function of ``(run seed, t, i, e)``.  This module is an
independent copy of that published arithmetic (threefry step key, 32-bit
counter hash, transcendental-free Box–Muller), so the reference step can
follow the trained model's trajectory without taking anything from the
program under test.  Write-once: a later change to the program's generator
shows up here as a failed comparison, never as a silently moved yardstick.

    key_t   = fold_in(PRNGKey(zo_seed), t)
    seed_t  = bitcast_i32(key_t[0] ^ key_t[1])
    seed_ti = seed_t + 0x1000003 · i            (int32, wrapping)
    z[e]    = sqrt(-2 ln u1) · cos(2π u2),  u_k = hash(e, seed_ti, salt k)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LEAF_STRIDE = 0x1000003
_LN2 = 0.6931471805599453
_COS = (-1.0 / 87178291200.0, 1.0 / 479001600.0, -1.0 / 3628800.0,
        1.0 / 40320.0, -1.0 / 720.0, 1.0 / 24.0, -1.0 / 2.0, 1.0)
_SIN = (1.0 / 6227020800.0, -1.0 / 39916800.0, 1.0 / 362880.0,
        -1.0 / 5040.0, 1.0 / 120.0, -1.0 / 6.0, 1.0)


def step_seed(zo_seed: int, step: int) -> jnp.ndarray:
    """int32 counter seed of step ``step`` of a run keyed by ``zo_seed``."""
    key = jax.random.fold_in(jax.random.PRNGKey(zo_seed), step)
    data = key if jnp.issubdtype(key.dtype, jnp.integer) \
        else jax.random.key_data(key)
    return jax.lax.bitcast_convert_type(
        (data[..., 0] ^ data[..., 1]).astype(jnp.uint32), jnp.int32)


def leaf_seed(seed_t: jnp.ndarray, leaf_index: int) -> jnp.ndarray:
    return seed_t + jnp.int32(LEAF_STRIDE) * jnp.int32(leaf_index)


def _mix(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def _uniform(idx, seed, salt: int):
    h = idx * jnp.uint32(0x9E3779B1)
    h = h ^ (seed * jnp.uint32(0x7FEB352D))
    h = h + jnp.uint32(salt) * jnp.uint32(0x846CA68B)
    h = _mix(h)
    u = (h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    return u * (1.0 / 16777216.0) + (0.5 / 16777216.0)


def _log(u):
    bits = jax.lax.bitcast_convert_type(u, jnp.uint32)
    e = (bits >> jnp.uint32(23)).astype(jnp.int32) - 127
    m = jax.lax.bitcast_convert_type(
        (bits & jnp.uint32(0x007FFFFF)) | jnp.uint32(0x3F800000), jnp.float32)
    s = (m - 1.0) / (m + 1.0)
    s2 = s * s
    p = jnp.float32(1.0 / 13.0)
    for c in (1.0 / 11.0, 1.0 / 9.0, 1.0 / 7.0, 1.0 / 5.0, 1.0 / 3.0, 1.0):
        p = p * s2 + jnp.float32(c)
    return jnp.float32(2.0) * (s * p) + e.astype(jnp.float32) * jnp.float32(_LN2)


def _cos2pi(t):
    t4 = t * 4.0
    k = jnp.floor(t4)
    phi = (t4 - k) * jnp.float32(jnp.pi / 2)
    p2 = phi * phi
    c = jnp.float32(_COS[0])
    for coef in _COS[1:]:
        c = c * p2 + jnp.float32(coef)
    s = jnp.float32(_SIN[0])
    for coef in _SIN[1:]:
        s = s * p2 + jnp.float32(coef)
    s = phi * s
    q = k.astype(jnp.int32) & 3
    return jnp.where(q == 0, c, jnp.where(q == 1, -s, jnp.where(q == 2, -c, s)))


def gaussian(idx: jnp.ndarray, seed: jnp.ndarray) -> jnp.ndarray:
    """z at flat element positions ``idx`` (uint32) of a leaf seeded ``seed``."""
    seed = seed.astype(jnp.uint32)
    u1 = _uniform(idx, seed, 1)
    u2 = _uniform(idx, seed, 2)
    r = jnp.sqrt(jnp.maximum(jnp.float32(-2.0) * _log(u1), 0.0))
    return r * _cos2pi(u2)


def leaf_z(shape: tuple, seed: jnp.ndarray, offset: int = 0) -> jnp.ndarray:
    """z of a block of ``shape`` that starts at flat element ``offset`` of its
    leaf (a layer of a stacked leaf starts at ``layer · layer_size``)."""
    n = 1
    for d in shape:
        n *= d
    idx = jnp.uint32(offset) + jnp.arange(n, dtype=jnp.uint32)
    return gaussian(idx, seed).reshape(shape)
