"""``paged_gather`` — block-table KV assembly for the paged serving engine.

The paged KV pool (`repro.serve.paged`) stores every sequence's KV in
fixed-size token blocks scattered over one pool tensor ``(L, NT, D)``
(``NT = n_blocks * block`` token rows, ``D = KV·hd`` folded).  Decode needs
each slot's logical view — the blocks named by its block table, in order —
assembled into a dense ``(T, D)`` run.  This kernel is that gather:

    out[l, i*block : (i+1)*block, :] = x[l, table[i]*block : …, :]

The block table is scalar-prefetched (``PrefetchScalarGridSpec``): it sits in
SMEM before the grid runs, and the pool's BlockSpec index map reads it, so
each grid step DMAs exactly one ``(1, block, D)`` pool block HBM→VMEM and the
pool is never resident as a whole.  The arithmetic is a pure copy, so the
kernel is bitwise-equal to the XLA gather by construction
(``paged_gather_ref``, test-enforced).

``interpret=True`` runs the same grid as jnp on the CPU (tests); callers pick
it with ``repro.kernels.interpret_default()``, which refuses a platform that
is neither CPU nor TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(tab_ref, x_ref, o_ref):
    del tab_ref                  # consumed by the index map
    o_ref[...] = x_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def paged_gather(x: jnp.ndarray, table: jnp.ndarray, block: int,
                 interpret: bool = True) -> jnp.ndarray:
    """Gather block rows of ``x (L, NT, D)`` by ``table (n,)`` block ids.

    Returns ``(L, n*block, D)`` where entry ``i`` is the ``block`` token rows
    of pool block ``table[i]``, per layer.  ``table`` entries must lie in
    ``[0, NT // block)``; the caller pads unused entries with a trash block.
    """
    L, NT, D = x.shape
    n = int(table.shape[0])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, n),
        in_specs=[pl.BlockSpec((1, block, D),
                               lambda l, i, tab: (l, tab[i], 0))],
        out_specs=pl.BlockSpec((1, block, D), lambda l, i, tab: (l, i, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, n * block, D), x.dtype),
        interpret=interpret,
    )(table.astype(jnp.int32), x)


@functools.partial(jax.jit, static_argnames=("block",))
def paged_gather_ref(x: jnp.ndarray, table: jnp.ndarray,
                     block: int) -> jnp.ndarray:
    """XLA oracle: one advanced-indexing take over expanded token rows."""
    rows = (table[:, None] * block
            + jnp.arange(block, dtype=jnp.int32)[None, :]).reshape(-1)
    return x[:, rows]
