"""``repro.perturb`` — pluggable perturbation backends behind one z-stream
contract.

The paper's entire memory story is that the perturbation direction z is
*regenerated from a seed, never stored*.  This package owns that regeneration:
``StreamRef`` is the one canonical identity of a z stream
(run seed → step → seed index → leaf index), and ``PerturbBackend`` is the
interface through which every consumer — estimators, the transform chain,
trajectory replay, checkpoint recovery, async workers, seed-parallel
collectives — perturbs or updates parameters.  Nothing outside this package
decides *how* z is generated.

Backend selection
-----------------
Pick per run via ``zo.mezo(..., backend=...)`` (or any preset /
``ZOEstimator`` factory); the choice is recorded in checkpoint and ledger
metadata so a replay under the wrong backend raises ``BackendMismatchError``
instead of silently reconstructing different parameters.

``backend="xla"`` (default)
    Threefry streams lowered by XLA.  z tiles are short-lived **HBM**
    temporaries inside the jitted step; with buffer donation the sequential
    perturb → loss → perturb → loss → update chain keeps one parameter-sized
    buffer alive (the paper's inference-memory property).  Partitioning-aware:
    under ``pjit`` each shard generates exactly its slice of the global z.
    Supports gaussian / rademacher / sphere.

``backend="pallas"``
    The fused Pallas kernel: z is generated tile-by-tile **inside VMEM** from
    a counter hash of (seed, element index) and never exists in HBM at all —
    perturb/update is one read-modify-write stream over the parameters at
    pure memory-bandwidth speed, with zero z traffic.  On a TPU it runs
    compiled; on the CPU it runs in Pallas interpret mode (identical
    arithmetic, jnp-evaluated) so CPU runs and CI exercise the same stream;
    on any other platform it raises rather than interpret in silence.
    Supports gaussian, rademacher (sign of one counter stream, generated
    in-kernel) and sphere (the gaussian stream rescaled by a kernel-measured
    ‖z‖; see the matrix in ``repro.perturb.base``).

``backend="pallas-interpret"``
    Same stream as ``pallas`` with interpret mode forced, on any platform —
    the only way to interpret on a TPU.  For measuring interpreter overhead
    (``benchmarks/bench_perturb.py``) and for debugging kernel semantics
    under jnp.

The two backends generate *different* (both valid N(0,1)) z streams for the
same ``StreamRef``; within a backend the stream is bitwise-stable across
tree restructuring and padding boundaries (contract-tested in
``tests/test_perturb_backend.py``).

Batched multi-seed streams
--------------------------
``PerturbBackend.perturb_many`` stacks B perturbed views of θ for
batched-seed estimators (``zo.fzoo``).  Both backends override the
stacked-singles default with genuinely vectorized generation — ``xla`` vmaps
threefry over the stacked per-seed keys, ``pallas`` runs the batched-seed
kernel (B z-streams generated against each resident VMEM tile of x) — and
both are bitwise-equal to stacking per-ref ``perturb`` calls
(contract-tested for B ∈ {1, 3, 8} across dtypes).

The default backend honors the ``REPRO_BACKEND`` environment variable (CI's
pallas-interpret job runs the unmodified suite under the fused kernel).

Parameter selection
-------------------
A ``StreamRef`` may carry a ``repro.select.Selection`` (static leaf predicate
+ optional block schedule): both backends *skip* unselected leaves in every
method — zero z generation and zero writes, not a masked multiply.  See
:mod:`repro.select`.

Extending
---------
New strategies (quantized z, mixed-stream formats) implement
``PerturbBackend`` and register with ``register_backend``; every existing
estimator × transform composition picks them up through the same kwarg.
"""
from repro.perturb.base import (BackendMismatchError, PerturbBackend,
                                available_backends, check_replay_backend,
                                get_backend, register_backend)
from repro.perturb.stream import StreamRef, as_stream_ref, step_key
from repro.perturb.xla import XLABackend

register_backend("xla", XLABackend)


# The pallas module pulls in jax.experimental.pallas (slow import, and a hard
# dependency xla-only runs don't need) — defer it to first resolution.
def _pallas():
    from repro.perturb.pallas import PallasBackend
    return PallasBackend()


def _pallas_interpret():
    from repro.perturb.pallas import PallasBackend
    return PallasBackend(interpret=True)


register_backend("pallas", _pallas)
register_backend("pallas-interpret", _pallas_interpret)


def __getattr__(name):      # PEP 562: `from repro.perturb import PallasBackend`
    if name == "PallasBackend":
        from repro.perturb.pallas import PallasBackend
        return PallasBackend
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "BackendMismatchError", "PerturbBackend", "StreamRef", "as_stream_ref",
    "XLABackend", "PallasBackend",
    "available_backends", "check_replay_backend", "get_backend",
    "register_backend", "step_key",
]
