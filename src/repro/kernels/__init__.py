# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
import jax

from repro.kernels.paged.gather import paged_gather, paged_gather_ref

__all__ = ["interpret_default", "paged_gather", "paged_gather_ref"]


def interpret_default() -> bool:
    """Interpret mode for a Pallas kernel whose caller did not choose one.

    The CPU runs kernels as jnp (tests, laptops); the TPU compiles them.
    Any other platform raises: a Pallas TPU kernel silently evaluated as jnp
    on a GPU would hide the device it was written for."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels here target the TPU (compiled) or the CPU "
        f"(interpret mode); the default JAX platform is {platform!r}.  Pick "
        "interpret mode explicitly (backend='pallas-interpret') or use the "
        "xla backend")
