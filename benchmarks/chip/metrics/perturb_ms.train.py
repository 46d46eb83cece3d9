"""Device time per ZO step of the perturbation and update passes (ms): the
union of the traced window's device ops under the program's scopes
``zo_perturb`` or ``zo_update`` (not under ``zo_forward`` inside them), from
the compiled step's op names.  The Pallas kernels and the relayout copies
around them."""
from benchmarks.chip import phases


def read(run):
    ns = phases.scope_ns_per_step(run, (phases.PERTURB, phases.UPDATE))
    return None if ns is None else ns / 1e6
