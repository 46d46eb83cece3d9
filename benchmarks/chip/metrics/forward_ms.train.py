"""Device time per ZO step of the two forwards (ms): the union of the
traced window's device ops under the program's scope ``zo_forward``, from
the compiled step's op names."""
from benchmarks.chip import phases


def read(run):
    ns = phases.scope_ns_per_step(run, (phases.FORWARD,))
    return None if ns is None else ns / 1e6
