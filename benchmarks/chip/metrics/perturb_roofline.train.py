"""Share (%) of the HBM roofline the perturbation and update passes reach:
the bytes one step's three passes need (read and write θ each pass, from
shapes) at the chip's HBM bandwidth, over the device time per step under
the scopes ``zo_perturb`` and ``zo_update``."""
from benchmarks.chip import phases


def read(run):
    ns = phases.scope_ns_per_step(run, (phases.PERTURB, phases.UPDATE))
    if ns is None:
        return None
    least_s = run["kernel_bytes_per_step"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
