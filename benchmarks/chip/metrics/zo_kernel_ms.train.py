"""Device time per ZO step of the Pallas perturbation kernels (ms), from
the trace: every op whose name or kernel text names the zo_affine kernels."""
from benchmarks.chip import trace_reduce


def read(run):
    if run.get("job") != "zo_train" or not run.get("steps"):
        return None
    red = run["trace"]
    ops = trace_reduce.matching(red["device_ops"], run["kernel_needles"])
    if not ops:
        return None
    return trace_reduce.op_ns(ops, red["window"]) / 1e6 / run["steps"]
