"""Share (%) of the HBM roofline the perturbation kernels reach: the bytes
one step's three passes need (read and write θ each pass, from shapes) over
the chip's HBM bandwidth, against their measured device time.  The other
bound, the VPU work of the z generator, has no entry in the peaks table;
the kernels are counted as bandwidth-bound."""
from benchmarks.chip import trace_reduce


def read(run):
    if run.get("job") != "zo_train" or not run.get("steps"):
        return None
    red = run["trace"]
    ops = trace_reduce.matching(red["device_ops"], run["kernel_needles"])
    kernel_s = trace_reduce.op_ns(ops, red["window"]) / 1e9
    if kernel_s <= 0:
        return None
    least_s = run["steps"] * run["kernel_bytes_per_step"] \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
