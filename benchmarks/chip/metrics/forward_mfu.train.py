"""Model FLOP/s utilisation of the two forwards (%): their model FLOPs per
step (benchmarks/chip/flops.py) over the device time per step under the
scope ``zo_forward`` and the chip's bf16 peak."""
from benchmarks.chip import phases


def read(run):
    ns = phases.scope_ns_per_step(run, (phases.FORWARD,))
    if ns is None:
        return None
    rate = run["flops_per_step"] / (ns / 1e9)
    return 100.0 * rate / run["peaks"]["bf16_flops"]
