"""Model FLOP/s utilisation of the whole ZO step (%): two forwards' model
FLOPs per step (benchmarks/chip/flops.py) times the steps of the traced
window, over the window and the chip's bf16 peak."""


def read(run):
    if run.get("job") != "zo_train" or not run.get("steps"):
        return None
    rate = run["steps"] * run["flops_per_step"] / run["window_s"]
    return 100.0 * rate / run["peaks"]["bf16_flops"]
