"""Share (%) of the traced training window in which no operation ran on the
device: 1 − (union of the device op intervals) / window."""
from benchmarks.chip import trace_reduce


def read(run):
    if run.get("job") != "zo_train":
        return None
    red = run["trace"]
    return 100.0 * trace_reduce.idle_share(red["device_ops"], red["window"])
