"""From a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to plain records first (:func:`load`), so that every
function below works on the same small structure in a test as on the chip:

    {"device_ops": [[name, start_ns, dur_ns], ...],    # one device's ops
     "host_spans": [[name, start_ns, dur_ns], ...],    # the harness's spans
     "window": [start_ns, end_ns]}

Device ops are the events of the ``XLA Ops`` line of the first TPU plane.
On a v5e each is named by its HLO instruction, e.g. ``%zo_affine_2d.52 =
bf16[1638400,512]{...} custom-call(...)`` for a call of the Mosaic kernel
``zo_affine_2d``; a ``%while`` op spans the ops of its body, which appear
too.  Host spans are the ``TraceAnnotation`` events the harness wrote
around its calls into the program; ``window`` encloses the measured window.
"""
from __future__ import annotations

import glob
import os

DEVICE_LINE = "XLA Ops"


def load(trace_dir: str, span_names: tuple) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, spans = [], []
    device_planes = sorted((p for p in data.planes
                            if p.name.startswith("/device:TPU:")),
                           key=lambda p: p.name)
    if device_planes:
        for line in device_planes[0].lines:
            if line.name != DEVICE_LINE:
                continue
            for e in line.events:
                ops.append([e.name, int(e.start_ns), int(e.duration_ns)])
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in span_names:
                    spans.append([e.name, int(e.start_ns), int(e.duration_ns)])
    ops.sort(key=lambda r: r[1])
    spans.sort(key=lambda r: r[1])
    return {"device_ops": ops, "host_spans": spans}


def clip(intervals, window):
    """``[start, end)`` pairs cut to the window, sorted."""
    lo, hi = window
    out = [(max(s, lo), min(s + d, hi)) for _, s, d in intervals]
    return sorted((a, b) for a, b in out if b > a)


def busy_intervals(ops, window) -> list:
    """Union of the device op intervals inside the window."""
    merged = []
    for a, b in clip(ops, window):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(ops, window) -> int:
    return sum(b - a for a, b in busy_intervals(ops, window))


def idle_share(ops, window) -> float:
    """1 − busy / window."""
    return 1.0 - busy_ns(ops, window) / (window[1] - window[0])


def matching(ops, needles) -> list:
    """Ops whose name contains any of ``needles``."""
    return [o for o in ops if any(n in o[0] for n in needles)]


def op_ns(ops, window) -> int:
    """Summed device time of ``ops`` inside the window (a kernel's time)."""
    return sum(b - a for a, b in clip(ops, window))


def top_ops(ops, window, n: int = 10) -> list:
    """``[[name, seconds], ...]``: device time by op family, largest first.
    A family is the instruction's name without its number, and its result
    type, so that one kernel's calls on one shape add up.  A ``%while``
    counts its whole body."""
    by = {}
    for name, s, d in ops:
        a, b = max(s, window[0]), min(s + d, window[1])
        if b > a:
            key = _family(name)
            by[key] = by.get(key, 0) + (b - a)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def _family(name: str) -> str:
    head, _, rest = name.partition(" = ")
    head = head.rstrip("0123456789").rstrip(".")
    return (head + " " + rest.split(" ", 1)[0]).strip()[:120]


def idle_gaps(ops, spans, window, n: int = 10) -> list:
    """The ``n`` longest gaps in device activity inside the window, each
    named by the host span inside the window that overlaps it most
    (``idle`` where none does): ``[[span name, seconds], ...]``, longest
    first."""
    busy = busy_intervals(ops, window)
    gaps, prev = [], window[0]
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if window[1] > prev:
        gaps.append((prev, window[1]))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        best, best_ov = "idle", 0
        for name, s, d in spans:
            if name == "window":
                continue
            ov = min(b, s + d) - max(a, s)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([best, (b - a) / 1e9])
    return out
