"""One run of one benchmark cell on the chip.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and its configuration, traffic and
limits by name (:mod:`benchmarks.chip.manifest`), hands the job to the
driver its traffic file names (``kinds/<kind>.py``), and prints one JSON
object as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, ``breakdown`` when
traced, and last ``checks``: each number compared with its limit, which
also close standard error.  Earlier lines say how many compilations fell
inside the window (none should).

There is no fallback: without a TPU, with fewer chips than the cell asks
for, or on a device the peaks table does not know, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import os
import time


def _process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = _process_start()

import argparse                                                   # noqa: E402
import contextlib                                                 # noqa: E402
import gc                                                         # noqa: E402
import json                                                       # noqa: E402
import math                                                       # noqa: E402
import shutil                                                     # noqa: E402
import sys                                                        # noqa: E402

from benchmarks.chip import manifest                              # noqa: E402

ROOT = manifest.ROOT
OUT = ROOT / ".bench_out"
sys.path.insert(0, str(ROOT / "src"))

import jax                                                        # noqa: E402

from benchmarks.chip import peaks as peaks_mod                    # noqa: E402
from benchmarks.chip import trace_reduce                          # noqa: E402
from benchmarks.chip import weights as wgen                       # noqa: E402

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_trace_duration")


class Ctx:
    """What a job driver gets: the cell's files, the seeds, the clock, and
    the hooks that mark the end of set-up and the measured window."""

    def __init__(self, cell, config, traffic, seed, seconds, trace,
                 devices, fault=None, span_names=()):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.seeds = wgen.seeds(seed)
        self.devices = devices
        self.fault = fault
        self.span_names = ("window",) + tuple(span_names)
        self.setup_s = None
        self.compiles_in_window = 0
        self._in_window = False
        self.reduced_trace = None
        self.window_peak = None
        self.window_stats = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self._in_window and event in _COMPILE_EVENTS:
            self.compiles_in_window += 1

    def setup_done(self):
        self.setup_s = time.time() - T_START

    @contextlib.contextmanager
    def window(self):
        trace_dir = OUT / "trace" / self.cell["name"]
        if self.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # host spans and device ops only: tracing every Python call
            # stalls the host for hundreds of ms inside the window
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        self._in_window = True
        try:
            with jax.profiler.TraceAnnotation("window"):
                yield
        finally:
            self._in_window = False
            if self.trace:
                jax.profiler.stop_trace()
            # the peak as the window leaves it, before any check runs
            self.window_peak = self.read_peak()
            self.window_stats = [d.memory_stats() for d in self.devices]
        if self.trace:
            red = trace_reduce.load(str(trace_dir), self.span_names)
            win = [s for s in red["host_spans"] if s[0] == "window"][-1]
            red["window"] = [win[1], win[1] + win[2]]
            self.reduced_trace = red
            shutil.rmtree(trace_dir, ignore_errors=True)

    def read_peak(self) -> int:
        """The fullest chip's peak: arrays in use plus the scratch the TPU
        runtime reserves for the loaded programs' temporaries, which
        ``peak_bytes_in_use`` leaves out."""
        peaks = []
        for d in self.devices:
            s = d.memory_stats() or {}
            peaks.append(s.get("peak_bytes_in_use", 0)
                         + s.get("peak_bytes_reserved", 0))
        return max(peaks)

    @staticmethod
    def free():
        gc.collect()


def _device_ok(devices, chips: int) -> str | None:
    d0 = devices[0]
    if d0.platform != "tpu":
        return (f"no TPU: JAX platform {d0.platform!r} "
                f"({len(devices)} {d0.device_kind} device(s))")
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devices)}"
    try:
        peaks_mod.peaks_for(d0)
    except peaks_mod.UnknownDevice as e:
        return str(e)
    return None


def _fmt_checks(numbers: dict, limits: dict) -> dict:
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def main(argv=None, *, allow_cpu: bool = False, fault=None,
         overrides: dict | None = None) -> int:
    """``allow_cpu``, ``fault`` and ``overrides`` (``config``, ``traffic``,
    ``limits``, ``bench``) are for the tests, which drive a run at a small
    size on the CPU with the timed path broken underneath."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    over = overrides or {}

    bench = over.get("bench") or manifest.load()
    cell = manifest.cell(bench, args.workload)
    config = over.get("config") or manifest.config(cell["config"])
    traffic = over.get("traffic") or manifest.traffic(cell["traffic"])
    limits = over.get("limits") or manifest.limits(cell["name"])

    devices = jax.devices()
    problem = _device_ok(devices, cell["chips"])
    if problem and not allow_cpu:
        print(f"benchmarks.chip.run: {problem}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import importlib
    kind = importlib.import_module(f"benchmarks.chip.kinds.{traffic['kind']}")
    ctx = Ctx(cell, config, traffic, args.seed, args.seconds, bool(args.trace),
              devices, fault=fault, span_names=kind.SPAN_NAMES)
    try:
        out = kind.run(ctx)
    finally:
        jax.monitoring.unregister_event_duration_listener(ctx._on_event)

    numbers = out["numbers"]
    checks = _fmt_checks(numbers, limits)
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": ctx.window_peak}
    metrics = {}
    result = {}
    if args.trace:
        red = ctx.reduced_trace
        win = red["window"]
        busy = trace_reduce.busy_ns(red["device_ops"], win) / 1e9
        device["busy_s"] = busy
        device["window_s"] = (win[1] - win[0]) / 1e9
        run = dict(out["layer_ctx"], trace=red, busy_s=busy,
                   trace_window_s=device["window_s"],
                   peaks=peaks_mod.peaks_for(d0))
        for m in manifest.per_layer(bench, cell["name"]):
            value = manifest.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(red["device_ops"], win),
            "idle_gaps": trace_reduce.idle_gaps(red["device_ops"],
                                                red["host_spans"], win)}
    else:
        e2e = dict(out["end_to_end"], setup_s=("s", ctx.setup_s))
        for m in manifest.end_to_end(bench, cell["name"]):
            unit, value = e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": unit}

    print(json.dumps({"info": out["info"], "setup_s": ctx.setup_s,
                      "compiles_in_window": ctx.compiles_in_window,
                      "memory_stats": ctx.window_stats,
                      "readings": out["readings"]}), flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps({"correct": correct, "attempted": out["attempted"],
                      "failed": out["failed"], "metrics": metrics,
                      "device": device, **result, "checks": checks}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
