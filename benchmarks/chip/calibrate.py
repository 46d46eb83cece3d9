"""Readings that set a training cell's limits, many seeds in one process.

    python3 -m benchmarks.chip.calibrate --workload <cell> --seeds 1,2,... \\
        [--controls N] [--out FILE]

For every seed: the program's checked steps on the chip against the float32
reference (the lower readings).  For the first ``N`` seeds also the control
— the reference put in the program's place, computed in float8 — and the
faults planted in the reference put in the program's place (``half_batch``,
``loss_token``, ``neg_grad``), each against the same float32 reference (the
upper readings).  ``frozen`` (the state left unchanged) reads 1 on ``change_gap``
by construction and needs no run.  One JSON line per seed.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.chip import manifest
from benchmarks.chip import run as runner


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip.kinds import zo_train
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    config = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    devices = jax.devices()
    problem = runner._device_ok(devices, cell["chips"])
    if problem:
        print(f"benchmarks.chip.calibrate: {problem}", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    model = config["model"]
    lines = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        ctx = runner.Ctx(cell, config, traffic, seed, 0, False, devices)
        job = zo_train.Job(ctx)
        peaks = {}
        prog = job.checked(peaks)
        job.params = job.state = job.step = job.compiled = None
        ctx.free()
        ref = job.reference()
        line = {"seed": seed, "peak_bytes": peaks,
                "program": zo_train.compare(
            prog, ref, zo_train.sizes(model)),
            "readings": {"program": prog, "reference": ref}}
        if i < args.controls:
            for name, kw in (("control_fp8", {"precision": "fp8"}),
                             ("half_batch", {"fault": "half_batch"}),
                             ("loss_token", {"fault": "loss_token"}),
                             ("neg_grad", {"fault": "neg_grad"})):
                other = job.reference(**kw)
                line[name] = zo_train.compare(zo_train.as_program(other), ref,
                                              zo_train.sizes(model))
                line["readings"][name] = other
        line["seconds"] = time.time() - t0
        lines.append(json.dumps(line))
        print(lines[-1], flush=True)
        if args.out:
            with open(args.out, "w") as f:
                f.write("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
