"""Does each training cell's step fit one chip?  Compile it for a described
TPU v5e (no chip attached) and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 -m benchmarks.chip.fit [--workload <cell> ...]

Each line is one cell: parameter (argument) bytes, temporaries, the sum,
and whether the Mosaic kernels are in the compiled step.  A compile here is
a fit check, not a measurement: it says nothing about time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmarks.chip import manifest                              # noqa: E402

sys.path.insert(0, str(manifest.ROOT / "src"))

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402


def fit_zo_train(config: dict, traffic: dict, device) -> dict:
    from jax.sharding import SingleDeviceSharding

    from repro import zo
    from repro.exec import as_step_program
    from repro.models import ModelConfig, bundle

    from benchmarks.chip import weights as wgen

    model = config["model"]
    one = SingleDeviceSharding(device)
    b = bundle(ModelConfig(**model))
    from repro.perturb.pallas import PallasBackend

    # the kernels compiled for the chip, not interpreted as on this CPU
    opt = zo.mezo(lr=traffic["lr"], eps=traffic["eps"],
                  estimator=traffic["estimator"],
                  backend=PallasBackend(interpret=False),
                  selection=traffic["selection"])
    program = as_step_program(opt)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree_util.tree_map(spec, wgen.shapes(model))
    state = jax.tree_util.tree_map(
        spec, jax.eval_shape(lambda: program.init(None, seed=0)))
    shape = (traffic["batch"], traffic["seq"])
    batch = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one),
             "labels": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)}
    compiled = jax.jit(program.step_fn(b.loss_fn()), donate_argnums=(0,)) \
        .lower(params, state, batch).compile()
    mem = compiled.memory_analysis()
    return {"arguments_gb": mem.argument_size_in_bytes / 1e9,
            "temporaries_gb": mem.temp_size_in_bytes / 1e9,
            "sum_gb": (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
            / 1e9,
            "mosaic_kernel": "tpu_custom_call" in compiled.as_text()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = manifest.load()
    names = args.workload or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = manifest.cell(bench, name)
        traffic = manifest.traffic(cell["traffic"])
        if traffic["kind"] != "zo_train":
            print(json.dumps({"workload": name, "skipped": traffic["kind"]}))
            continue
        out = fit_zo_train(manifest.config(cell["config"]), traffic,
                           topo.devices[0])
        print(json.dumps({"workload": name, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
