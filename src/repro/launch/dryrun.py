import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) builds the 256/512-chip production
# meshes out of host-platform placeholder devices; smoke tests and benches
# see the normal single device.

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell and record memory/cost/collective analysis for the roofline report.

Usage:
  python -m repro.launch.dryrun                      # all cells, both meshes
  python -m repro.launch.dryrun --arch qwen2-7b --cell train_4k --mesh single
  python -m repro.launch.dryrun --set attention_impl=chunked --tag chunked
  python -m repro.launch.dryrun --ep-mesh --arch mixtral-8x7b   # EP hillclimb

Outputs one JSON line per case to results/dryrun.jsonl (append).
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import flops as flops_lib
from repro.analysis import roofline as roofline_lib
from repro import exec as zexec
from repro import zo
from repro.distributed.sharding import (infer_batch_spec,
                                        make_activation_resolver,
                                        param_shardings)
from repro.launch.mesh import make_ep_mesh, make_production_mesh
from repro.models import all_archs, bundle, cells_for
from repro.models.common import shard_resolver
from repro.models.config import ALL_CELLS
from repro.models.rwkv6 import RWKVLayerState


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def batch_sharding_tree(cfg, specs: dict, mesh):
    """Map the input_specs dict (incl. nested caches/states) to shardings."""
    out = {}
    for name, sds in specs.items():
        if name == "cache":
            out[name] = {
                "k": _ns(mesh, infer_batch_spec("cache_k", sds["k"].shape, mesh)),
                "v": _ns(mesh, infer_batch_spec("cache_v", sds["v"].shape, mesh)),
                "pos": _ns(mesh, infer_batch_spec("cache_pos_arr",
                                                  sds["pos"].shape, mesh)),
            }
        elif name == "cross_kv":
            out[name] = {
                "k": _ns(mesh, infer_batch_spec("cross_k", sds["k"].shape, mesh)),
                "v": _ns(mesh, infer_batch_spec("cross_v", sds["v"].shape, mesh)),
            }
        elif name == "state":
            if isinstance(sds, RWKVLayerState):
                out[name] = RWKVLayerState(
                    shift_tm=_ns(mesh, infer_batch_spec("rwkv_shift",
                                                        sds.shift_tm.shape, mesh)),
                    shift_cm=_ns(mesh, infer_batch_spec("rwkv_shift",
                                                        sds.shift_cm.shape, mesh)),
                    wkv=_ns(mesh, infer_batch_spec("rwkv_wkv", sds.wkv.shape, mesh)),
                )
            else:
                out[name] = _ns(mesh, infer_batch_spec("ssm_state", sds.shape, mesh))
        else:
            out[name] = _ns(mesh, infer_batch_spec(name, sds.shape, mesh))
    return out


def replicated_tree(tree, mesh):
    return jax.tree_util.tree_map(lambda _: _ns(mesh, P()), tree)


def _compile_case(cfg, b, cell, mesh, donate: bool = True,
                  backend: str = "xla", estimator: str = "spsa",
                  batch_seeds: int = 8, exec_plan: str = "local",
                  n_groups: int = 1, selection: str = "full"):
    """Lower + compile the cell's step function; returns the compiled exe."""
    specs = b.input_specs(cell)
    params_sds = b.param_shapes()
    pshard = param_shardings(params_sds, mesh)
    bshard = batch_sharding_tree(cfg, specs, mesh)
    resolver_p = make_activation_resolver(mesh, cfg)
    resolver = lambda logical, shape: (
        _ns(mesh, resolver_p(logical, shape))
        if resolver_p(logical, shape) is not None else None)

    if cell.kind == "train":
        if estimator == "fzoo":
            opt = zo.fzoo(lr=1e-6, eps=1e-3, batch_seeds=batch_seeds,
                          backend=backend, selection=selection)
        else:
            opt = zo.mezo(lr=1e-6, eps=1e-3, estimator=estimator,
                          backend=backend, selection=selection)
        # the engine lowers the same composition onto the requested plan;
        # the dry-run proves each (estimator × backend × plan) cell COMPILES
        # on the production meshes, not just the blessed local path
        plan = (zexec.seed_parallel(n_groups, mesh=mesh)
                if exec_plan == "seed_parallel" else zexec.local())
        prog = zexec.StepProgram(opt, plan)
        state_sds = jax.eval_shape(lambda: prog.init(seed=0))
        sshard = replicated_tree(state_sds, mesh)
        step = prog.step_fn(b.loss_fn())
        jitted = jax.jit(step, in_shardings=(pshard, sshard, bshard),
                         donate_argnums=(0,) if donate else ())
        args = (params_sds, state_sds, specs)
    elif cell.kind == "prefill":
        jitted = jax.jit(b.prefill_fn(), in_shardings=(pshard, bshard))
        args = (params_sds, specs)
    else:
        jitted = jax.jit(b.decode_fn(), in_shardings=(pshard, bshard),
                         donate_argnums=(1,) if donate else ())
        args = (params_sds, specs)

    with mesh:
        with shard_resolver(resolver):
            lowered = jitted.lower(*args)
    return lowered.compile()


def _cost_triple(compiled):
    """(flops, hbm_bytes, collective_bytes) per chip from a compiled exe."""
    cost = compiled.cost_analysis()
    try:
        coll = roofline_lib.collective_stats(compiled.as_text())
    except Exception:
        coll = {"total_bytes": 0}
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll.get("total_bytes", 0)), coll)


def calibrate_loop_costs(arch, cell, mesh, overrides: dict):
    """XLA's cost analysis counts while-loop bodies ONCE, not × trip count.
    All sequential recurrences in this codebase are loop-free (chunked
    matmul + associative_scan), leaving exactly one loop: the scan over
    layers.  Compile UNROLLED 1- and 2-layer variants of the same cell —
    per-layer cost = f(2) − f(1) exactly (layers are homogeneous) — and
    return (outside, per_layer) triples for extrapolation to the real L."""
    cals = {}
    for L in (1, 2):
        over = dict(overrides)
        over.update(n_layers=L, scan_layers=False)
        if arch.cfg.family == "encdec":
            over["encoder_layers"] = L
        cfg_L = dataclasses.replace(arch.cfg, **over)
        compiled = _compile_case(cfg_L, bundle(cfg_L), cell, mesh, donate=False)
        cals[L] = _cost_triple(compiled)[:3]
    per_layer = tuple(cals[2][i] - cals[1][i] for i in range(3))
    outside = tuple(cals[1][i] - per_layer[i] for i in range(3))
    return outside, per_layer


def run_case(arch_id: str, cell, mesh, mesh_name: str, overrides: dict,
             optimizer: str = "mezo", verbose: bool = True,
             calibrate: bool = True, backend: str = "xla",
             estimator: str = "spsa", batch_seeds: int = 8,
             exec_plan: str = "local", n_groups: int = 1,
             selection: str = "full") -> dict:
    arch = all_archs()[arch_id]
    cfg = arch.cfg
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    b = bundle(cfg)
    chips = int(mesh.devices.size)
    rec = {"arch": arch_id, "cell": cell.name, "mesh": mesh_name,
           "chips": chips, "optimizer": optimizer,
           "perturb_backend": backend, "estimator": estimator,
           "batch_seeds": batch_seeds if estimator == "fzoo" else 1,
           "exec_plan": exec_plan,
           "n_groups": n_groups if exec_plan == "seed_parallel" else 1,
           "selection": selection,
           "overrides": {k: str(v) for k, v in overrides.items()},
           "status": "ok"}
    t0 = time.time()
    try:
        compiled = _compile_case(cfg, b, cell, mesh, backend=backend,
                                 estimator=estimator,
                                 batch_seeds=batch_seeds,
                                 exec_plan=exec_plan, n_groups=n_groups,
                                 selection=selection)
        t_compile = time.time() - t0
        flops_raw, hbm_raw, coll_raw, coll_detail = _cost_triple(compiled)
        rec["raw"] = {"flops": flops_raw, "hbm_bytes": hbm_raw,
                      "collective_bytes": coll_raw}

        # loop-trip correction via 1/2-layer unrolled calibration compiles
        flops, hbm, coll_b = flops_raw, hbm_raw, coll_raw
        if calibrate and cfg.scan_layers:
            t1 = time.time()
            outside, per_layer = calibrate_loop_costs(arch, cell, mesh,
                                                      overrides)
            L = cfg.n_layers
            flops = outside[0] + L * per_layer[0]
            hbm = outside[1] + L * per_layer[1]
            coll_b = outside[2] + L * per_layer[2]
            rec["calibration"] = {"outside": outside, "per_layer": per_layer,
                                  "calib_s": round(time.time() - t1, 2)}

        model_fl = flops_lib.model_flops(cfg, cell, optimizer)
        roof = roofline_lib.Roofline(
            arch=arch_id, cell=cell.name, mesh=mesh_name, chips=chips,
            flops_per_chip=flops, hbm_bytes_per_chip=hbm,
            link_bytes_per_chip=coll_b,
            model_flops=model_fl["model_flops"],
            model_flops_6nd=model_fl["model_flops_6nd"],
            collectives=coll_detail).finalize()
        rec.update(dataclasses.asdict(roof))
        rec["compile_s"] = round(t_compile, 2)
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis_str"] = str(ma)[:2000] if ma is not None else None
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes"):
                if hasattr(ma, k):
                    rec.setdefault("memory_analysis", {})[k] = int(getattr(ma, k))
        except Exception as e:  # CPU backend may not support it
            rec["memory_analysis_str"] = f"unavailable: {e}"
        if verbose:
            print(f"[dryrun] {arch_id:22s} {cell.name:12s} {mesh_name:6s} "
                  f"OK  compile={t_compile:6.1f}s "
                  f"flops/chip={rec['flops_per_chip']:.3e} "
                  f"bottleneck={rec['bottleneck']:10s} "
                  f"roofline={rec['roofline_fraction']:.3f}", flush=True)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch_id:22s} {cell.name:12s} {mesh_name:6s} "
                  f"FAIL {rec['error'][:200]}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all assigned)")
    ap.add_argument("--cell", default=None,
                    help="train_4k|prefill_32k|decode_32k|long_500k")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--ep-mesh", action="store_true",
                    help="use the expert-parallel mesh factorization (MoE)")
    ap.add_argument("--mesh-shape", default=None,
                    help="override data,model (e.g. 32,8) — same 256 chips, "
                         "different DP/TP factorization (hillclimb lever)")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. attention_impl=chunked)")
    ap.add_argument("--optimizer", default="mezo", choices=["mezo"])
    ap.add_argument("--estimator", default="spsa",
                    choices=["spsa", "one_point", "fzoo"],
                    help="gradient estimator for the train cells; 'fzoo' "
                         "compiles the batched-seed one-sided step "
                         "(--batch-seeds streams, one vmapped forward)")
    ap.add_argument("--batch-seeds", type=int, default=8,
                    help="seed streams per step for --estimator fzoo")
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas", "pallas-interpret"],
                    help="perturbation backend for the train cells")
    ap.add_argument("--exec-plan", default="local",
                    choices=["local", "seed_parallel"],
                    help="execution plan for the train cells (repro.exec)")
    ap.add_argument("--n-groups", type=int, default=2,
                    help="seed groups for --exec-plan seed_parallel")
    ap.add_argument("--select", default="full",
                    help="parameter selection for the train cells "
                         "(repro.select spec: full, leaves(<regex>), "
                         "block_cyclic(<k>), peft(lora|prefix), "
                         "moe_experts(<G>)) or 'auto' for the registry's "
                         "per-family default")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    from repro.configs import ASSIGNED_ARCHS
    archs = [args.arch] if args.arch else ASSIGNED_ARCHS

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        fields = {f.name: f.type for f in dataclasses.fields(
            all_archs()[archs[0]].cfg)}
        if v.isdigit():
            v = int(v)
        elif v in ("True", "False"):
            v = v == "True"
        else:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", False))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", True))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    n_ok = n_fail = 0
    with open(args.out, "a") as f:
        for mesh_name, multi in meshes:
            for arch_id in archs:
                cfg = all_archs()[arch_id].cfg
                if args.ep_mesh:
                    mesh = make_ep_mesh(cfg.n_experts or 8, multi_pod=multi)
                    mesh_label = mesh_name + "-ep"
                elif args.mesh_shape:
                    d, m = (int(x) for x in args.mesh_shape.split(","))
                    mesh = jax.make_mesh((d, m), ("data", "model"))
                    mesh_label = f"{mesh_name}-{d}x{m}"
                else:
                    mesh = make_production_mesh(multi_pod=multi)
                    mesh_label = mesh_name
                cells = cells_for(cfg)
                if args.cell:
                    cells = [c for c in ALL_CELLS if c.name == args.cell]
                    if cells[0] not in cells_for(cfg):
                        print(f"[dryrun] {arch_id} {args.cell}: skipped "
                              f"(N/A per DESIGN.md §4)", flush=True)
                        continue
                selection = args.select
                if selection == "auto":
                    # registry per-family default (same hook as launch/train)
                    from repro.models import default_selection
                    selection = default_selection(
                        dataclasses.replace(cfg, **overrides)
                        if overrides else cfg)
                for cell in cells:
                    # the roofline table is single-pod; the multi-pod pass
                    # proves the 'pod' axis shards (compile success + memory)
                    rec = run_case(arch_id, cell, mesh, mesh_label, overrides,
                                   calibrate=(mesh_name == "single"),
                                   backend=args.backend,
                                   estimator=args.estimator,
                                   batch_seeds=args.batch_seeds,
                                   exec_plan=args.exec_plan,
                                   n_groups=args.n_groups,
                                   selection=selection)
                    if args.tag:
                        rec["tag"] = args.tag
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    n_ok += rec["status"] == "ok"
                    n_fail += rec["status"] != "ok"
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
