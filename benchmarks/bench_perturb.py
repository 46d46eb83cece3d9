"""Perturbation-backend comparison: per-step wall-clock and peak memory of
the same MeZO composition under each ``repro.perturb`` backend.

Backends:
  * ``xla``              — threefry z as HBM temporaries (default).
  * ``pallas-interpret`` — the fused kernel under Pallas interpret mode
                           (CPU-runnable; measures interpreter overhead, not
                           kernel speed).
  * ``pallas``           — the compiled kernel (TPU; recorded as unavailable
                           when the host platform cannot compile it).

Peak memory is the compiled step's static analysis (argument + temp bytes),
the same methodology as bench_memory; on backends/platforms where XLA does
not expose it the record says so instead of guessing.

Output: CSV rows on stdout (the ``benchmarks/run.py`` contract) plus one JSON
document at ``results/bench_perturb.json`` for machine consumption.
"""
from __future__ import annotations

import json
import os

import jax

from benchmarks.common import emit, note, time_fn, tiny_lm
from repro import zo
from repro.data.synthetic import lm_batch
from repro.models import bundle

BACKENDS = ("xla", "pallas-interpret", "pallas")
OUT_PATH = os.path.join("results", "bench_perturb.json")


def _peak_bytes(step_fn, params, state, batch):
    compiled = jax.jit(step_fn).lower(params, state, batch).compile()
    ma = compiled.memory_analysis()
    if ma is None or not hasattr(ma, "temp_size_in_bytes"):
        return None
    return int(ma.argument_size_in_bytes) + int(ma.temp_size_in_bytes)


def run():
    cfg = tiny_lm(d_model=128, n_layers=2, ff=256, vocab=512)
    b = bundle(cfg)
    params = b.init(jax.random.PRNGKey(0))
    loss_fn = b.loss_fn()
    batch = lm_batch(0, 0, 4, 32, cfg.vocab_size)

    records = []
    baseline_us = None
    for backend in BACKENDS:
        rec = {"backend": backend, "status": "ok"}
        try:
            if backend == "pallas":
                # force the COMPILED kernel: on the CPU get_backend("pallas")
                # interprets, which would just duplicate the
                # pallas-interpret row instead of reporting "unavailable"
                # honestly
                from repro.perturb import PallasBackend
                be = PallasBackend(interpret=False)
            else:
                from repro.perturb import get_backend
                be = get_backend(backend)
            if hasattr(be, "interpret"):
                rec["interpret"] = bool(be.interpret)
            opt = zo.mezo(lr=1e-4, eps=1e-3, backend=be)
            state = opt.init(params, seed=0)
            step_fn = opt.step_fn(loss_fn)
            us = time_fn(jax.jit(step_fn), params, state, batch)
            rec["us_per_step"] = us
            try:
                rec["peak_bytes"] = _peak_bytes(step_fn, params, state, batch)
            except Exception as e:      # CPU backend may not expose analysis
                rec["peak_bytes"] = None
                rec["peak_bytes_error"] = f"{type(e).__name__}: {e}"
            if backend == "xla":
                baseline_us = us
            slow = (us / baseline_us) if baseline_us else 0.0
            emit(f"perturb/{backend}_step", us, f"vs_xla={slow:.2f}x")
            pk = rec["peak_bytes"]
            emit(f"perturb/{backend}_peak_bytes", 0.0,
                 str(pk) if pk is not None else "unavailable")
            note(f"{backend}: {us/1e3:.2f} ms/step, peak "
                 f"{pk/1e6:.2f} MB" if pk else
                 f"{backend}: {us/1e3:.2f} ms/step, peak unavailable")
        except Exception as e:
            # e.g. compiled pallas on a host without a TPU lowering
            rec["status"] = "unavailable"
            rec["error"] = f"{type(e).__name__}: {e}"
            emit(f"perturb/{backend}_step", 0.0, "unavailable")
            note(f"{backend}: unavailable ({rec['error'][:120]})")
        records.append(rec)

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    with open(OUT_PATH, "w") as f:
        json.dump({"bench": "perturb_backends",
                   "platform": jax.default_backend(),
                   "model": {"d_model": 128, "n_layers": 2, "ff": 256},
                   "records": records}, f, indent=2)
    note(f"JSON written to {OUT_PATH}")


if __name__ == "__main__":
    run()
