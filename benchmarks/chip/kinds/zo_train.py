"""A MeZO fine-tuning job: the program's jitted ZO step, driven as
``train/loop.py`` drives it, timed over the window.

Set-up builds one object — the step compiled with the parameters donated,
its state, the weights from the seed — and drives it through the first
``checked_steps`` steps on the window's own feed; the window then continues
with that same object.  After the window the program's state is freed and
the reference (:mod:`benchmarks.chip.reference.mezo`) follows those first
steps.  The numbers (the cell's limits file says which are compared):

* ``loss_gap``   — worst relative gap of a step's loss;
* ``g_gap``      — each step's projected gradient as the optimizer gets it
  (read from its state after the step), with its sign:
  max_t |g_t − g_ref,t| / max(rms_t |g_ref,t|, G_FLOOR).  The gradient of
  leaf l is g·z_l and z is shared, so this is the gradient's gap by every
  leaf.  The scale does not vanish where the steps' g lie near 0: sound
  runs' |g_t − g_ref,t| is bf16 loss noise over 2ε, under 0.2 at the
  cells' sizes, so the floor keeps their ratio under that;
* ``change_gap`` — the parameters' change after the checked steps, by the
  worst leaf: | ‖Δθ_l‖ − ‖Δθ_ref,l‖ | / max(‖Δθ_ref,l‖, median leaf's).

The program-side readings are taken in set-up without holding more on the
device than the step does, so the peak that the window leaves is the
step's own: ``change_norms`` draws θ_0 inside the reduction.
"""
from __future__ import annotations

import statistics
import time

import jax
import numpy as np

from benchmarks.chip import flops, phases
from benchmarks.chip import weights as wgen
from benchmarks.chip.reference import mezo as ref_mezo

SPAN_NAMES = ("window", "step_dispatch", "ledger_fetch", "feed")
# The Pallas perturbation kernels as the TPU trace names them.
KERNEL_NEEDLES = ("zo_affine",)
# Least scale of ``g_gap``'s denominator, in units of the projected gradient.
G_FLOOR = 1.0


class Feed:
    """Batch ``t`` of the job: ``batch`` rows of ``seq`` tokens drawn
    uniformly from the vocabulary by ``(data seed, t)``, labels the next
    token.  Every row of every step differs."""

    def __init__(self, data_seed: int, batch: int, seq: int, vocab: int):
        self.seed, self.shape, self.vocab = data_seed, (batch, seq + 1), vocab

    def host(self, t: int) -> dict:
        rng = np.random.default_rng([self.seed, t])
        x = rng.integers(0, self.vocab, self.shape, dtype=np.int32)
        return {"tokens": x[:, :-1], "labels": x[:, 1:]}

    def __call__(self, t: int) -> dict:
        return jax.device_put(self.host(t))


def check_layout(bundle, model: dict) -> None:
    """The benchmark's weights have the program's tree, shapes and dtype."""
    want = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    have = wgen.shapes(model)
    if jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(have):
        raise ValueError("the program's parameter tree differs from the "
                         f"benchmark's layout: {want} vs {have}")
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(have)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a} differs from the layout's {b}")


def change_norms(params, model: dict, weights_seed: int) -> dict:
    """Per leaf, ‖θ − θ_0‖ in float32, θ_0 drawn again from the seed."""
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        name = "/".join(k.key for k in path)
        out[name] = float(wgen.gap_to_seed(leaf, model, weights_seed, name))
    return out


def compare(prog: dict, ref: dict, sizes: dict) -> dict:
    """The three numbers of the check (see the module's docstring)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    g_scale = max(statistics.fmean(g * g for g in ref["g"]) ** 0.5, G_FLOOR)
    g_gap = max(abs(a - b) for a, b in zip(prog["g"], ref["g"])) / g_scale
    # a leaf whose reference gradient |g_ref|·‖z_l‖ (‖z_l‖ ≈ sqrt(size)) is
    # under a thousandth of the median leaf's moves by round-off alone
    gnorm = {p: abs(ref["g"][0]) * sizes[p] ** 0.5 for p in ref["change"]}
    med_g = statistics.median(gnorm.values())
    kept = [p for p in ref["change"] if gnorm[p] >= 1e-3 * med_g]
    med = statistics.median(ref["change"][p] for p in kept)
    change_gap = max(abs(prog["change"][p] - ref["change"][p])
                     / max(ref["change"][p], med) for p in kept)
    return {"loss_gap": loss_gap, "g_gap": g_gap, "change_gap": change_gap}


def build(ctx):
    """The program's step object, its state and the job's feed."""
    from repro import zo
    from repro.core import TrajectoryLedger
    from repro.exec import as_step_program
    from repro.models import ModelConfig, bundle

    model, tr = ctx.config["model"], ctx.traffic
    cfg = ModelConfig(**model)
    b = bundle(cfg)
    check_layout(b, model)
    params = wgen.make(model, ctx.seeds["weights"])
    opt = zo.mezo(lr=tr["lr"], eps=tr["eps"], estimator=tr["estimator"],
                  backend=tr["backend"], selection=tr["selection"])
    program = as_step_program(opt)
    state = program.init(params, seed=ctx.seeds["zo"])
    feed = Feed(ctx.seeds["data"], tr["batch"], tr["seq"], model["vocab_size"])
    compiled = jax.jit(program.step_fn(b.loss_fn()), donate_argnums=(0,)) \
        .lower(params, state, feed(0)).compile()
    ledger = TrajectoryLedger(base_seed=ctx.seeds["zo"], grad_dtype="float32",
                              backend=opt.backend_name,
                              batch_seeds=opt.batch_seeds,
                              selection=opt.selection_spec,
                              sel_phase=opt.selection_phase)
    return compiled, params, state, feed, ledger


class Job:
    """The step object, its state and feed, driven through the checked
    steps; :func:`run` hands the same object to the window."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.compiled, self.params, self.state, self.feed, self.ledger = \
            build(ctx)
        self.step = self.compiled if ctx.fault is None \
            else ctx.fault(self.compiled)
        self.k = ctx.traffic["checked_steps"]
        self.t = 0

    def one(self):
        """One step as the training loop makes it: feed, dispatch, ledger
        record (which waits for the step's scalars)."""
        with jax.profiler.TraceAnnotation("feed"):
            batch = self.feed(self.t)
        with jax.profiler.TraceAnnotation("step_dispatch"):
            self.params, self.state, m = self.step(self.params, self.state,
                                                   batch)
        with jax.profiler.TraceAnnotation("ledger_fetch"):
            self.ledger.append(self.t, float(m["projected_grad"]),
                               float(m["lr"]))
        self.t += 1
        return m

    def checked(self, peaks: dict | None = None) -> dict:
        """The program's readings over the checked steps.  ``peaks`` gets
        the device's peak after the steps and after the readings."""
        prog = {"losses": [], "g": []}
        for _ in range(self.k):
            m = self.one()
            prog["losses"].append(float(m["loss"]))
            prog["g"].append(float(self.state.last_projected_grad))
        if peaks is not None:
            peaks["steps"] = self.ctx.read_peak()
        prog["change"] = change_norms(self.params, self.ctx.config["model"],
                                      self.ctx.seeds["weights"])
        if peaks is not None:
            peaks["readings"] = self.ctx.read_peak()
        return prog

    def reference(self, **kw) -> dict:
        tr = self.ctx.traffic
        return ref_mezo.steps(self.ctx.config["model"],
                              self.ctx.seeds["weights"], self.ctx.seeds["zo"],
                              [self.feed.host(t) for t in range(self.k)],
                              tr["lr"], tr["eps"], **kw)


def sizes(model: dict) -> dict:
    return {p: int(np.prod(s[0])) for p, s in wgen.leaf_specs(model).items()}


def as_program(ref: dict) -> dict:
    """Reference readings in the program's form (the control, a fault)."""
    return {"losses": ref["losses"], "g": ref["g"], "change": ref["change"]}


def run(ctx) -> dict:
    model, tr = ctx.config["model"], ctx.traffic
    job = Job(ctx)
    # the device's peak after each part of set-up, and after the window
    peaks = {"weights": ctx.read_peak()}
    memory = job.compiled.memory_analysis()
    prog = job.checked(peaks)
    jax.block_until_ready(job.params)
    ctx.setup_done()

    n, t0 = 0, time.perf_counter()
    with ctx.window():
        while True:
            job.one()
            n += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= ctx.seconds:
                break
    window_s = elapsed
    peaks["window"] = ctx.window_peak
    info = {"steps": n, "window_s": window_s, "step_ms": 1e3 * window_s / n,
            "peak_bytes": peaks}
    traced = {}
    if ctx.trace:
        # the scope of each device op, for the readers of program spans
        t0 = time.perf_counter()
        traced["op_names"] = phases.hlo_op_names(job.compiled.as_text())
        info["op_names_s"] = time.perf_counter() - t0
        traced["traffic"] = tr
    job.params = job.state = job.step = job.compiled = None
    ctx.free()

    ref = job.reference()
    tokens_per_step = tr["batch"] * tr["seq"]
    out = {
        "attempted": n, "failed": 0,
        "numbers": compare(prog, ref, sizes(model)),
        "readings": {"program": prog, "reference": ref},
        "end_to_end": {
            "train_tokens_per_s": ("tokens/s", n * tokens_per_step / window_s),
            "peak_hbm_gb": ("GB", ctx.window_peak / 1e9),
        },
        "info": info,
        "layer_ctx": {
            "job": "zo_train", "steps": n, "window_s": window_s,
            "model": model,
            "flops_per_step": 2 * flops.forward_flops(model, tr["batch"],
                                                      tr["seq"]),
            "kernel_bytes_per_step": flops.zo_kernel_bytes_per_step(model),
            "kernel_needles": KERNEL_NEEDLES,
            "temp_bytes": memory.temp_size_in_bytes,
            **traced,
        },
    }
    return out

