"""The ZO step's phase names (``repro.zo.phases``) land where the benchmark
reads them (``benchmarks/chip/phases.py``): a tiny step of every estimator,
on both perturbation backends, under the local plan, the seed-parallel plan
on the suite's CPU mesh and ledger replay, is compiled, and each HLO
instruction's ``op_name`` is read from the compiled text.

* every phase the plan has appears (replay has only ``zo_update``);
* every ``dot`` is under ``zo_forward``;
* no random-bit or z-generation op is under ``zo_forward``;
* every parameter-sized op of replay is under ``zo_update``, and every
  parameter-sized op of a step under some phase.

An op is what a device trace names: an instruction outside fusion bodies.
The dot and z-generation checks read each instruction's own ``op_name``:
XLA may fuse work of two phases into one op, which a trace counts in its
root's phase.
"""
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import exec as zexec
from repro import zo
from repro.core.trajectory import TrajectoryLedger
from repro.exec import StepProgram
from repro.zo import phases

from benchmarks.chip.phases import PHASES, phase_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BACKENDS = ["xla", "pallas-interpret"]
ESTIMATORS = ["spsa", "n_spsa", "fzoo", "one_point", "rescaled_spsa"]
PLANS = ["local", "seed_parallel", "replay"]
CASES = ([(e, b, p) for e in ESTIMATORS for b in BACKENDS for p in PLANS]
         + [("mezo_adam", b, "local") for b in BACKENDS])

# ops XLA adds or that move no data; they may carry no op_name of their own
# (nor may a fusion that only broadcasts a constant)
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "copy", "copy-start", "copy-done", "opt-barrier"}
# the z generators: threefry (xla) and the counter hash of the kernel (pallas)
ZGEN_OPS = {"rng", "rng-bit-generator", "xor", "shift-right-logical",
            "shift-left"}
ZGEN_NAMES = ("threefry", "random_bits", "pallas_call")

PARAMS = {"embed": jnp.full((8, 16), 0.1), "layers": jnp.full((2, 16, 16), 0.05),
          "head": jnp.full((16, 4), 0.1)}
BATCH = {"x": jnp.ones((4, 8)), "y": jnp.zeros((4, 4))}
LEAST_LEAF = min(int(np.prod(p.shape)) for p in PARAMS.values())


def loss_fn(p, b):
    def layer(h, w):
        return jnp.tanh(h @ w), None
    h, _ = jax.lax.scan(layer, b["x"] @ p["embed"], p["layers"])
    return jnp.mean((h @ p["head"] - b["y"]) ** 2)


def make_opt(name, backend):
    kw = dict(lr=1e-3, eps=1e-3, backend=backend)
    return {"spsa": lambda: zo.mezo(**kw),
            "n_spsa": lambda: zo.mezo(n=2, **kw),
            "fzoo": lambda: zo.fzoo(batch_seeds=2, **kw),
            "one_point": lambda: zo.mezo(estimator="one_point", **kw),
            "rescaled_spsa": lambda: zo.mezo_rescaled(
                modify_expectation=True, **kw),
            "mezo_adam": lambda: zo.mezo_adam(**kw)}[name]()


def _instruction(line):
    """``(opcode, elements, op_name, fusion body)`` of one HLO text line,
    or ``None`` where the line is no instruction."""
    m = re.match(r"\s*(?:ROOT )?%\S+ = (.*)$", line)
    if not m:
        return None
    rest = m.group(1)
    if rest.startswith("("):               # a tuple: skip to its end
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = "", rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    op = re.match(r"\s*([\w-]+)\(", rest)
    name = re.search(r'op_name="([^"]*)"', rest)
    calls = re.search(r"calls=%([\w.-]+)", rest)
    dims = re.match(r"\w+\[([\d,]*)\]", shape)
    elements = 0 if dims is None else math.prod(
        int(d) for d in dims.group(1).split(",") if d)
    return (op.group(1) if op else "?", elements,
            name.group(1) if name else "",
            calls.group(1) if calls and op and op.group(1) == "fusion"
            else None)


def device_ops(hlo_text):
    """The ops a device runs, as a trace names them: every instruction
    outside fusion bodies, each as ``(opcode, elements, phase, inner)``
    with ``inner`` the ``(opcode, op_name)`` of it and of its fusion
    body's instructions, which XLA may leave without an op_name."""
    comps, comp = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = comps.setdefault(head.group(1), [])
            continue
        ins = _instruction(line)
        if ins is not None and comp is not None:
            comp.append(ins)
    bodies = {c for ops in comps.values() for *_, c in ops if c}
    out = []
    for name, ops in comps.items():
        if name in bodies:
            continue
        for op, n, op_name, body in ops:
            inner = [(op, op_name)] + [(o, nm) for o, _, nm, _ in
                                       comps.get(body, [])]
            out.append((op, n, phase_of(op_name), inner))
    return out


def compiled_step(opt, plan):
    if plan == "local":
        prog = StepProgram(opt, zexec.local())
        state = prog.init(PARAMS, seed=3)
        return jax.jit(prog.step_fn(loss_fn)).lower(
            PARAMS, state, BATCH).compile().as_text()
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    prog = StepProgram(opt, zexec.seed_parallel(2, mesh=mesh))
    state = prog.init(PARAMS, seed=3)
    pshard, sshard, bshard = prog.shardings(PARAMS, BATCH)
    with mesh:
        return jax.jit(prog.step_fn(loss_fn),
                       in_shardings=(pshard, sshard, bshard)).lower(
            PARAMS, state, BATCH).compile().as_text()


def compiled_replay(opt):
    """A two-entry ledger of the local plan's shape, replayed inside one
    jit so that the whole replay program is one compiled module."""
    live = StepProgram(opt, zexec.local())
    meta = live.meta
    ledger = TrajectoryLedger(base_seed=3, grad_dtype="float32",
                              backend=meta["perturb_backend"],
                              batch_seeds=meta["batch_seeds"],
                              exec_plan=meta["exec_plan"],
                              n_groups=meta["n_groups"])
    width = meta["n_groups"] * meta["batch_seeds"]
    for t in range(2):
        g = 0.5 - t
        ledger.append(t, np.full((width,), g, np.float32) if width > 1
                      else g, 1e-3)
    prog = StepProgram(opt, zexec.replay())
    return jax.jit(lambda p: prog.replay(p, ledger)).lower(
        PARAMS).compile().as_text()


def check_step(ops):
    """Every phase named, every parameter-sized op in one, dots in the
    forward, no z generation in it."""
    seen = {ph for _, _, ph, _ in ops if ph is not None}
    big = [(op, ph, inner[0][1]) for op, n, ph, inner in ops
           if n >= LEAST_LEAF and any(o not in PLUMBING | {"broadcast"}
                                      for o, _ in inner[op == "fusion":])]
    assert big and seen == set(phases.NAMES)
    assert all(ph is not None for _, ph, _ in big), \
        [x for x in big if x[1] is None][:5]
    # each instruction by its own op_name: XLA may fuse work of two phases
    # into one op, which a trace then counts in the phase of its root
    own = [(o, nm, phase_of(nm)) for *_, inner in ops for o, nm in inner]
    dots = [(ph, nm) for o, nm, ph in own if o == "dot"]
    assert dots and all(ph == phases.FORWARD for ph, _ in dots), dots[:5]
    zgen_in_forward = [(o, nm) for o, nm, ph in own if ph == phases.FORWARD
                       and (o in ZGEN_OPS
                            or any(z in nm for z in ZGEN_NAMES))]
    assert not zgen_in_forward, zgen_in_forward[:5]


@pytest.mark.parametrize("estimator,backend,plan", CASES)
def test_ops_fall_under_their_phase(estimator, backend, plan):
    opt = make_opt(estimator, backend)
    if plan != "replay":
        check_step(device_ops(compiled_step(opt, plan)))
        return
    ops = device_ops(compiled_replay(opt))
    big = [(op, ph, inner[0][1]) for op, n, ph, inner in ops
           if n >= LEAST_LEAF and op not in PLUMBING]
    assert big and {ph for _, _, ph, _ in ops} - {None} == {phases.UPDATE}
    assert all(ph == phases.UPDATE for _, ph, _ in big), \
        [x for x in big if x[1] != phases.UPDATE][:5]
    assert not any(o == "dot" for *_, inner in ops for o, _ in inner)


def per_device_ops(backend):
    """The seed-parallel step's per-device body (one group per device,
    ``shard_map``) on a mesh of two CPU devices; run in a process of its
    own, which has them."""
    mesh = jax.make_mesh((2, 1), ("data", "model"))
    prog = StepProgram(make_opt("spsa", backend),
                       zexec.seed_parallel(2, mesh=mesh))
    state = prog.init(PARAMS, seed=3)
    pshard, _, bshard = prog.shardings(PARAMS, BATCH)
    with mesh:
        text = jax.jit(prog.step_fn(loss_fn),
                       in_shardings=(pshard, None, bshard)).lower(
            PARAMS, state, BATCH).compile().as_text()
    assert "all-gather" in text          # the per-device body, not the loop
    return device_ops(text)


_PER_DEVICE = """
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, {tests!r})
import test_phases
print(json.dumps(test_phases.per_device_ops({backend!r})))
"""


@pytest.mark.parametrize("backend", BACKENDS)
def test_seed_parallel_per_device_ops_fall_under_their_phase(backend):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"), REPO]))
    script = _PER_DEVICE.format(tests=os.path.join(REPO, "tests"),
                                backend=backend)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    check_step([tuple(o) for o in json.loads(out.stdout.splitlines()[-1])])


def test_the_benchmark_reads_the_programs_phase_names():
    assert PHASES == phases.NAMES
    assert phase_of("jit(step)/zo_perturb/zo_forward/while/body/dot") \
        == phases.FORWARD
    assert phase_of("jit(step)/zo_perturb/vmap(zo_forward)/while/body/dot") \
        == phases.FORWARD
    assert phase_of("jit(step)/zo_update/jit(zo_affine)/pallas_call") \
        == phases.UPDATE
    assert phase_of("jit(step)/jit(step_key)/threefry2x32") is None
    assert phase_of("jit(step)/zo_perturbation/add") is None
    assert phase_of("") is None
