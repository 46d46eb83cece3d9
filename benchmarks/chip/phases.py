"""Device time by phase of the ZO step, from the step's own scope names.

The program names the three phases of a ZO step with ``jax.named_scope``
(``zo_perturb``, ``zo_forward``, ``zo_update``).  A scope is metadata of
each HLO instruction (its ``op_name``, e.g. ``jit(step)/zo_perturb/
zo_forward/while/body/dot_general``); a device op is in the phase named
innermost in that path, and in none where no phase is named.  The time of
a phase is the union of its ops' intervals inside the window, not their
sum: a ``%while`` op spans the ops of its body, which appear too.

The op names come from the compiled step's HLO text (:func:`hlo_op_names`),
keyed by the instruction name that the v5e trace gives each device op
(``%zo_affine_2d.52 = bf16[...] custom-call(...)``, :func:`instruction`).
A traced run of the training job hands that map to the metric readers as
``run["op_names"]``, taken after the window.  A program without the scopes
gives no op a phase, and every phase time 0.
The names are the program's (``repro.zo.phases``), spelled here so that the
reduction also runs on a program that lacks them.
"""
from __future__ import annotations

import re

from benchmarks.chip import trace_reduce

PERTURB, FORWARD, UPDATE = "zo_perturb", "zo_forward", "zo_update"
PHASES = (PERTURB, FORWARD, UPDATE)

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="([^"]*)"',
                    re.M)


def phase_of(op_name: str, scopes=PHASES):
    """The innermost of ``scopes`` named in an ``op_name`` path, also where
    a transform wraps it (``vmap(zo_forward)``), else ``None``."""
    for part in reversed(op_name.split("/")):
        while part.endswith(")") and "(" in part:    # vmap(zo_forward)
            part = part[part.index("(") + 1:-1]
        if part in scopes:
            return part
    return None


def hlo_op_names(hlo_text: str) -> dict:
    """Instruction name → ``op_name`` for every instruction of a compiled
    module's text that carries one."""
    return dict(_INSTR.findall(hlo_text))


def instruction(op_name_in_trace: str) -> str:
    """The HLO instruction a trace event names: ``%fusion.3 = f32[8]
    fusion(...)`` → ``fusion.3``."""
    return op_name_in_trace.split(" = ", 1)[0].strip().lstrip("%")


def op_phases(ops, op_names: dict, scopes=PHASES) -> list:
    """The innermost of ``scopes`` that names each device op ``[name,
    start_ns, dur_ns]``, or ``None``."""
    return [phase_of(op_names.get(instruction(o[0]), ""), scopes)
            for o in ops]


def phase_ns(ops, op_phase, window, phases) -> int:
    """Device time inside the window under any of ``phases``: the union of
    those ops' intervals."""
    return trace_reduce.busy_ns(
        [o for o, ph in zip(ops, op_phase) if ph in phases], window)


def scope_ns_per_step(run: dict, scopes) -> float | None:
    """Device time a step of a traced run under ``scopes`` (ns): the union
    of the window's device ops whose innermost named scope is one of
    ``scopes``, over ``run["steps"]``.  Innermost among the three phases and
    ``scopes``, so that a scope inside ``zo_forward`` takes its ops from the
    forward, and ``zo_forward`` inside ``zo_perturb`` from the perturbation.
    ``None`` where the run has no op names (``run["op_names"]``, traced runs
    only) or no op falls under ``scopes`` inside the window."""
    op_names, steps = run.get("op_names"), run.get("steps")
    if not op_names or not steps:
        return None
    scopes = tuple(scopes)
    among = PHASES + tuple(s for s in scopes if s not in PHASES)
    ops = run["trace"]["device_ops"]
    ns = phase_ns(ops, op_phases(ops, op_names, among), run["trace"]["window"],
                  scopes)
    return ns / steps if ns else None
