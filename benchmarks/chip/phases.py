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
A program without the scopes gives no op a phase, and every phase time 0.
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


def phase_of(op_name: str):
    """The innermost phase named in an ``op_name`` path, also where a
    transform wraps it (``vmap(zo_forward)``), else ``None``."""
    for part in reversed(op_name.split("/")):
        while part.endswith(")") and "(" in part:    # vmap(zo_forward)
            part = part[part.index("(") + 1:-1]
        if part in PHASES:
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


def op_phases(ops, op_names: dict) -> list:
    """The phase of each device op ``[name, start_ns, dur_ns]``."""
    return [phase_of(op_names.get(instruction(o[0]), "")) for o in ops]


def phase_ns(ops, op_phase, window, phases) -> int:
    """Device time inside the window under any of ``phases``: the union of
    those ops' intervals."""
    return trace_reduce.busy_ns(
        [o for o, ph in zip(ops, op_phase) if ph in phases], window)
