"""The ZO step's phase names: stable ``jax.named_scope`` names that metrics
read from the compiled program.

Every device op of a compiled ZO step falls under one of three names in its
HLO ``op_name`` (``jit(step)/zo_perturb/zo_forward/while/body/...``), read
by the innermost of them, also where a transform wraps it
(``vmap(zo_forward)``):

* ``zo_perturb`` — an estimate: every write of θ ± εz made for a forward
  (``perturb``, ``perturb_many``, the rescaled estimator's D⁻¹z writes),
  with any relayout a backend makes around its kernel;
* ``zo_forward`` — every loss evaluation inside an estimate, the batched
  (vmapped) forward included;
* ``zo_update`` — restore, fused restore + update, rank-1 and group
  applications, transform updates (an applier's ``final_params``), and
  ledger replay.

A scope is trace-time metadata: it adds no op and no host call.  Key
derivation, the loss mean and the state update stay unnamed (a few scalar
ops).  The names are a contract: the benchmark reads them
(``benchmarks/chip/phases.py``), so they change only together with it.
"""
from __future__ import annotations

from typing import Optional

import jax

PERTURB = "zo_perturb"
FORWARD = "zo_forward"
UPDATE = "zo_update"
NAMES = (PERTURB, FORWARD, UPDATE)


def estimate(est, loss_fn, params, batch, key, est_state,
             phase: Optional[int] = None):
    """``est.estimate`` under ``zo_perturb``, each of its loss evaluations
    under ``zo_forward``.  ``phase`` is the static block-schedule phase,
    passed on only when given."""
    def forward(p, b):
        with jax.named_scope(FORWARD):
            return loss_fn(p, b)

    kw = {} if phase is None else {"phase": phase}
    with jax.named_scope(PERTURB):
        return est.estimate(forward, params, batch, key, est_state, **kw)
