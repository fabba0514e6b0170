"""Names the program gives its own work, for a profiler to read.

``PHASES`` are the phases of a training step.  Every train step (the
research trainer's flat and pytree steps, the launch path's DPSGD, AD-PSGD
and SSGD steps) wraps its work in ``jax.named_scope(<phase>)``:

* ``fwd_bwd`` -- the per-learner forward and backward passes;
* ``gossip``  -- the exchange between learners: mixing rounds,
  collective-permutes, consensus averaging, and the flat engine's fused
  gossip kernel, which mixes and applies the momentum update in one pass;
* ``update``  -- the optimizer and ``apply_updates`` wherever they run
  outside that kernel;
* ``stats``   -- the step's metrics (loss, gradient norms, weight spread).

A scope only sets the ``op_name`` metadata of the HLO instructions traced
inside it (``jit(_train_step)/fwd_bwd/...``): the compiled operations,
fusions and numerics are unchanged.  ``phase_of`` reads the phase back from
an op_name.

``SPANS`` name parts of the model inside ``fwd_bwd``, in the same way:

* ``mla``         -- latent attention (``models/mla.py``), projections to
  output;
* ``moe_route``   -- the MoE router, expert selection, the sort and gather
  into expert order and the scatter-add that combines the results;
* ``moe_experts`` -- the grouped matmuls over the held experts and their
  activation.

``span_of`` reads the innermost span back from an op_name.
"""
from __future__ import annotations

import functools
import re

import jax

PHASES = ("fwd_bwd", "gossip", "update", "stats")
SPANS = ("mla", "moe_route", "moe_experts")


class phase:
    """``jax.named_scope(name)`` for one of ``PHASES``, as a context manager
    or a function decorator (each call of the function opens its own
    scope, so the decorated function may be re-entered)."""

    def __init__(self, name: str):
        if name not in PHASES:
            raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
        self.name = name

    def __enter__(self):
        self._scope = jax.named_scope(self.name)
        return self._scope.__enter__()

    def __exit__(self, *exc):
        return self._scope.__exit__(*exc)

    def __call__(self, fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(self.name):
                return fn(*args, **kwargs)
        return scoped


def phase_of(op_name: str):
    """The first component of an op_name path that is a phase, or None."""
    for part in op_name.split("/"):
        if part in PHASES:
            return part
    return None


def span(name: str):
    """``jax.named_scope(name)`` for one of ``SPANS``."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; one of {SPANS}")
    return jax.named_scope(name)


_WRAPPED = re.compile(r"(?:[\w-]+\()*([\w-]+)\)*")


def span_of(op_name: str):
    """The innermost span an op_name path names, or None.  Inside a
    differentiated function a scope's component carries the transforms
    around it (``jvp(mla)``, ``transpose(jvp(mla))``)."""
    for part in reversed(op_name.split("/")):
        m = _WRAPPED.fullmatch(part)
        if m and m.group(1) in SPANS:
            return m.group(1)
    return None
