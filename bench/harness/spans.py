"""Device time per model span, read from a traced run's trace.

The program names parts of its model inside the ``fwd_bwd`` phase with
``jax.named_scope`` (``repro.obs.SPANS``: ``mla``, ``moe_route``,
``moe_experts``).  As for the phases (``phases.py``), the map from an op to
its span is built from the HLO the profiler stores in the trace, and the
ops of the step programs that ran wholly inside the traced window are
summed per span and divided by the number of those steps; the backward
and the recomputed forward of a span count as the span.

XLA's TPU compiler replaces each ragged dot, the held experts' grouped
matmul, by a kernel of its own whose op_name is ``ragged-dot-none`` (and
its group metadata's ``ragged-dot-metadata``), with the scopes around it
dropped.  The program's only ragged dots are in ``moe_experts``, so
``span_of`` here gives those ops that span.

``for_run`` finds nothing (None) where the run has no trace, or where the
program names no spans: a checkout from before them.
"""
from __future__ import annotations

from typing import Dict, Optional

from . import cell as C
from . import phases as P
from . import trace as T


def span_of(op_name: str) -> Optional[str]:
    """The program's span of an op_name, the compiler's ragged-dot kernels
    counted as ``moe_experts``."""
    from repro.obs import span_of as program_span
    if op_name.startswith("ragged-dot"):
        return "moe_experts"
    return program_span(op_name)


def for_run(run: C.Run) -> Optional[Dict[str, float]]:
    """{span: device seconds per step} of a traced run, computed once and
    kept in ``run.extra``; None where there is nothing to read."""
    if "spans" in run.extra:
        return run.extra["spans"]
    run.extra["spans"] = None
    if run.trace is None:
        return None
    try:
        from repro.obs import SPANS  # noqa: F401
    except ImportError:                 # a program that names no spans
        return None
    path = P.trace_file(run)
    step_module = run.cell.traffic.get("step_module", "")
    if path is None or not step_module:
        return None
    ev = T.load(path)
    steps = P.whole_steps(ev, step_module, run.counters.get("chips", 1))
    lo, hi = P.window(ev)
    ran = {n for mods in ev.modules.values() for n, a, b in mods
           if step_module in n and lo <= a < hi}
    spans = P.phase_map(P.op_names(path, ran, step_module), span_of)
    if not spans or not any(steps.values()):
        return None
    run.extra["spans"] = P.phase_seconds(ev, spans, steps)
    return run.extra["spans"]


def ms_per_step(run: C.Run, span: str) -> Optional[float]:
    """Device milliseconds of one span per train step, or None where the
    run has no spans or the program ran none of this one."""
    r = for_run(run)
    if r is None or span not in r:
        return None
    return 1e3 * r[span]
