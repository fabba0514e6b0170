"""Device time per training-step phase, read from a traced run's trace.

The program names the phases of its train step with ``jax.named_scope``
(``repro.obs.PHASES``): every HLO instruction traced inside a scope carries
the scope in its ``op_name`` metadata (``jit(_train_step)/fwd_bwd/...``).
The profiler writes each compiled module's HLO, metadata included, into the
trace's ``/host:metadata`` plane, so the map from an op's name to its phase
is built from the trace itself, after the window has closed:

* ``op_names`` decodes the HLO of the step modules that ran in the window
  (protobuf wire format, read here with no generated classes) into
  {module: {instruction: op_name}};
* ``phase_map`` gives each instruction the first component of its op_name
  that is a phase (a fusion carries its root's metadata);
* ``whole_steps`` finds the step programs (the device's ``XLA Modules``
  events) that ran wholly inside the traced window, on each device;
* ``phase_seconds`` sums the device seconds of the ops inside those steps
  per phase, per step, with the loop and call ops left out as
  ``trace.reduce_events`` leaves them out of its per-op sums, and ops that
  carry no phase under ``"other"``: the phases add up to the summed op
  time of a mean step.  A step the window cuts is left out whole, so the
  mean does not move with where the window falls.

``for_run`` does all four for a traced benchmark run and keeps the result
in the run, for the per-layer readers ``phase_ms.*`` and ``mfu.fwd_bwd``.
It finds nothing (None) where the run has no trace, or where the program
names no phases: a checkout from before the scopes.
"""
from __future__ import annotations

import bisect
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from . import cell as C
from . import trace as T

OTHER = "other"
TRACE_DIR = C.ROOT / ".bench_cache" / "trace"    # bench/run.py's trace_dir
HLO_PROTO_STAT = "Hlo Proto"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varint
    fields, bytes for length-delimited ones; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _string(msg: bytes, num: int) -> str:
    for f, v in fields(msg):
        if f == num:
            return bytes(v).decode()
    return ""


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """HloProto -> {instruction name: op_name} over all its computations."""
    ops = {}
    for f, module in fields(hlo_proto):
        if f != 1:                                # HloProto.hlo_module
            continue
        for g, v in fields(module):
            if g == 3:                            # HloModuleProto.computations
                for h, inst in fields(v):
                    if h != 2:                    # HloComputationProto
                        continue                  # .instructions
                    iname, op_name = "", ""
                    for k, w in fields(inst):
                        if k == 1:                # HloInstructionProto.name
                            iname = bytes(w).decode()
                        elif k == 7:              # .metadata (OpMetadata)
                            op_name = _string(w, 2)
                    ops[iname] = op_name
    return ops


def op_names(path, ran=(), step_module: str = "") -> Dict[str, dict]:
    """{module: {instruction name: op_name}} of HLO modules the trace file
    at ``path`` holds in its ``/host:metadata`` plane, which keeps every
    program the process has loaded, keyed by the plane's name for each
    (``jit_train_step(9288140135439557166)``, as the device's ``XLA
    Modules`` line names the program that ran).  Kept: the modules named in
    ``ran``; where none is, those whose name holds ``step_module``; where
    none does, every one."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    protos: Dict[str, object] = {}
    for num, plane in fields(space):
        if num != 1 or _string(plane, 2) != "/host:metadata":   # XPlane
            continue
        stat_names, metas = {}, []
        for g, v in fields(plane):
            if g == 5:          # stat_metadata: map<int64, XStatMetadata>
                entry = dict(fields(v))
                stat_names[entry.get(1, 0)] = _string(entry.get(2, b""), 2)
            elif g == 4:        # event_metadata: map<int64, XEventMetadata>
                metas.append(dict(fields(v)).get(2, b""))
        proto_ids = {k for k, n in stat_names.items() if n == HLO_PROTO_STAT}
        for meta in metas:
            name = _string(meta, 2)                # XEventMetadata.name
            for h, stat in fields(meta):
                if h != 5:                         # XEventMetadata.stats
                    continue
                st = dict(fields(stat))
                if st.get(1) in proto_ids and 6 in st:  # bytes_value
                    protos[name] = st[6]
    keep = ([m for m in protos if m in ran]
            or [m for m in protos if step_module and step_module in m]
            or list(protos))
    return {m: _hlo_op_names(protos[m]) for m in keep}


def phase_map(modules: Dict[str, Dict[str, str]],
              phase_of: Callable[[str], Optional[str]]) -> Dict[str, str]:
    """{instruction name: phase} over the modules' instructions; those
    whose op_name holds no phase are left out."""
    out = {}
    for m in modules:
        for inst, op_name in modules[m].items():
            p = phase_of(op_name)
            if p is not None:
                out[inst] = p
    return out


def window(ev: T.Events) -> Tuple[float, float]:
    """(start, end) ns of the ``bench.traced`` span."""
    win = [(a, b) for n, a, b in ev.host if n == "bench.traced"]
    if not win:
        raise ValueError("trace has no bench.traced window")
    return win[0]


def whole_steps(ev: T.Events, step_module: str,
                n_devices: int) -> Dict[int, List[Tuple[float, float]]]:
    """{device: [(start, end) ns]} of the step programs that ran wholly
    inside the window.  A step the window cuts at either end is left out,
    so where the window falls does not change the steps' mean."""
    lo, hi = window(ev)
    return {d: sorted((a, b) for n, a, b in ev.modules.get(d, [])
                      if step_module in n and lo <= a and b <= hi)
            for d in sorted(ev.ops)[:n_devices]}


def phase_seconds(ev: T.Events, phases: Dict[str, str],
                  steps: Dict[int, List[Tuple[float, float]]]
                  ) -> Dict[str, float]:
    """Device seconds per phase of one step: the ops that ran inside the
    ``steps`` spans, summed per phase over the devices and divided by the
    number of spans.  Ops with no phase go under ``"other"``."""
    out: Dict[str, float] = {}
    n = sum(len(s) for s in steps.values())
    if not n:
        raise ValueError("no step ran wholly inside the window")
    for d, spans in steps.items():
        starts = [a for a, _ in spans]
        for name, a, b in ev.ops.get(d, []):
            k = bisect.bisect_right(starts, a) - 1
            if k < 0 or a >= spans[k][1] or T.CONTAINER.match(name):
                continue
            p = phases.get(name, OTHER)
            out[p] = out.get(p, 0.0) + min(b, spans[k][1]) - a
    return {p: t / n * 1e-9 for p, t in out.items()}


def trace_file(run: C.Run):
    """The ``.xplane.pb`` of this run's traced segment, or None."""
    return min((TRACE_DIR / run.cell.name).rglob("*.xplane.pb"),
               default=None)


def for_run(run: C.Run) -> Optional[Dict[str, float]]:
    """{phase: device seconds per step} of a traced run, computed once and
    kept in ``run.extra``; None where there is nothing to read."""
    if "phases" in run.extra:
        return run.extra["phases"]
    run.extra["phases"] = None
    if run.trace is None:
        return None
    try:
        from repro.obs import phase_of
    except ImportError:                 # a program that names no phases
        return None
    path = trace_file(run)
    step_module = run.cell.traffic.get("step_module", "")
    if path is None or not step_module:
        return None
    ev = T.load(path)
    steps = whole_steps(ev, step_module, run.counters.get("chips", 1))
    lo, hi = window(ev)
    ran = {n for mods in ev.modules.values() for n, a, b in mods
           if step_module in n and lo <= a < hi}
    phases = phase_map(op_names(path, ran, step_module), phase_of)
    if not phases or not any(steps.values()):
        return None
    run.extra["phases"] = phase_seconds(ev, phases, steps)
    return run.extra["phases"]


def ms_per_step(run: C.Run, phase: str) -> Optional[float]:
    """Device milliseconds of one phase per train step, or None."""
    r = for_run(run)
    if r is None:
        return None
    return 1e3 * r.get(phase, 0.0)
