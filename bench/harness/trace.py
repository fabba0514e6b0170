"""Reduction of a JAX profiler trace (``.xplane.pb``) to per-layer numbers.

What it reads:

* the window: the host span ``bench.traced`` that the harness opens right
  after the profiler starts and closes right before it stops;
* per device plane (``/device:TPU:<i>``), the ``XLA Ops`` line: one event
  per operation that ran on the device, named by its HLO instruction
  (``fusion.12``, ``gossip_mix_update_flat.1``); a ``while`` or ``call``
  op spans the ops it runs, and counts for busy time only;
* per device, the ``XLA Modules`` line: one event per program execution,
  counted as steps where its name holds the traffic's ``step_module``;
* the host spans the harness opens around its own calls (``bench.*``).

What it gives, averaged over the devices traced:

* busy seconds: the union of the operations' intervals in the window;
* per kernel (the traffic's ``kernels``: name -> substring of the op
  name): summed seconds, and its calls on device 0;
* collective seconds, and the exposed part: collective intervals not
  covered by any other operation on that device;
* the operations that took most time, and the idle gaps of device 0, each
  put down to the innermost host span around its midpoint.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

COLLECTIVE = re.compile(r"collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|collective-broadcast")
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    steps: int
    kernel_s: Dict[str, float]
    kernel_calls: Dict[str, int]
    collective_s: float
    collective_exposed_s: float
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops[:10]],
                "idle_gaps": [list(x) for x in self.idle_gaps[:10]]}


def union(intervals) -> List[Tuple[float, float]]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a_int, b_int) -> List[Tuple[float, float]]:
    """Merged intervals ``a_int`` minus merged intervals ``b_int``."""
    out, j = [], 0
    for a, b in a_int:
        cur = a
        while j < len(b_int) and b_int[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_int) and b_int[k][0] < b:
            s, e = b_int[k]
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


@dataclasses.dataclass
class Events:
    """The trace as plain lists of (name, start_ns, end_ns)."""
    host: List[Tuple[str, float, float]]
    ops: Dict[int, List[Tuple[str, float, float]]]
    modules: Dict[int, List[Tuple[str, float, float]]]


CONTAINER = re.compile(r"^(while|conditional|call)\b")


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path) -> Events:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host, ops, modules = [], {}, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.search(plane.name)
        for line in plane.lines:
            evs = [(short_name(e.name), e.start_ns,
                    e.start_ns + e.duration_ns) for e in line.events]
            if m:
                dev = int(m.group(1))
                if line.name == "XLA Ops":
                    ops[dev] = evs
                elif line.name == "XLA Modules":
                    modules[dev] = evs
            elif plane.name.startswith("/host"):
                host.extend(e for e in evs if e[0].startswith("bench."))
    return Events(host=host, ops=ops, modules=modules)


def reduce(path, *, n_devices: int, kernels: dict,
           step_module: str = "") -> Reduction:
    return reduce_events(load(path), n_devices=n_devices, kernels=kernels,
                         step_module=step_module)


def reduce_events(ev: Events, *, n_devices: int, kernels: dict,
                  step_module: str = "") -> Reduction:
    win = [(a, b) for n, a, b in ev.host if n == "bench.traced"]
    if not win:
        raise ValueError("trace has no bench.traced window")
    lo, hi = win[0]
    devs = sorted(ev.ops)[:n_devices]
    if not devs:
        raise ValueError("trace has no device operations")
    busy = coll = exposed = 0.0
    ksum = {k: 0.0 for k in kernels}
    kcalls = {k: 0 for k in kernels}
    per_op: Dict[str, float] = {}
    gaps0 = []
    for d in devs:
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in ev.ops[d]
               if b > lo and a < hi]
        all_u = union((a, b) for _, a, b in ops)
        busy += length(all_u)
        # a loop or call op spans the ops it runs: it hides nothing itself
        ops = [o for o in ops if not CONTAINER.match(o[0])]
        c_u = union((a, b) for n, a, b in ops if COLLECTIVE.search(n))
        other_u = union((a, b) for n, a, b in ops
                        if not COLLECTIVE.search(n))
        coll += length(c_u)
        exposed += length(subtract(c_u, other_u))
        for n, a, b in ops:
            per_op[n] = per_op.get(n, 0.0) + (b - a)
            for k, pat in kernels.items():
                if pat in n:
                    ksum[k] += b - a
                    kcalls[k] += d == devs[0]
        if d == devs[0]:
            gaps0 = subtract([(lo, hi)], all_u)
    nd = len(devs)
    steps = 0
    if step_module:
        steps = sum(1 for n, a, b in ev.modules.get(devs[0], [])
                    if step_module in n and a >= lo and a < hi)
    top = sorted(((n, s / nd * 1e-9) for n, s in per_op.items()),
                 key=lambda x: -x[1])
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy / nd * 1e-9, steps=steps,
        kernel_s={k: v / nd * 1e-9 for k, v in ksum.items()},
        kernel_calls=kcalls,
        collective_s=coll / nd * 1e-9,
        collective_exposed_s=exposed / nd * 1e-9,
        top_ops=top, idle_gaps=attribute(gaps0, ev.host))


def attribute(gaps, host) -> List[Tuple[str, float]]:
    """Idle seconds per innermost host span around each gap's midpoint."""
    spans = [(a, b, n) for n, a, b in host if n != "bench.traced"]
    out: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        inner = [(e - s, n) for s, e, n in spans if s <= mid <= e]
        name = min(inner)[1] if inner else "no bench span"
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return sorted(out.items(), key=lambda x: -x[1])
