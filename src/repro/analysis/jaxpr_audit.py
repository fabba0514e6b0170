"""jaxpr-level invariant rules (DESIGN §16).

The engine's performance contracts — no parameter-sized concatenate in the
hot step (PR 3), buffer donation actually honored by XLA (PR 3), one
collective per live neighbor slot (PR 4), bf16 params ship bf16 gossip
(PR 3), no host callback inside a jitted step — were each hand-checked at
least once in an ad-hoc test.  This module turns them into reusable rules
over a traced jaxpr (or a compiled executable, for the contracts only XLA
can vouch for), so any entry point can be audited with one call and CI runs
the whole set before any benchmark (see ``repro.analysis.run``).

Traversal helpers (`iter_eqns`, `count_primitive`, `max_concat_elems`)
recurse into every sub-jaxpr — pjit/closed_call bodies, scan/while carries,
cond branches, custom_jvp/vjp call jaxprs — so a violation cannot hide one
`lax.cond` deep.  ``core.flatstate.max_concat_elems`` is a thin delegate of
the implementation here (the rule framework generalized it; the old import
path keeps working).
"""
from __future__ import annotations

import re
from typing import List, Optional

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr as _ClosedJaxpr
from jax.extend.core import Jaxpr as _Jaxpr

from .report import Finding, rule

__all__ = [
    "iter_eqns", "count_primitive", "primitive_eqns", "max_concat_elems",
    "no_param_concat", "no_host_callback", "collective_count", "wire_dtype",
    "donation_honored", "aliased_param_bytes", "HOST_CALLBACK_PRIMITIVES",
]


# primitives that round-trip through the host inside a traced computation:
# one of these in a hot step means a device->host->device sync per call
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "outside_call",
    "host_callback_call",
})


# ---------------------------------------------------------------------------
# traversal
# ---------------------------------------------------------------------------

def _as_jaxpr(j) -> _Jaxpr:
    return j.jaxpr if isinstance(j, _ClosedJaxpr) else j


def _subjaxprs(v):
    if isinstance(v, _ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, _Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _subjaxprs(item)


def iter_eqns(jaxpr):
    """Every equation in ``jaxpr`` (Jaxpr or ClosedJaxpr), recursing into
    all nested sub-jaxprs carried in equation params (pjit bodies, scan and
    while carries, cond branch lists, custom_jvp/vjp call jaxprs)."""
    for eqn in _as_jaxpr(jaxpr).eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def primitive_eqns(jaxpr, name: str) -> List:
    """All equations (recursively) whose primitive is ``name``."""
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == name]


def count_primitive(jaxpr, name: str) -> int:
    return len(primitive_eqns(jaxpr, name))


def max_concat_elems(jaxpr) -> int:
    """Largest ``concatenate`` output (in elements) anywhere in the jaxpr.

    The flat engine's contract is that this stays far below the parameter
    count inside a train step: RNG internals emit tiny concats (threefry
    key plumbing), but nothing parameter-sized — the flatten happened once,
    at init.  Returns 0 for a jaxpr with no equations at all (an identity
    program is trivially clean).
    """
    worst = 0
    for eqn in primitive_eqns(jaxpr, "concatenate"):
        for out in eqn.outvars:
            worst = max(worst, int(np.prod(out.aval.shape, dtype=np.int64)))
    return worst


# ---------------------------------------------------------------------------
# rules over a traced jaxpr
# ---------------------------------------------------------------------------

@rule("no-param-concat",
      "no concatenate in the traced step may reach the flat-engine bound "
      "(the per-step re-flatten PR 3 removed must never come back)")
def no_param_concat(jaxpr, *, bound: int, target: str) -> List[Finding]:
    """Flag any concatenate output of ``bound`` elements or more.

    Callers pass ``bound = n_params // 100`` (the tier-1 guard's margin):
    RNG key plumbing concats a handful of words; anything within two orders
    of magnitude of the model is a parameter-sized layout rebuild.
    """
    worst = max_concat_elems(jaxpr)
    if worst >= bound:
        return [Finding(
            "no-param-concat", target,
            f"concatenate of {worst} elems >= bound {bound} — a "
            "parameter-sized flatten is back in the hot step")]
    return []


@rule("no-host-callback",
      "a jitted hot-loop step must not embed host callbacks "
      "(pure/io/debug_callback force a device->host sync per call)")
def no_host_callback(jaxpr, *, target: str) -> List[Finding]:
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in HOST_CALLBACK_PRIMITIVES:
            out.append(Finding(
                "no-host-callback", target,
                f"host callback primitive {eqn.primitive.name!r} traced "
                "into the hot step"))
    return out


@rule("collective-count",
      "collectives per step == live neighbor slots in the compiled "
      "GossipSchedule tables (padding slots must cost nothing)")
def collective_count(jaxpr, *, expected: int, target: str,
                     primitive: str = "ppermute",
                     hlo_text: Optional[str] = None) -> List[Finding]:
    """Count gossip collectives against the schedule's live-slot total.

    With ``hlo_text`` the count is taken from the compiled executable
    (``collective-permute`` ops, async ``-start`` forms included) — what
    actually runs; otherwise from the traced jaxpr's ``primitive`` eqns.
    Both too many (leaf-multiplied or padded-slot traffic) and too few
    (a silently-elided mix) are violations.
    """
    if hlo_text is not None:
        got = len(re.findall(r"collective-permute(?:-start)?\(", hlo_text))
        src = "compiled HLO"
    else:
        got = count_primitive(jaxpr, primitive)
        src = f"jaxpr {primitive!r}"
    if got != expected:
        return [Finding(
            "collective-count", target,
            f"{got} collectives in {src}, schedule tables say {expected} "
            "live neighbor slots")]
    return []


@rule("wire-dtype",
      "gossip collectives ship the params' own wire dtype — a bf16 model "
      "must not move f32 over the links")
def wire_dtype(jaxpr, *, expected, target: str,
               primitive: str = "ppermute") -> List[Finding]:
    expected = np.dtype(expected)
    out = []
    for eqn in primitive_eqns(jaxpr, primitive):
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            if np.dtype(aval.dtype) != expected:
                out.append(Finding(
                    "wire-dtype", target,
                    f"{primitive} ships {np.dtype(aval.dtype).name}, wire "
                    f"dtype is {expected.name} — "
                    f"{np.dtype(aval.dtype).itemsize}x"
                    f"{int(np.prod(aval.shape, dtype=np.int64))} B on the "
                    "links instead of "
                    f"{expected.itemsize}x that"))
    return out


# ---------------------------------------------------------------------------
# donation: only the compiled executable can vouch for this one
# ---------------------------------------------------------------------------

_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
              "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
              "f64": 8, "c64": 8, "c128": 16}


def _split_top_level(s: str) -> List[str]:
    parts, cur, depth = [], [], 0
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    tail = "".join(cur).strip()
    if tail:
        parts.append(tail)
    return parts


def _param_bytes(hlo_text: str) -> List[int]:
    """Per-parameter byte sizes from the compiled module's entry layout."""
    m = re.search(r"entry_computation_layout=\{\((.*?)\)->", hlo_text, re.S)
    if m is None:
        return []
    out = []
    for part in _split_top_level(re.sub(r"/\*.*?\*/", "", m.group(1))):
        t = re.match(r"([a-z]+[0-9]*)\[([0-9,]*)\]", part.strip())
        if t is None:
            out.append(0)
            continue
        dtype, dims = t.group(1), t.group(2)
        elems = 1
        if dims:
            elems = int(np.prod([int(d) for d in dims.split(",")],
                                dtype=np.int64))
        out.append(elems * _HLO_BYTES.get(dtype, 4))
    return out


def aliased_param_bytes(compiled) -> int:
    """Total bytes of input parameters the compiled executable aliases to
    outputs (``input_output_alias`` in the post-compile HLO) — the bytes XLA
    will actually update in place when the caller donates them."""
    txt = compiled.as_text()
    m = re.search(r"input_output_alias=\{(.*?)\}\s*,\s*entry", txt, re.S)
    if m is None:
        return 0
    sizes = _param_bytes(txt)
    total = 0
    for pm in re.finditer(r"\}:\s*\((\d+)", m.group(1)):
        idx = int(pm.group(1))
        total += sizes[idx] if idx < len(sizes) else 0
    return total


@rule("donation-honored",
      "donate_argnums must survive compilation: the compiled executable "
      "aliases at least the model-sized state buffers in place")
def donation_honored(compiled, *, min_bytes: int,
                     target: str) -> List[Finding]:
    """``compiled`` is a ``jax.stages.Compiled`` (``jit(...).lower(
    ...).compile()``).  ``min_bytes`` is the state volume the caller knows
    must be updated in place (e.g. the (n, T, 128) parameter store, or a
    serve engine's K/V page pools); anything less means XLA silently
    double-buffers model-sized state — the regression PR 3 pinned by hand.
    """
    got = aliased_param_bytes(compiled)
    if got < min_bytes:
        return [Finding(
            "donation-honored", target,
            f"compiled executable aliases {got} B of donated inputs, "
            f"expected >= {min_bytes} B — donation dropped, model-sized "
            "state is double-buffered")]
    return []
