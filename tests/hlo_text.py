"""Reading a compiled module's HLO text (``compiled.as_text()``) in tests:
its instructions and the train-step phase each one's op_name names."""
import re

from repro.obs import phase_of

INST = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = \S+ (\S+?)\(")
OP_NAME = re.compile(r'^\s*(?:ROOT )?%?([^\s=]+) = .*'
                     r'metadata=\{[^}]*op_name="([^"]*)"')


def instructions(hlo_text):
    """[(instruction name, opcode)] over every computation."""
    return [m.groups() for m in map(INST.match, hlo_text.splitlines()) if m]


def phases(hlo_text):
    """{instruction name: phase} over every computation; instructions with
    no phase in their op_name are left out.  A fusion carries its root's
    metadata, so it takes its root's phase."""
    out = {}
    for m in map(OP_NAME.match, hlo_text.splitlines()):
        if m and phase_of(m.group(2)) is not None:
            out[m.group(1)] = phase_of(m.group(2))
    return out
