"""Device time per train step of the operations that carry no phase: those
outside the program's phase scopes, and those XLA adds with no source of
their own, such as copies between layouts (trace)."""
from bench.harness.phases import ms_per_step


def read(run):
    return ms_per_step(run, "other")
