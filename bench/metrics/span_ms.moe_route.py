"""Device time per train step of the model's ``moe_route`` span (forward,
recomputed forward and backward), from the trace: ops mapped to spans by
the op_name scopes the program compiles in."""
from bench.harness.spans import ms_per_step


def read(run):
    return ms_per_step(run, "moe_route")
