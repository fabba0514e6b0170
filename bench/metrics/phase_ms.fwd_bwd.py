"""Device time per train step of the ``fwd_bwd`` phase (the per-learner
forward and backward passes), from the trace: ops mapped to phases by
the op_name scopes the program compiles in."""
from bench.harness.phases import ms_per_step


def read(run):
    return ms_per_step(run, "fwd_bwd")
