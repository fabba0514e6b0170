"""Device time per train step of the ``stats`` phase (the step's metrics:
loss, gradient norms, weight spread), from the trace: ops mapped to
phases by the op_name scopes the program compiles in."""
from bench.harness.phases import ms_per_step


def read(run):
    return ms_per_step(run, "stats")
