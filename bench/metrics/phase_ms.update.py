"""Device time per train step of the ``update`` phase (the optimizer and
the weight update where they run outside the fused kernel), from the
trace: ops mapped to phases by the op_name scopes the program compiles
in."""
from bench.harness.phases import ms_per_step


def read(run):
    return ms_per_step(run, "update")
