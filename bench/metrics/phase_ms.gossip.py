"""Device time per train step of the ``gossip`` phase (the exchange between
learners: mixing rounds, collective-permutes and the flat engine's fused
gossip + momentum kernel), from the trace: ops mapped to phases by the
op_name scopes the program compiles in."""
from bench.harness.phases import ms_per_step


def read(run):
    return ms_per_step(run, "gossip")
