"""The forward/backward's own share of the chip's bf16 peak: the FLOPs its
tokens require (no recomputation) over the device time of the ``fwd_bwd``
phase, per chip, in percent.  ``mfu.train`` divides the same FLOPs by the
whole step's host time, gossip, statistics and copies included."""
from bench.harness.flops import train_flops_per_token
from bench.harness.phases import ms_per_step


def read(run):
    ms = ms_per_step(run, "fwd_bwd")
    c = run.counters
    if not ms or "tokens_per_step" not in c:
        return None
    flops = c["tokens_per_step"] / c["chips"] * train_flops_per_token(
        c["shard"], c["seq"])
    return 100.0 * flops / (ms * 1e-3) / run.extra["peak"]["flops"]
