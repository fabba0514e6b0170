"""Model FLOP/s utilization of training: the forward and backward
operations the window's tokens require (no recomputation), per second,
over the chips' bf16 peak, in percent."""
from bench.harness.flops import train_flops_per_token


def read(run):
    c = run.counters
    if "tokens" not in c:
        return None
    rate = c["tokens"] * train_flops_per_token(c["shard"], c["seq"])
    return 100.0 * rate / run.window_s / (c["chips"] * run.extra["peak"]["flops"])
