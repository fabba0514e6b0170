"""Device time of the fused gossip + momentum-SGD kernel per train step,
from the trace."""


def read(run):
    t = run.trace
    if t is None or not t.kernel_calls.get("gossip_mix") or not t.steps:
        return None
    return 1e3 * t.kernel_s["gossip_mix"] / t.steps
