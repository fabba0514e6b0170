"""The fused gossip kernel's share of its roofline: the least time its
calls could take at the chip's peaks (bytes from the call's shapes), over
its summed trace time, in percent."""
from bench.harness.flops import gossip_mix_cost


def read(run):
    t, c = run.trace, run.counters
    calls = t.kernel_calls.get("gossip_mix") if t is not None else 0
    if not calls or "gossip_rows" not in c:
        return None
    pk = run.extra["peak"]
    flops, bytes_ = gossip_mix_cost(c["learners"], c["gossip_rows"],
                                    c["neighbours"])
    least = max(flops / pk["flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * least * calls / t.kernel_s["gossip_mix"]
