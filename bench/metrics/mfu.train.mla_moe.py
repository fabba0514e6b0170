"""Model FLOP/s utilization of MLA/MoE training: the forward and backward
operations the window's tokens require (no recomputation; the routed
experts' part from the program's ``moe_held_assignments`` counter), per
second of the window, over the chips' bf16 peak, in percent."""
from bench.harness.flops_mla_moe import train_flops_per_token


def read(run):
    c = run.counters
    held = c.get("held_assignments")
    if "tokens" not in c or not held:
        return None
    per_learner_tokens = c["tokens_per_step"] / c["learners"]
    flops = train_flops_per_token(c["mla_moe"], c["seq"],
                                  held / per_learner_tokens)
    rate = c["tokens"] * flops / run.window_s
    return 100.0 * rate / (c["chips"] * run.extra["peak"]["flops"])
