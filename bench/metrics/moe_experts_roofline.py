"""The held experts' grouped matmuls against the chip's bf16 peak: the
FLOPs their (token, expert) assignments require forward and backward, from
the program's ``moe_held_assignments`` counter, over the device time of
the ``moe_experts`` span, per chip and step, in percent."""
from bench.harness.flops_mla_moe import expert_train_flops
from bench.harness.spans import ms_per_step


def read(run):
    c = run.counters
    held = c.get("held_assignments")
    ms = ms_per_step(run, "moe_experts")
    if not held or not ms:
        return None
    flops = expert_train_flops(c["mla_moe"], held)
    return 100.0 * flops / (ms * 1e-3) / run.extra["peak"]["flops"]
