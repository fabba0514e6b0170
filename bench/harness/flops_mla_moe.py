"""Operations a DeepSeek-V3-class share's training step requires, from
shapes and the MoE counter: no recomputation, and only the (token, expert)
assignments the held experts computed (``moe_held_assignments``).  Forward
and backward count 3x the forward."""
from __future__ import annotations


def expert_train_flops(s, assignments: float) -> float:
    """The held experts' grouped matmuls, forward and backward, for
    ``assignments`` (token, expert) pairs: three d x f products each."""
    return 3.0 * assignments * 3 * 2.0 * s.d_model * s.expert_ff


def forward_flops_per_token(s, seq: int, held_per_token: float) -> float:
    """One token's forward, in a causal sequence of ``seq`` tokens (it
    attends to (seq + 1) / 2 keys on average), with ``held_per_token`` held
    experts' assignments per token, summed over the MoE layers."""
    d, H = s.d_model, s.n_heads
    qk = s.nope + s.rope
    mla = (d * H * qk + d * (s.kv_rank + s.rope)
           + s.kv_rank * H * (s.nope + s.v_dim) + H * s.v_dim * d)
    core = H * (qk + s.v_dim) * (seq + 1) / 2.0
    per_layer = 2.0 * mla + 2.0 * core
    dense = 2.0 * 3 * d * s.dense_ff
    moe = 2.0 * d * s.router_experts + 2.0 * 3 * d * s.shared_ff
    return (s.n_layers * per_layer + s.dense_layers * dense
            + s.moe_layers * moe
            + held_per_token * 2.0 * 3 * d * s.expert_ff
            + 2.0 * d * s.vocab)


def train_flops_per_token(s, seq: int, held_per_token: float) -> float:
    return 3.0 * forward_flops_per_token(s, seq, held_per_token)
