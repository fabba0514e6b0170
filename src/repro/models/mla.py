"""Multi-head latent attention (DeepSeek-V2/V3), the training forward.

With ``q_lora_rank`` 0 (q is one plain projection), for x: (B, S, d):

    c, k_pe        = split(x W_kva, [kv_lora_rank, qk_rope_head_dim])
    c              = rmsnorm(c) * (1 + kv_norm)
    [k_nope | v]   = c W_kvb                        per head
    [q_nope | q_pe] = x W_q                         per head
    q_pe, k_pe     = rope(q_pe), rope(k_pe)         k_pe one key for all heads
    o              = softmax_causal((q_nope.k_nope + q_pe.k_pe)
                                    / sqrt(qk_nope + qk_rope)) v
    out            = [o_1 | ... | o_H] W_o

The core is causal attention with q/k ``qk_nope + qk_rope`` wide and v
``v_head_dim`` wide.  On the TPU it is the fused flash kernel, forward and
backward (``kernels.ops.causal_flash_attention``: score tiles stay in
VMEM, tiles above the diagonal are skipped).  On the CPU it is its oracle,
``attention.chunked_attention`` (blocked over queries and keys with an
online softmax, each query block recomputed in the backward pass).

One departure from the published checkpoint's layout: RoPE rotates the two
halves of the rope dims (rotate-half) where the checkpoint rotates
interleaved pairs.  The two differ by a fixed permutation of W_q's and
W_kva's rope columns, which weights drawn from a seed cannot tell apart.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs import span
from .attention import chunked_attention
from .layers import apply_rope, dense_init, rms_norm

__all__ = ["init_mla_params", "mla_forward"]


def init_mla_params(key, cfg, dtype):
    if cfg.q_lora_rank:
        raise NotImplementedError("MLA with a low-rank q projection "
                                  "(q_lora_rank > 0) is not built")
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d, H * qk, dtype),
        "wkv_a": dense_init(k2, d, r + cfg.qk_rope_head_dim, dtype),
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": dense_init(k3, r, H * (cfg.qk_nope_head_dim
                                        + cfg.v_head_dim), dtype),
        "wo": dense_init(k4, H * cfg.v_head_dim, d, dtype),
    }


def mla_forward(params, x, cfg, positions):
    """x: (B, S, d); positions: (S,), 0..S-1 as training gives them (the
    TPU kernel masks by index) -> (B, S, d), causal."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    from ..kernels import ops
    with span("mla"):
        q = (x @ params["wq"]).reshape(B, S, H, nope + rope)
        kva = x @ params["wkv_a"]
        c = rms_norm(kva[..., :r], params["kv_norm"], cfg.norm_eps)
        kv = (c @ params["wkv_b"]).reshape(B, S, H, nope + vd)
        q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta)
        k_pe = apply_rope(kva[..., None, r:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))],
            axis=-1)
        v = kv[..., nope:]
        if ops.on_cpu():
            o = chunked_attention(q, k, v, q_positions=positions,
                                  k_positions=positions,
                                  chunk=min(cfg.attn_chunk, S))
        else:
            o = ops.causal_flash_attention(q, k, v)
        return o.reshape(B, S, H * vd) @ params["wo"]
