"""Pallas TPU kernel: blocked flash attention (causal / sliding-window /
logit-softcap, GQA-aware).

Grid: (B, KV, G, nq, nk) — the innermost nk axis is sequential ("arbitrary"
semantics) and accumulates the online softmax in VMEM scratch, writing the
output tile on the last nk step.  BlockSpecs tile q/k/v into
(block_q, head_dim) / (block_k, head_dim) VMEM tiles; head_dim is MXU-lane
aligned (128 for every assigned config; 64 for the small ones — still a
multiple of the 8x128 f32 tile after padding by Mosaic).

Positions are implicit: q row = iq*bq + lane, k row = ik*bk + lane (training/
prefill layouts are contiguous from 0).  The causal/window masking is
computed in-kernel from the grid indices, so fully-masked (iq, ik) tiles
cost one predicated vector op, not a matmul (the jnp reference cannot skip
them — that is the kernel's win besides fusion).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 block_q: int, block_k: int, n_k_blocks: int, scale: float,
                 causal: bool, window: int, attn_softcap: float):
    iq = pl.program_id(3)
    ik = pl.program_id(4)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :, :].astype(jnp.float32)       # (bq, hd)
    k = k_ref[0, 0, :, :].astype(jnp.float32)       # (bk, hd)
    v = v_ref[0, 0, :, :].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == n_k_blocks - 1)
    def _finalize():
        o_ref[0, 0, :, :] = (acc_scr[...]
                             / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "attn_softcap", "block_q",
                              "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        attn_softcap: float = 0.0, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    G = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    grid = (B, KV, G, nq, nk)
    kern = functools.partial(
        _attn_kernel, block_q=bq, block_k=bk, n_k_blocks=nk,
        scale=hd ** -0.5, causal=causal, window=window,
        attn_softcap=attn_softcap)

    return pl.pallas_call(
        kern,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, kv, g, iq, ik: (b, kv * G + g, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, kv, g, iq, ik: (b, kv, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, kv, g, iq, ik: (b, kv, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, kv, g, iq, ik: (b, kv * G + g, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
