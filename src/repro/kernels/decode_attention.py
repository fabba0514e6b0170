"""Pallas TPU kernel: paged decode attention (one query token per slot).

Grown from flash_attention.py for the serving decode grid (ISSUE 7): every
serve slot contributes exactly ONE query token, and its K/V history lives in
fixed-size pages scattered through a shared pool.  The page table rides in
as a scalar-prefetch operand and the K/V BlockSpec index_maps gather each
logical page straight out of the pool (``table[s, j]``) — the same
SMEM-partner idiom gossip_mix.py uses for neighbor rows, so the gather costs
zero extra HBM passes: the kernel streams exactly the pages the slot owns.

Grid: (S, KV, n_pages) with the page axis sequential ("arbitrary"), online
softmax in VMEM scratch exactly like the prefill kernel.  Masking is
computed in-kernel from the page index and the per-slot length (second
scalar-prefetch operand): entry t of logical page j is valid iff
j*page + t < length[s] (and, for sliding-window layers, >= length - window).
Pages wholly past the slot's length still run one predicated vector op, and
the flash rescale trick keeps fully-masked pages from polluting the
accumulator (their contribution is wiped by ``corr`` once a live page is
seen; a length-0 slot degenerates to the same uniform average the oracle
produces — finite garbage the scheduler ignores).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, page: int, n_pages: int,
                   scale: float, window: int, attn_softcap: float):
    s_idx = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)             # (page, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)

    length = len_ref[s_idx]
    kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < length
    if window:
        mask &= kpos >= length - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "attn_softcap", "interpret"))
def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, lengths, *,
                               window: int = 0, attn_softcap: float = 0.0,
                               interpret: bool = False):
    """q: (S, H, hd); k_pages, v_pages: (P, KV, page, hd);
    page_table: (S, max_pages) int32; lengths: (S,) int32 -> (S, H, hd).

    Head-major pages make one (page, hd) K/V tile the block's last two
    dims, equal to the pool's own — legal under the TPU block rule for any
    page size (a (page, 1, hd) slice of a (page, KV, hd) page is not)."""
    S, H, hd = q.shape
    P, KV, page, _ = k_pages.shape
    G = H // KV
    max_pages = page_table.shape[1]
    grid = (S, KV, max_pages)

    kern = functools.partial(_decode_kernel, page=page, n_pages=max_pages,
                             scale=hd ** -0.5, window=window,
                             attn_softcap=attn_softcap)
    qg = q.reshape(S, KV, G, hd)

    out = pl.pallas_call(
        kern,
        name="paged_decode_attention_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda s, kv, j, tbl, ln: (s, kv, 0, 0)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda s, kv, j, tbl, ln: (tbl[s, j], kv, 0, 0)),
                pl.BlockSpec((1, 1, page, hd),
                             lambda s, kv, j, tbl, ln: (tbl[s, j], kv, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, hd),
                                   lambda s, kv, j, tbl, ln: (s, kv, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), qg,
      k_pages, v_pages)
    return out.reshape(S, H, hd)
