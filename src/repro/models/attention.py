"""Attention: GQA projections + chunked (flash-style) jnp attention + decode.

The training/prefill path is *chunked* with an online softmax — materializing
a 32k x 32k score matrix is a non-starter on 16 GB HBM, so the pure-jnp
reference is already blocked (the Pallas kernel in repro/kernels is the
TPU-tiled version of exactly this loop and is checked against it).

Supports: causal masking, sliding windows, gemma2 attn-logit softcap, GQA
(n_kv_heads <= n_heads), MQA (n_kv_heads == 1), RoPE / M-RoPE via a caller-
supplied rope_fn.  Decode uses a rotating KV buffer of size
min(seq_len, window) so long_500k sliding-window serving is O(window) memory.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .layers import dense_init, softcap

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attn_params(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                     dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d_model, n_heads * head_dim, dtype),
        "wk": dense_init(k2, d_model, n_kv * head_dim, dtype),
        "wv": dense_init(k3, d_model, n_kv * head_dim, dtype),
        "wo": dense_init(k4, n_heads * head_dim, d_model, dtype),
    }


# ---------------------------------------------------------------------------
# chunked attention core (training / prefill)
# ---------------------------------------------------------------------------

def chunked_attention(q, k, v, *, q_positions, k_positions, causal: bool = True,
                      window: int = 0, attn_softcap: float = 0.0,
                      chunk: int = 1024):
    """q, k: (B, Sq|Sk, H|KV, hd); v: (B, Sk, KV, vd); positions: (Sq,),
    (Sk,).  v may be narrower than q and k (latent attention).

    Returns (B, Sq, H, vd).  Blocked over both q and k with an online
    softmax; each q-block body is rematerialized in the backward pass.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    vd = v.shape[-1]
    G = H // KV
    cq = min(chunk, Sq)
    ck = min(chunk, Sk)
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, Sk, chunk)
    nq, nk = Sq // cq, Sk // ck
    scale = hd ** -0.5

    qs = q.reshape(B, nq, cq, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    ks = k.reshape(B, nk, ck, KV, hd).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, ck, KV, vd).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(nq, cq)
    kp = k_positions.reshape(nk, ck)

    @jax.checkpoint
    def one_q_block(qb, qpb):
        # qb: (B, cq, KV, G, hd); qpb: (cq,)
        m0 = jnp.full((B, KV, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, KV, G, vd), jnp.float32)

        def kv_step(carry, inp):
            m, l, acc = carry
            kb, vb, kpb = inp
            s = jnp.einsum("bqkgd,bckd->bkgqc", qb.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            if attn_softcap:
                s = softcap(s, attn_softcap)
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpb[:, None] >= kpb[None, :]
            if window:
                mask &= qpb[:, None] - kpb[None, :] < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckd->bqkgd", p, vb.astype(jnp.float32))
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, kp))
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return out  # (B, cq, KV, G, vd)

    def q_step(_, inp):
        qb, qpb = inp
        return None, one_q_block(qb, qpb)

    _, outs = jax.lax.scan(q_step, None, (qs, qp))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, H, vd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# full attention layer forward (training / prefill)
# ---------------------------------------------------------------------------

def attn_forward(params, x, *, n_heads: int, n_kv: int, head_dim: int,
                 rope_fn: Callable, q_positions, k_positions=None,
                 window: int = 0, attn_softcap: float = 0.0, chunk: int = 1024,
                 kv_input=None, causal: bool = True, use_pallas: bool = False,
                 mask_positions=None):
    """x: (B, S, d).  kv_input: cross-attention memory (B, Sk, d) or None.

    q_positions feed the rope_fn (may be (3, S) for M-RoPE); mask_positions
    (default: q_positions) are the scalar (S,) ids used for causal/window
    masking.
    """
    B, S, _ = x.shape
    kv_src = x if kv_input is None else kv_input
    Sk = kv_src.shape[1]
    if mask_positions is None:
        mask_positions = q_positions
    k_mask_positions = mask_positions if kv_input is None else jnp.arange(Sk)

    q = (x @ params["wq"]).reshape(B, S, n_heads, head_dim)
    k = (kv_src @ params["wk"]).reshape(B, Sk, n_kv, head_dim)
    v = (kv_src @ params["wv"]).reshape(B, Sk, n_kv, head_dim)
    if rope_fn is not None:
        q = rope_fn(q, q_positions)
        k = rope_fn(k, k_positions if k_positions is not None
                    else (q_positions if kv_input is None
                          else k_mask_positions))
    if use_pallas:
        from ..kernels.ops import flash_attention
        out = flash_attention(q, k, v, q_positions=mask_positions,
                              k_positions=k_mask_positions, causal=causal,
                              window=window, attn_softcap=attn_softcap)
    else:
        out = chunked_attention(q, k, v, q_positions=mask_positions,
                                k_positions=k_mask_positions, causal=causal,
                                window=window, attn_softcap=attn_softcap,
                                chunk=chunk)
    return out.reshape(B, S, n_heads * head_dim) @ params["wo"]


# ---------------------------------------------------------------------------
# decode (single new token against a rotating KV cache)
# ---------------------------------------------------------------------------

def init_attn_cache(batch: int, buf_len: int, n_kv: int, head_dim: int, dtype):
    return {
        "k": jnp.zeros((batch, buf_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, buf_len, n_kv, head_dim), dtype),
        "slot_pos": jnp.full((buf_len,), -1, jnp.int32),
    }


def init_paged_attn_cache(n_pages: int, page_size: int, n_kv: int,
                          head_dim: int, dtype):
    """Paged KV pool for one attention layer (ISSUE 7, DESIGN §14).

    Unlike the rotating buffer above there is no per-sequence axis: pages
    are a shared pool, and each serve slot owns an ordered list of page ids
    (the page table, held OUTSIDE the cache by the scheduler).  Page 0 is
    reserved as a scratch page by convention — idle/stalled slots write
    there and length masks keep it from ever being read.
    """
    return {
        "k_pages": jnp.zeros((n_pages, n_kv, page_size, head_dim), dtype),
        "v_pages": jnp.zeros((n_pages, n_kv, page_size, head_dim), dtype),
    }


def attn_decode_paged(params, cache, x, positions, page_table, *,
                      n_heads: int, n_kv: int, head_dim: int,
                      rope_fn: Callable, attn_softcap: float = 0.0,
                      window: int = 0, backend: str = "auto"):
    """Paged-cache decode: one new token per slot at PER-SLOT positions.

    x: (S, 1, d); positions: (S,) int32 — the position each slot's token is
    written at (so slots at different depths decode in one batch, the
    capability the rotating ``attn_decode`` lacks: its scalar ``pos`` is
    shared by the whole batch).  page_table: (S, max_pages) int32 physical
    page ids in logical order; cache: init_paged_attn_cache pools.

    Mirrors ``attn_decode``'s arithmetic exactly (same einsum chain on the
    gathered logical buffer on the jnp oracle path) so the two are bitwise
    equal on CPU when every slot sits at the same position and the logical
    capacities match — the parity pin in tests/test_serve.py.
    """
    from ..kernels.ops import paged_decode_attention

    S = x.shape[0]
    page = cache["k_pages"].shape[2]
    q = (x @ params["wq"]).reshape(S, 1, n_heads, head_dim)
    k = (x @ params["wk"]).reshape(S, 1, n_kv, head_dim)
    v = (x @ params["wv"]).reshape(S, 1, n_kv, head_dim)
    if rope_fn is not None:
        q = rope_fn(q, positions[:, None])
        k = rope_fn(k, positions[:, None])

    # scatter the new token through the page table; idle slots resolve to
    # the scratch page (table entry 0) and are never read back
    ppage = jnp.take_along_axis(page_table, (positions // page)[:, None],
                                axis=1)[:, 0]
    off = positions % page
    kc = cache["k_pages"].at[ppage, :, off].set(
        k[:, 0].astype(cache["k_pages"].dtype))
    vc = cache["v_pages"].at[ppage, :, off].set(
        v[:, 0].astype(cache["v_pages"].dtype))

    o = paged_decode_attention(q.reshape(S, n_heads, head_dim), kc, vc,
                               page_table, positions + 1, window=window,
                               attn_softcap=attn_softcap, backend=backend)
    out = o.reshape(S, 1, n_heads * head_dim).astype(x.dtype) @ params["wo"]
    return out, {"k_pages": kc, "v_pages": vc}


def attn_decode(params, cache, x, pos, *, n_heads: int, n_kv: int,
                head_dim: int, rope_fn: Callable, attn_softcap: float = 0.0):
    """x: (B, 1, d); pos: scalar int32 (same for all sequences).

    Returns (out (B,1,d), new_cache).  Rotating buffer: slot = pos % buf_len.
    """
    B = x.shape[0]
    buf = cache["k"].shape[1]
    q = (x @ params["wq"]).reshape(B, 1, n_heads, head_dim)
    k = (x @ params["wk"]).reshape(B, 1, n_kv, head_dim)
    v = (x @ params["wv"]).reshape(B, 1, n_kv, head_dim)
    posv = jnp.reshape(pos, (1,))
    if rope_fn is not None:
        q = rope_fn(q, posv)
        k = rope_fn(k, posv)

    slot = pos % buf
    kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    sp = jax.lax.dynamic_update_slice(cache["slot_pos"],
                                      jnp.reshape(pos, (1,)).astype(jnp.int32),
                                      (slot,))

    G = n_heads // n_kv
    qg = q.reshape(B, n_kv, G, head_dim)
    s = jnp.einsum("bkgd,bwkd->bkgw", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) * head_dim ** -0.5
    if attn_softcap:
        s = softcap(s, attn_softcap)
    valid = (sp >= 0) & (sp <= pos)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", p, vc.astype(jnp.float32))
    out = o.reshape(B, 1, n_heads * head_dim).astype(x.dtype) @ params["wo"]
    return out, {"k": kc, "v": vc, "slot_pos": sp}
