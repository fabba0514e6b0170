"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gossip_mix_update_ref(w, neighbors, grads, momentum, coefs, *, lr: float,
                          beta: float = 0.9):
    """Same contract as kernels.gossip_mix.gossip_mix_update."""
    mixed = coefs[0] * w
    for k in range(neighbors.shape[0]):
        mixed = mixed + coefs[k + 1] * neighbors[k]
    mu_new = beta * momentum + grads
    return mixed - lr * mu_new, mu_new


def gossip_mix_update_flat_ref(w, remote, grads, momentum, partners, coefs, *,
                               lr: float, beta: float = 0.0,
                               weight_decay: float = 0.0,
                               has_momentum: bool = True, buffer=None):
    """Same contract as kernels.gossip_mix.gossip_mix_update_flat.

    Mirrors the kernel's arithmetic order (self term first, neighbors in
    schedule order, fused lr scale, where-based active select, publish-mode
    neighbor/buffer selects) so the two stay bitwise-close in interpret
    mode.  K is arbitrary: the loop consumes one compiled GossipSchedule
    round of any static neighbor count (padded self-loop slots contribute
    coefficient-0 terms, exactly like the kernel); with ``lr=0.0`` this is
    the mixing-only round ops.flat_gossip_mix dispatches."""
    K = partners.shape[0]
    publish = buffer is not None
    mixed = coefs[:, 0][:, None, None] * w
    for k in range(K):
        nbr = remote[partners[k]]
        if publish:
            nbr = jnp.where((coefs[:, 3 + K] > 0.5)[:, None, None], nbr,
                            buffer[partners[k]])
        mixed = mixed + coefs[:, 1 + k][:, None, None] * nbr
    g = grads
    if weight_decay:
        g = g + weight_decay * w
    lr_eff = (lr * coefs[:, 1 + K])[:, None, None]
    active = (coefs[:, 2 + K] > 0.5)[:, None, None]
    if has_momentum:
        mu_new = beta * momentum + g
        new_w = jnp.where(active, mixed - lr_eff * mu_new, w)
        mu_out = jnp.where(active, mu_new, momentum)
    else:
        new_w = jnp.where(active, mixed - lr_eff * g, w)
        mu_out = momentum
    if publish:
        buf_new = jnp.where((coefs[:, 4 + K] > 0.5)[:, None, None], new_w,
                            buffer)
        return new_w, mu_out, buf_new
    return new_w, mu_out


def reorth_ref(basis, w, mask):
    """Same contract as kernels.reorth.reorth_pass (one CGS sweep).

    basis: (M, T, 128); w: (T, 128); mask: (M,) 0/1.  Returns (w_new, dots).
    Loops vector-by-vector exactly like the kernel so the two stay
    bitwise-close in interpret mode.
    """
    wf = w.astype(jnp.float32)
    dots = jnp.stack([jnp.sum(basis[k].astype(jnp.float32) * wf)
                      for k in range(basis.shape[0])]) * mask
    acc = wf
    for k in range(basis.shape[0]):
        acc = acc - dots[k] * basis[k].astype(jnp.float32)
    return acc.astype(w.dtype), dots


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, lengths, *,
                               window: int = 0, attn_softcap: float = 0.0):
    """Same contract as kernels.ops.paged_decode_attention.

    q: (S, H, hd) one query token per slot; k_pages, v_pages:
    (P, KV, page, hd) shared head-major page pools; page_table:
    (S, max_pages) int32 physical page ids in logical order; lengths: (S,)
    int32 valid tokens per slot (including the current one).  Gathers each slot's logical
    (W = max_pages * page) KV buffer through its table row, then runs the
    exact einsum/softmax chain of models.attention.attn_decode so the paged
    and rotating decode paths stay bitwise equal on CPU (the test pin).
    ``window`` keeps only the trailing ``window`` tokens (sliding-window
    layers); 0 disables it.  A slot with length 0 degenerates to a uniform
    softmax over the masked row — finite garbage the scheduler ignores.
    """
    S, H, hd = q.shape
    P, KV, page, _ = k_pages.shape
    G = H // KV
    W = page_table.shape[1] * page

    def logical(pages):     # (S, max_pages, KV, page, hd) -> (S, W, KV, hd)
        return pages[page_table].transpose(0, 1, 3, 2, 4).reshape(
            S, W, KV, hd)

    kc = logical(k_pages)
    vc = logical(v_pages)
    qg = q.reshape(S, KV, G, hd)
    s = jnp.einsum("bkgd,bwkd->bkgw", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) * hd ** -0.5
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)
    kpos = jnp.arange(W)[None, :]
    valid = kpos < lengths[:, None]
    if window:
        valid &= kpos >= lengths[:, None] - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkd->bkgd", p, vc.astype(jnp.float32))
    return o.reshape(S, H, hd).astype(q.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        attn_softcap: float = 0.0):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd).
    Dense (unblocked) softmax attention with identical masking semantics."""
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, Sq, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qf, kf) * hd ** -0.5
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, vf)
    return o.reshape(B, H, Sq, hd).astype(q.dtype)


def causal_flash_ref(q, k, v):
    """Same contract as kernels.causal_flash (forward): q, k: (B, H, S, dk);
    v: (B, H, S, dv) -> (B, H, S, dv).  One causal softmax over positions
    0..S-1, f32 at the highest precision."""
    S = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")
