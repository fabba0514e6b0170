"""jit'd public wrappers around the Pallas kernels, in the models' layouts.

Each kernel compiles to Mosaic on the TPU.  On the CPU (``on_cpu``) it runs
in interpret mode, which exists to verify it: a dispatcher with
``backend='auto'`` picks its jnp oracle there instead.

* ``flash_attention``: (B, S, H, hd) attention for the opt-in
  ``use_pallas`` path; the forward is the kernel, the backward recomputes
  through the jnp reference.
* ``causal_flash_attention``: latent attention's core, causal, with q/k
  and v of different widths; forward and backward are kernels
  (``causal_flash.py``), one call per learner under a vmap over learners.
  ``models/mla.py`` runs it on the TPU and ``chunked_attention`` on the
  CPU.
* ``paged_decode_attention``, ``reorthogonalize``, ``flat_gossip_update``,
  ``flat_gossip_mix`` and ``dpsgd_fused_update``: serving decode, the
  landscape probe and the gossip updates.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..models.shard_hints import per_learner
from . import ref
from .causal_flash import causal_flash_bwd, causal_flash_fwd
from .decode_attention import paged_decode_attention_fwd
from .flash_attention import flash_attention_fwd
from .gossip_mix import (flatten_for_kernel, gossip_mix_update,
                         gossip_mix_update_flat)
from .reorth import reorth_pass


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, attn_softcap, q_positions, k_positions):
    # layout: (B, S, H, hd) -> kernel layout (B, H, S, hd)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                            attn_softcap=attn_softcap, interpret=on_cpu())
    return o.transpose(0, 2, 1, 3)


def _ref_bsh(q, k, v, causal, window, attn_softcap):
    o = ref.flash_attention_ref(q.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3),
                                causal=causal, window=window,
                                attn_softcap=attn_softcap)
    return o.transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal, window, attn_softcap, qp, kp):
    return _flash(q, k, v, causal, window, attn_softcap, qp, kp), (q, k, v)


def _flash_bwd(causal, window, attn_softcap, qp, kp, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_bsh(q_, k_, v_, causal, window,
                                                 attn_softcap), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, q_positions=None, k_positions=None,
                    causal: bool = True, window: int = 0,
                    attn_softcap: float = 0.0):
    """Model-layout flash attention.  q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd).

    Assumes contiguous positions from 0 (training/prefill); the explicit
    position arrays are accepted for API parity with chunked_attention and
    validated when concrete.
    """
    return _flash(q, k, v, causal, window, attn_softcap, q_positions,
                  k_positions)


def _bhsd(x):
    return x.transpose(0, 2, 1, 3)


_causal_fwd = per_learner(
    lambda q, k, v: causal_flash_fwd(q, k, v, interpret=on_cpu()))
_causal_bwd = per_learner(
    lambda q, k, v, o, lse, do: causal_flash_bwd(q, k, v, o, lse, do,
                                                 interpret=on_cpu()))


@jax.custom_vjp
def causal_flash_attention(q, k, v):
    """Causal attention, q, k: (B, S, H, dk); v: (B, S, H, dv) ->
    (B, S, H, dv), positions 0..S-1, scale dk ** -0.5.  Forward and
    backward run the fused kernels, once per learner under a vmap over
    learners (the compiler cannot partition a Mosaic call)."""
    return _causal_flash_fwd(q, k, v)[0]


def _causal_flash_fwd(q, k, v):
    q, k, v = _bhsd(q), _bhsd(k), _bhsd(v)
    o, lse = _causal_fwd(q, k, v)
    return _bhsd(o), (q, k, v, o, lse)


def _causal_flash_bwd(res, g):
    return tuple(_bhsd(d) for d in _causal_bwd(*res, _bhsd(g)))


causal_flash_attention.defvjp(_causal_flash_fwd, _causal_flash_bwd)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           window: int = 0, attn_softcap: float = 0.0,
                           backend: str = "auto"):
    """Paged serving decode attention (ISSUE 7, DESIGN §14).

    q: (S, H, hd) — one query token per serve slot; k_pages, v_pages:
    (P, KV, page, hd) shared head-major pools; page_table: (S, max_pages) int32
    physical page ids in logical order; lengths: (S,) int32 valid tokens
    per slot (current token included).  ``backend='auto'`` follows the
    repo's kernel/oracle/dispatch rule: the Mosaic kernel on accelerators,
    the jnp oracle on CPU (interpret mode exists to *verify* the kernel —
    tests force ``backend='pallas'`` for that).  Inference-only: no VJP.
    """
    if backend == "auto":
        backend = "ref" if on_cpu() else "pallas"
    if backend == "ref":
        return ref.paged_decode_attention_ref(
            q, k_pages, v_pages, page_table, lengths, window=window,
            attn_softcap=attn_softcap)
    return paged_decode_attention_fwd(
        q, k_pages, v_pages, page_table, lengths, window=window,
        attn_softcap=attn_softcap, interpret=on_cpu())


def reorthogonalize(basis, w, mask, *, backend: str = "pallas"):
    """Fully reorthogonalize w against the masked basis prefix (DESIGN §10).

    basis: (M, T, 128) stacked flat Lanczos vectors; w: (T, 128) candidate;
    mask: (M,) 0/1 f32 marking the live prefix.  Two classical-Gram-Schmidt
    sweeps (CGS2 — the "twice is enough" rule) through the fused Pallas
    dot/axpy kernels, or through the jnp oracle with ``backend='ref'``
    (used under multi-device meshes where the flat view would break the
    parameter sharding; see launch/train.py).
    """
    if backend == "ref":
        w, _ = ref.reorth_ref(basis, w, mask)
        w, _ = ref.reorth_ref(basis, w, mask)
        return w
    interpret = on_cpu()
    w, _ = reorth_pass(basis, w, mask, interpret=interpret)
    w, _ = reorth_pass(basis, w, mask, interpret=interpret)
    return w


def flat_gossip_update(w, remote, grads, momentum, partners, coefs, *,
                       lr: float, beta: float = 0.0, weight_decay: float = 0.0,
                       buffer=None, backend: str = "auto"):
    """Batched fused gossip+SGD update on the persistent (n, T, 128) store.

    The flat engine's hot-path dispatch (DESIGN §11): ``backend='pallas'``
    runs the learner-major Pallas kernel (Mosaic on TPU, interpret mode on
    CPU); ``backend='ref'`` the jnp oracle — same contract, the ground
    truth in tests.  ``'auto'`` (the default) picks the kernel on
    accelerators and the oracle on CPU: interpret mode exists to *verify*
    the kernel, not to win benchmarks, and the oracle is the faster correct
    implementation where there is no Mosaic compiler.

    momentum=None selects the momentum-free fused update (no (n, T, 128)
    momentum buffer is read or written).  ``buffer`` (AD-PSGD) switches on
    publish mode — see gossip_mix_update_flat; returns (w_new, mu_new,
    buffer_new) there, (w_new, mu_new) otherwise.
    """
    has_momentum = momentum is not None
    mu = momentum if has_momentum else w      # ignored when has_momentum=False
    if backend == "auto":
        backend = "ref" if on_cpu() else "pallas"
    if backend == "ref":
        out = ref.gossip_mix_update_flat_ref(
            w, remote, grads, mu, partners, coefs, lr=lr, beta=beta,
            weight_decay=weight_decay, has_momentum=has_momentum,
            buffer=buffer)
    else:
        out = gossip_mix_update_flat(
            w, remote, grads, mu, partners, coefs, lr=lr, beta=beta,
            weight_decay=weight_decay, has_momentum=has_momentum,
            buffer=buffer, interpret=on_cpu())
    w_new, mu_new = out[0], (out[1] if has_momentum else None)
    if buffer is not None:
        return w_new, mu_new, out[2]
    return w_new, mu_new


def flat_gossip_mix(w, partners, coefs, *, active=None,
                    backend: str = "auto"):
    """One mixing-only gossip round on the flat (n, T, 128) store.

    ``partners``: (K, n) int32; ``coefs``: (n, K + 1) f32 ``[self,
    neighbors...]`` — exactly one row of a compiled GossipSchedule
    (core/schedule.py).  Multi-round schedules (full-as-rounds,
    hierarchical, random matching with ``gossip_rounds > 1``) run their
    leading rounds through this and fuse the optimizer update into the
    LAST round only.  Reuses the batched kernel with a zero learning rate
    and ``w`` aliased as the (ignored) gradient operand, so arbitrary
    static K rides the same scalar-prefetch hot path with no second kernel
    to maintain.

    ``active`` ((n,) bool, elastic membership): inactive rows are left
    bitwise untouched by the kernel's in-pass select — a quarantined row
    holding arbitrary (even non-finite) values neither moves nor, given
    only-active partner tables, bleeds into live rows.
    """
    n = w.shape[0]
    act = (jnp.ones((n, 1), jnp.float32) if active is None
           else active.astype(jnp.float32)[:, None])
    pad = jnp.ones((n, 1), jnp.float32)          # lr scale (ignored: lr=0)
    full = jnp.concatenate([coefs.astype(jnp.float32), pad, act], axis=1)
    out = flat_gossip_update(w, w, w, None, partners, full, lr=0.0,
                             backend=backend)
    return out[0]


def dpsgd_fused_update(params_tree, neighbor_trees, grads_tree, momentum_tree,
                       coefs, *, lr: float, beta: float = 0.9):
    """Pytree-level fused gossip+momentum update (see kernels.gossip_mix).

    neighbor_trees: list of pytrees (the ppermute-received weight replicas).
    Returns (new_params_tree, new_momentum_tree).
    """
    w, unflatten_w = flatten_for_kernel(params_tree)
    mu, unflatten_mu = flatten_for_kernel(momentum_tree)
    g, _ = flatten_for_kernel(grads_tree)
    nbrs = jnp.stack([flatten_for_kernel(t)[0] for t in neighbor_trees])
    w_new, mu_new = gossip_mix_update(w, nbrs, g, mu,
                                      jnp.asarray(coefs, jnp.float32),
                                      lr=lr, beta=beta, interpret=on_cpu())
    return unflatten_w(w_new), unflatten_mu(mu_new)
