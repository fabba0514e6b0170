"""The plain reference for a DeepSeek-V3-class share: MLA, the sigmoid
router and its held experts, in float32 jax.numpy.

It imports nothing of the program.  It reads the benchmark's weights by
their names in the program's layout (``params["lead"]``: the dense layers,
``params["periods"]``: the MoE layers, each stacked on a leading axis) and
computes the published equations:

    x = embed[tokens] * sqrt(d_model)
    per layer:  h = rmsnorm(x) * (1 + norm1);  x += mla(h)
                h = rmsnorm(x) * (1 + norm2);  x += mlp(h)
    logits = (rmsnorm(x) * (1 + final_norm)) lm_head

    mla(h):  c, k_pe = split(h W_kva);  c = rmsnorm(c) * (1 + kv_norm)
             [k_nope | v] = c W_kvb;  [q_nope | q_pe] = h W_q    (per head)
             scores = (q_nope.k_nope + rope(q_pe).rope(k_pe)) / sqrt(nope + rope)
             out = (causal softmax(scores) v) W_o
    moe(h):  s = sigmoid(h W_router)                             (all experts)
             top-k of s + select_bias;  g = s of those / their sum * scaling
             out = sum over held experts e of g_e * swiglu_e(h)  + swiglu_shared(h)

Each held expert runs densely on every token and is weighted by its gate
(zero where the token did not choose it): no dispatch, no sort.  What the
experts held elsewhere would add is left out, as in the program.  The
departures are the program's own and are followed here: embeddings scaled
by sqrt(d_model), RMSNorm weights stored as ``1 + scale``, rotate-half
RoPE, no aux-loss bias update and no sequence aux loss.  Attention runs in
blocks of queries, each recomputed in the backward pass, and every layer
is checkpointed, so the reference fits beside the program's state.  Every
matrix product runs at ``highest`` precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference import HIGHEST, _mm, _rms, _rope, f32

Q_BLOCK = 1024


def _swiglu(h, p):
    u = jax.nn.silu(_mm(h, f32(p["w1"]))) * _mm(h, f32(p["w3"]))
    return _mm(u, f32(p["w2"]))


def mla(h, at, s, pos):
    """h: (B, S, d) normed input -> (B, S, d)."""
    B, S, _ = h.shape
    H, r = s.n_heads, s.kv_rank
    q = _mm(h, f32(at["wq"])).reshape(B, S, H, s.nope + s.rope)
    kva = _mm(h, f32(at["wkv_a"]))
    c = _rms(kva[..., :r], at["kv_norm"], s.norm_eps)
    kv = _mm(c, f32(at["wkv_b"])).reshape(B, S, H, s.nope + s.v_dim)
    k_nope, v = kv[..., :s.nope], kv[..., s.nope:]
    q_nope = q[..., :s.nope]
    q_pe = _rope(q[..., s.nope:], pos, s.rope_theta)
    k_pe = _rope(kva[:, :, None, r:], pos, s.rope_theta)[:, :, 0]
    scale = 1.0 / math.sqrt(s.nope + s.rope)
    nb = max(S // Q_BLOCK, 1)

    @jax.checkpoint
    def block(args):
        qn, qp, qpos = args                       # (B, Qb, H, .), (Qb,)
        sc = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope, precision=HIGHEST)
              + jnp.einsum("bqhd,bkd->bhqk", qp, k_pe, precision=HIGHEST))
        sc = jnp.where((qpos[:, None] >= pos[None, :])[None, None],
                       sc * scale, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST)

    split = lambda a: jnp.moveaxis(a.reshape(B, nb, S // nb, *a.shape[2:]),
                                   1, 0)
    o = jax.lax.map(block, (split(q_nope), split(q_pe),
                            pos.reshape(nb, S // nb)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, H * s.v_dim)
    return _mm(o, f32(at["wo"]))


def gates(xt, mp, s):
    """xt: (T, d) -> (T, router_experts) routed weights, zero off the top-k."""
    scores = jax.nn.sigmoid(_mm(xt, f32(mp["router"])))
    _, ids = jax.lax.top_k(scores + f32(mp["select_bias"]), s.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if s.norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * s.routed_scaling
    chosen = jax.nn.one_hot(ids, s.router_experts, dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", w, chosen, precision=HIGHEST)


def moe(h, mp, s):
    """h: (B, S, d) normed input -> (B, S, d): the held experts' part and
    the shared experts."""
    B, S, d = h.shape
    xt = h.reshape(B * S, d)
    g = gates(xt, mp, s)[:, s.held_first:s.held_first + s.held]

    def expert(out, ex):
        w, gate = ex
        return out + gate[:, None] * _swiglu(xt, w), None
    held = {k: mp[k] for k in ("w1", "w3", "w2")}
    out, _ = jax.lax.scan(expert, _swiglu(xt, mp["shared"]), (held, g.T))
    return out.reshape(B, S, d)


def _layer(x, lp, s, pos, mlp):
    h = _rms(x, lp["norm1"], s.norm_eps)
    x = x + mla(h, lp["mixer"], s, pos)
    h = _rms(x, lp["norm2"], s.norm_eps)
    return x + mlp(h, lp["mlp"], s)


def _stack(x, stacked, s, pos, mlp):
    @jax.checkpoint
    def body(x, lp):
        return _layer(x, lp["l0"], s, pos, mlp), None
    return jax.lax.scan(body, x, stacked)[0]


def logits(params, tokens, s):
    """tokens: (B, S) int32 -> (B, S, vocab) float32 logits."""
    pos = jnp.arange(tokens.shape[1])
    x = f32(params["embed"][tokens]) * math.sqrt(s.d_model)
    x = _stack(x, params["lead"], s, pos, _swiglu_mlp)
    x = _stack(x, params["periods"], s, pos, moe)
    h = _rms(x, params["final_norm"], s.norm_eps)
    return _mm(h, f32(params["lm_head"][:, :s.vocab]))


def _swiglu_mlp(h, p, s):
    return _swiglu(h, p)


def loss(params, batch, s):
    """Masked mean token cross-entropy over the vocabulary slice."""
    lg = logits(params, batch["tokens"], s)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    m = batch["mask"].astype(jnp.float32)
    return jnp.sum((logz - gold) * m) / jnp.maximum(jnp.sum(m), 1.0)
