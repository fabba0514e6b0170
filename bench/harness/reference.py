"""The plain reference: the dense decoder and DPSGD in float32 jax.numpy.

It imports nothing of the program.  It reads the benchmark's own weights
(``model.make_params``) by their names in the program's layout, and computes
the function the program's dense family is documented to compute:

    x = embed[tokens] * sqrt(d_model)
    per layer:  h = rmsnorm(x) * (1 + norm1);  x += attention(h)
                h = rmsnorm(x) * (1 + norm2);  x += (silu(h w1) * (h w3)) w2
    logits = (rmsnorm(x) * (1 + final_norm)) lm_head

with causal grouped-query attention and rotate-half RoPE.  Two departures
from the published Llama block are the program's, and the reference
follows them: embeddings are scaled by sqrt(d_model), and the RMSNorm
weight is stored as ``1 + scale``.  Every matrix product runs at
``highest`` precision.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    """x: (B, S, H, hd); rotate-half RoPE at integer positions ``pos``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv           # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def f32(a):
    return a.astype(jnp.float32)


def _layer(x, lp, shard, pos):
    B, S, _ = x.shape
    H, KV, hd = shard.n_heads, shard.n_kv_heads, shard.head_dim
    at = lp["mixer"]
    h = _rms(x, lp["norm1"], shard.norm_eps)
    q = _rope(_mm(h, f32(at["wq"])).reshape(B, S, H, hd), pos,
              shard.rope_theta)
    k = _rope(_mm(h, f32(at["wk"])).reshape(B, S, KV, hd), pos,
              shard.rope_theta)
    v = _mm(h, f32(at["wv"])).reshape(B, S, KV, hd)
    head_kv = jnp.arange(H) // (H // KV)
    k, v = k[:, :, head_kv], v[:, :, head_kv]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    x = x + _mm(o.reshape(B, S, H * hd), f32(at["wo"]))
    mlp = lp["mlp"]
    h = _rms(x, lp["norm2"], shard.norm_eps)
    u = jax.nn.silu(_mm(h, f32(mlp["w1"]))) * _mm(h, f32(mlp["w3"]))
    return x + _mm(u, f32(mlp["w2"]))


def logits(params, tokens, shard):
    """tokens: (B, S) int32 -> (B, S, vocab) float32 logits."""
    S = tokens.shape[1]
    pos = jnp.arange(S)
    x = f32(params["embed"][tokens]) * math.sqrt(shard.d_model)

    def body(x, lp):
        return _layer(x, lp, shard, pos), None

    x, _ = jax.lax.scan(body, x, params["periods"]["l0"])
    h = _rms(x, params["final_norm"], shard.norm_eps)
    return _mm(h, f32(params["lm_head"][:, :shard.vocab]))


def loss(params, batch, shard):
    """Masked mean token cross-entropy over the logical vocabulary."""
    lg = logits(params, batch["tokens"], shard)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    m = batch["mask"].astype(jnp.float32)
    return jnp.sum((logz - gold) * m) / jnp.maximum(jnp.sum(m), 1.0)


# ---------------------------------------------------------------------------
# DPSGD: n learners from one start, the recipe's mixing and momentum SGD
# ---------------------------------------------------------------------------

def pair_mixing(key, n):
    """The random-pair matching of one step as an (n, n) matrix: a random
    permutation of the learners, consecutive entries paired, weight 1/2."""
    perm = jax.random.permutation(key, n)
    partner = jnp.arange(n)
    k = (n // 2) * 2
    partner = partner.at[perm[:k:2]].set(perm[1:k:2])
    partner = partner.at[perm[1:k:2]].set(perm[:k:2])
    eye = jnp.eye(n, dtype=jnp.float32)
    return jnp.where((partner == jnp.arange(n))[:, None], eye,
                     0.5 * (eye + eye[partner]))


def ring_mixing(n):
    """Each learner averages itself and its two ring neighbours, 1/3 each."""
    eye = jnp.eye(n, dtype=jnp.float32)
    return (eye + jnp.roll(eye, 1, axis=1) + jnp.roll(eye, -1, axis=1)) / 3.0


def mixing(traffic, rng, step, n):
    """The step's mixing matrix, keyed as the recipe states: the step key
    is ``fold_in(rng, step)``, and its first split draws the matching."""
    topo = traffic["topology"]
    if topo == "ring":
        return ring_mixing(n)
    if topo == "random_pair":
        k_mix, _ = jax.random.split(jax.random.fold_in(rng, step))
        return pair_mixing(k_mix, n)
    raise ValueError(f"no reference mixing for topology {topo!r}")


def dpsgd_step(w, mu, batch, mix, lr, beta, shard, fault=None, chips=1):
    """One DPSGD step on stacked (n, ...) weights and momentum: each
    learner's gradient at its own weights (``chips`` learners at a time,
    to fit: one on each chip where the learners are spread over chips),
    momentum, the mixing, the step.  Returns (w, mu, mean loss, per-leaf
    gradient norms).

    ``fault`` plants a fault in the reference, where it stands in for the
    program: 'half_batch' takes the loss over the first half of each
    learner's rows only; 'no_exchange' leaves the mixing out."""
    if fault == "half_batch":
        half = batch["tokens"].shape[1] // 2
        batch = jax.tree_util.tree_map(lambda a: a[:, :half], batch)
    if fault == "no_exchange":
        mix = jnp.eye(mix.shape[0], dtype=jnp.float32)

    def one(args):
        p, b = args
        return jax.value_and_grad(lambda q: loss(q, b, shard))(p)
    losses, g = jax.lax.map(one, (w, batch), batch_size=chips)
    mu = jax.tree_util.tree_map(lambda m, gg: beta * m + gg, mu, g)
    mixed = jax.tree_util.tree_map(
        lambda a: jnp.einsum("ij,j...->i...", mix, a, precision=HIGHEST), w)
    w = jax.tree_util.tree_map(lambda a, u: a - lr * u, mixed, mu)
    return w, mu, jnp.mean(losses), leaf_norms(g)


def leaf_norms(tree):
    """Per learner, per leaf: the 2-norm over every axis but the first."""
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                   axis=tuple(range(1, a.ndim)))), tree)


def change_norms(w, w0):
    """Per learner, per leaf: ||w_i - w0|| for stacked ``w``."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)[None]),
            axis=tuple(range(1, a.ndim)))), w, w0)
