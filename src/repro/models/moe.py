"""Mixture-of-Experts FFN: routing that drops no token, grouped matmuls over
the experts this model holds, and optional shared experts.

Routing runs over all ``n_experts`` routed experts, whether or not their
weights are here: scores are the softmax or the sigmoid of the router's
logits; the top-k are chosen on the scores plus a per-expert selection bias
where the parameters carry one (``select_bias``, DeepSeek-V3's ``noaux_tc``:
a buffer, never trained -- its gradient is stopped); the weights are the
unbiased scores of the chosen experts, renormalised to sum 1 when
``norm_topk`` and scaled by ``routed_scaling``.

The model holds the weights of routed experts ``held = (first, count)``
(all by default): one chip's share of an expert-parallel deployment.  Every
(token, expert) assignment to a held expert is computed, whatever the
routing does -- there is no capacity and no token is dropped.  Assignments
to held experts are sorted by expert into a buffer of ``T * top_k`` rows
(the most a routing can send here) and run as three grouped matmuls
(``grouped_matmul``, XLA's ragged dot; rows past the held groups are set
to zero, forward and backward); the results are scattered back onto their
tokens, scaled by their weights.  Assignments to experts held elsewhere add nothing here.
The shared experts are one dense SwiGLU that every token passes through.

Named scopes (``repro.obs.SPANS``): ``moe_route`` holds the router, the
selection, the sort and gather into expert order and the combine;
``moe_experts`` the grouped matmuls and their activation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs import span
from .layers import dense_init
from .shard_hints import per_learner

__all__ = ["grouped_matmul", "init_moe_params", "moe_forward", "route"]


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int, dtype, *,
                    held=None, shared_d_ff: int = 0,
                    select_bias: bool = False):
    """Router over all ``n_experts``; expert weights for ``held = (first,
    count)`` only (all by default); a shared SwiGLU of width
    ``shared_d_ff`` (none where 0); a zero selection bias where asked."""
    first, count = held or (0, n_experts)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    keys = lambda k: jax.random.split(k, n_experts)[first:first + count]
    p = {
        "router": dense_init(k1, d_model, n_experts, jnp.float32),
        "w1": jax.vmap(lambda k: dense_init(k, d_model, d_ff, dtype))(
            keys(k2)),
        "w3": jax.vmap(lambda k: dense_init(k, d_model, d_ff, dtype))(
            keys(k3)),
        "w2": jax.vmap(lambda k: dense_init(k, d_ff, d_model, dtype))(
            keys(k4)),
    }
    if select_bias:
        p["select_bias"] = jnp.zeros((n_experts,), jnp.float32)
    if shared_d_ff:
        ks = jax.random.split(k5, 3)
        p["shared"] = {"w1": dense_init(ks[0], d_model, shared_d_ff, dtype),
                       "w3": dense_init(ks[1], d_model, shared_d_ff, dtype),
                       "w2": dense_init(ks[2], shared_d_ff, d_model, dtype)}
    return p


def route(params, xt, *, top_k: int, scoring: str = "softmax",
          routed_scaling: float = 1.0, norm_topk: bool = True):
    """xt: (T, d) -> (weights (T, k) float32, expert ids (T, k) int32)."""
    logits = xt.astype(jnp.float32) @ params["router"]          # (T, E)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    choose = scores
    if "select_bias" in params:
        choose = scores + jax.lax.stop_gradient(params["select_bias"])
    _, ids = jax.lax.top_k(choose, top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk:
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-20)
    return w * routed_scaling, ids


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


# ---------------------------------------------------------------------------
# grouped matmul, one learner at a time
#
# The TPU's grouped matmul (XLA's ragged dot) takes no batch dimension, so a
# vmap over learners must not batch it: its forward and its backward each
# run as one unbatched call per learner (``shard_hints.per_learner``).
# ---------------------------------------------------------------------------

def _ragged(x, w, sizes):
    return jax.lax.ragged_dot(x, w, sizes)


_gmm_fwd = per_learner(_ragged)
_gmm_bwd = per_learner(
    lambda x, w, sizes, g: jax.vjp(lambda a, b: _ragged(a, b, sizes),
                                   x, w)[1](g))


def _past_groups_zero(y, sizes):
    """Rows of ``y`` past the groups set to 0: the TPU's ragged dot leaves
    them unwritten (whatever the buffer held, NaN included), and a gradient
    read from them would reach every token through the gather."""
    rows = jnp.arange(y.shape[0]) < jnp.sum(sizes)
    return jnp.where(rows[:, None], y, 0)


@jax.custom_vjp
def grouped_matmul(x, w, sizes):
    """x: (M, d) rows sorted by group; w: (G, d, f); sizes: (G,) int32 ->
    (M, f): row block g times w[g]; rows past the groups come back 0."""
    return _past_groups_zero(_gmm_fwd(x, w, sizes), sizes)


def _grouped_matmul_fwd(x, w, sizes):
    return _past_groups_zero(_gmm_fwd(x, w, sizes), sizes), (x, w, sizes)


def _grouped_matmul_bwd(res, g):
    x, w, sizes = res
    dx, dw = _gmm_bwd(x, w, sizes, g)
    return _past_groups_zero(dx, sizes), dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def moe_forward(params, x, *, n_experts: int, top_k: int,
                scoring: str = "softmax", routed_scaling: float = 1.0,
                norm_topk: bool = True, held=None,
                return_stats: bool = False):
    """x: (B, S, d) -> (B, S, d) [, held assignments computed (int32)]."""
    B, S, d = x.shape
    T = B * S
    first, count = held or (0, n_experts)
    xt = x.reshape(T, d)
    with span("moe_route"):
        w, ids = route(params, xt, top_k=top_k, scoring=scoring,
                       routed_scaling=routed_scaling, norm_topk=norm_topk)
        local = ids.reshape(-1) - first                         # (T*k,)
        group = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(group, stable=True)     # held, by expert, first
        token = order // top_k
        sizes = jnp.bincount(group, length=count + 1)[:count].astype(
            jnp.int32)
        xs = xt[token]                                          # (T*k, d)
    with span("moe_experts"):
        h = jax.nn.silu(grouped_matmul(xs, params["w1"], sizes)) \
            * grouped_matmul(xs, params["w3"], sizes)
        ys = grouped_matmul(h, params["w2"], sizes)            # (T*k, d)
    with span("moe_route"):
        gate = jnp.where(group[order] < count, w.reshape(-1)[order], 0.0)
        yt = jnp.zeros((T, d), ys.dtype).at[token].add(
            ys * gate[:, None].astype(ys.dtype))
    if "shared" in params:
        yt = yt + _swiglu(xt, params["shared"])
    y = yt.reshape(B, S, d).astype(x.dtype)
    if return_stats:
        return y, jnp.sum(sizes)
    return y
