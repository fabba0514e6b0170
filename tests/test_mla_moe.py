"""Latent attention, dropless routing over held experts and the leading
dense layer (the DeepSeek-V3 block of ``moonlight-16b-a3b``), at a size the
CPU holds: against direct computations of the published equations, and the
expert-parallel share against the whole layer."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.mla import mla_forward
from repro.models.moe import init_moe_params, moe_forward, route

KEY = jax.random.PRNGKey(0)


def small(**kw):
    """The registry entry at test widths: d 64, 4 heads, kv rank 32, rope/
    nope/v 16, 16 routed experts (8 held) top-4, one shared expert, vocab
    512, the dense layer and 2 MoE layers."""
    base = dict(name="moonlight-small", n_layers=3, d_model=64, n_heads=4,
                n_kv_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=16, v_head_dim=16, n_experts=16,
                experts_held=(0, 8), experts_per_tok=4, d_ff=32,
                shared_d_ff=32, dense_d_ff=96, vocab=512, attn_chunk=8,
                param_dtype="float32", compute_dtype="float32")
    base.update(kw)
    return dataclasses.replace(get_config("moonlight-16b-a3b"), **base)


def test_registry_entry_is_at_published_widths():
    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab) == (
        27, 2048, 16, 163840)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.q_lora_rank) == (512, 128, 64, 128, 0)
    assert (cfg.n_experts, cfg.experts_per_tok, cfg.d_ff, cfg.shared_d_ff,
            cfg.dense_d_ff, cfg.first_dense_layers) == (
        64, 6, 1408, 2816, 11264, 1)
    assert cfg.router_scoring == "sigmoid" and cfg.routed_scaling == 2.446
    shapes = jax.eval_shape(build_model(cfg).init, KEY)
    n = sum(s.size for s in jax.tree_util.tree_leaves(shapes))
    # 27 MLA blocks, 1 dense and 26 MoE MLPs (64 routed + 2 shared experts,
    # router and selection bias), embedding and head
    d = 2048
    mla = (d * 16 * 192 + d * 576 + 512 + 512 * 16 * 256 + 16 * 128 * d)
    moe = 64 * 3 * d * 1408 + 3 * d * 2816 + d * 64 + 64
    want = (27 * (mla + 2 * d) + 3 * d * 11264 + 26 * moe
            + 2 * d * 163840 + d)
    assert n == want
    assert 15.9e9 < n < 16.1e9


def _direct_mla(p, x, cfg):
    """The published equations with one full softmax (no blocks)."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
    kva = x @ p["wkv_a"]
    c = kva[..., :r]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.norm_eps)
    kv = (c @ p["wkv_b"]).reshape(B, S, H, nope + vd)

    def rot(t):                           # rotate-half RoPE, (B, S, h, rope)
        inv = cfg.rope_theta ** (-jnp.arange(0, rope, 2) / rope)
        a = jnp.arange(S)[:, None] * inv
        cos, sin = jnp.cos(a)[:, None], jnp.sin(a)[:, None]
        t1, t2 = t[..., :rope // 2], t[..., rope // 2:]
        return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)
    q_pe, k_pe = rot(q[..., nope:]), rot(kva[:, :, None, r:])
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
         + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0]))
    s = s / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., nope:])
    return o.reshape(B, S, H * vd) @ p["wo"]


def test_mla_matches_the_published_equations():
    """Blocked over queries and keys with an online softmax (chunk 8 of 32
    positions) against one full softmax; float32 on the CPU, so the gap is
    the summation order's: 1e-5."""
    cfg = small()
    p = build_model(cfg).init(KEY)["periods"]["l0"]["mixer"]
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    got = mla_forward(p, x, cfg, jnp.arange(32))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_direct_mla(p, x, cfg)),
                               rtol=1e-5, atol=1e-5)


def _layer(held, bias, key=KEY):
    p = init_moe_params(key, 64, 32, 16, jnp.float32, held=held,
                        shared_d_ff=32, select_bias=True)
    p["select_bias"] = bias
    return p


def test_expert_shares_add_up_to_the_whole_layer():
    """Two chips of a 2-way expert-parallel layer: the outputs
    of experts 0-7 and 8-15, with the shared expert counted once, add up to
    the layer that holds all 16 (same router, same seeded weights)."""
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64))
    kw = dict(n_experts=16, top_k=4, scoring="sigmoid", routed_scaling=2.5)
    whole = moe_forward(_layer((0, 16), bias), x, **kw)
    a, na = moe_forward(_layer((0, 8), bias), x, held=(0, 8),
                        return_stats=True, **kw)
    b, nb = moe_forward(_layer((8, 8), bias), x, held=(8, 8),
                        return_stats=True, **kw)
    p = _layer((0, 16), bias)
    shared = ((jax.nn.silu(x @ p["shared"]["w1"]) * (x @ p["shared"]["w3"]))
              @ p["shared"]["w2"])
    np.testing.assert_allclose(np.asarray(a + b - shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert int(na) + int(nb) == 2 * 16 * 4


def test_rows_past_the_held_groups_reach_nothing(monkeypatch):
    """The TPU's ragged dot leaves the rows past the held groups unwritten:
    with NaN there, forward and backward, the layer's output and every
    gradient are what the CPU's zero-filling kernel gives."""
    from repro.models import moe
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    p = _layer((0, 8), bias)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64))
    kw = dict(n_experts=16, top_k=4, scoring="sigmoid", held=(0, 8))

    def loss(q, xx):
        return jnp.sum(moe_forward(q, xx, **kw) ** 2)
    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)

    def past_nan(y, sizes):
        rows = jnp.arange(y.shape[0]) < jnp.sum(sizes)
        return jnp.where(rows[:, None], y, jnp.nan)
    fwd, bwd = moe._gmm_fwd, moe._gmm_bwd
    monkeypatch.setattr(moe, "_gmm_fwd",
                        lambda x, w, s: past_nan(fwd(x, w, s), s))
    monkeypatch.setattr(moe, "_gmm_bwd", lambda x, w, s, g: (
        past_nan(bwd(x, w, s, g)[0], s), bwd(x, w, s, g)[1]))
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_selection_bias_chooses_but_does_not_weigh():
    """noaux_tc: experts are chosen on score + bias, weighted by the
    unbiased scores renormalised and scaled; the bias gets no gradient."""
    p = init_moe_params(KEY, 8, 16, 6, jnp.float32, select_bias=True)
    p["select_bias"] = jnp.array([0.0, 0.0, 0.0, 0.0, 10.0, 10.0])
    xt = jax.random.normal(jax.random.PRNGKey(3), (5, 8))
    w, ids = route(p, xt, top_k=2, scoring="sigmoid", routed_scaling=3.0)
    assert sorted(np.asarray(ids[0]).tolist()) == [4, 5]
    s = jax.nn.sigmoid(xt @ p["router"])[:, 4:6]
    np.testing.assert_allclose(np.sort(np.asarray(w), -1),
                               np.sort(np.asarray(3.0 * s / s.sum(-1,
                                                              keepdims=True)),
                                       -1), rtol=1e-6)
    x = xt.reshape(1, 5, 8)
    g = jax.grad(lambda q: jnp.sum(moe_forward(
        q, x, n_experts=6, top_k=2, scoring="sigmoid") ** 2))(p)
    assert float(jnp.abs(g["select_bias"]).max()) == 0.0
    assert float(jnp.abs(g["router"]).max()) > 0.0


def test_loss_and_stats_counts_held_assignments():
    cfg = small()
    api = build_model(cfg)
    params = api.init(KEY)
    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tok, "labels": tok, "mask": jnp.ones((2, 16))}
    loss, stats = jax.jit(api.loss_and_stats)(params, batch)
    assert float(loss) == pytest.approx(float(api.loss_fn(params, batch)))
    held = stats["moe_held_assignments"]
    # every MoE layer routes 32 tokens x 4 experts over 16; 8 are held
    assert 0 < int(held) < 2 * 32 * 4
    whole = build_model(small(experts_held=()))
    _, st = whole.loss_and_stats(whole.init(KEY), batch)
    assert int(st["moe_held_assignments"]) == 2 * 32 * 4
    dense = build_model(get_config("transformer-100m").smoke_config())
    assert dense.loss_and_stats(dense.init(KEY), batch)[1] == {}


def test_training_only_models_refuse_decode():
    api = build_model(small())
    assert not api.has_decode and not api.has_paged
    with pytest.raises(ValueError, match="training only"):
        api.init_cache(None, 2, 32)
    from repro.serve.engine import ServeEngine
    with pytest.raises(AssertionError, match="no paged decode"):
        ServeEngine(api, api.init(KEY), n_slots=2, n_pages=4, page_size=8)
