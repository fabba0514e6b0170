import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import init_moe_params, moe_forward


def test_moe_shapes_and_finite():
    """Every (token, expert) assignment is computed: the held count is
    T * k whatever the routing (no capacity, nothing dropped)."""
    cfgs = [(8, 2), (4, 4), (16, 8)]
    key = jax.random.PRNGKey(0)
    for E, k in cfgs:
        p = init_moe_params(key, 32, 64, E, jnp.float32)
        x = jax.random.normal(key, (2, 16, 32))
        y, held = moe_forward(p, x, n_experts=E, top_k=k, return_stats=True)
        assert y.shape == x.shape
        assert bool(jnp.isfinite(y).all())
        assert int(held) == 2 * 16 * k


def test_moe_no_drops_with_big_capacity():
    """Every token picks the same experts (a zero router ties all scores,
    so each takes experts 0..k-1): a capacity router would drop most of
    them; the dropless layer gives every token its experts' full output."""
    key = jax.random.PRNGKey(1)
    E, k, d = 4, 2, 16
    p = init_moe_params(key, d, 32, E, jnp.float32)
    p["router"] = jnp.zeros_like(p["router"])
    x = jax.random.normal(key, (1, 8, d))
    y, held = moe_forward(p, x, n_experts=E, top_k=k, return_stats=True)
    assert int(held) == 8 * k
    xt = x.reshape(-1, d)
    ref = sum(0.5 * ((jax.nn.silu(xt @ p["w1"][e]) * (xt @ p["w3"][e]))
                     @ p["w2"][e]) for e in range(k))
    np.testing.assert_allclose(np.asarray(y.reshape(-1, d)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_moe_topk_equals_experts_is_dense_mixture():
    """k == E: every expert sees every token; output is the gate-weighted
    sum over ALL experts — check vs direct computation."""
    key = jax.random.PRNGKey(2)
    E, d, f = 4, 8, 16
    p = init_moe_params(key, d, f, E, jnp.float32)
    x = jax.random.normal(key, (1, 4, d))
    y = moe_forward(p, x, n_experts=E, top_k=E)
    xt = x.reshape(-1, d)
    gates = jax.nn.softmax(xt @ p["router"], -1)
    ref = jnp.zeros_like(xt)
    for e in range(E):
        h = jax.nn.silu(xt @ p["w1"][e]) * (xt @ p["w3"][e])
        ref += gates[:, e:e + 1] * (h @ p["w2"][e])
    np.testing.assert_allclose(np.asarray(y.reshape(-1, d)), np.asarray(ref),
                               atol=1e-4)


def test_moe_grads_flow_to_router():
    key = jax.random.PRNGKey(3)
    p = init_moe_params(key, 16, 32, 4, jnp.float32)
    x = jax.random.normal(key, (2, 8, 16))

    def loss(p_):
        return jnp.sum(moe_forward(p_, x, n_experts=4, top_k=2) ** 2)
    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["w1"]).sum()) > 0


def test_grouped_matmul_matches_dense_per_group():
    """Rows of group g times w[g], rows past the groups zero; the
    gradients of x and w equal those of the dense per-row products."""
    from repro.models.moe import grouped_matmul
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(4), 3)
    G, M, d, f = 3, 12, 8, 16
    sizes = jnp.array([4, 0, 5], jnp.int32)              # 9 of 12 rows held
    x = jax.random.normal(kx, (M, d))
    w = jax.random.normal(kw, (G, d, f))
    g = jax.random.normal(kg, (M, f))
    rows = np.repeat(np.arange(G), np.asarray(sizes))
    held = len(rows)

    def dense(x_, w_):
        y = jnp.einsum("md,mdf->mf", x_[:held], w_[rows],
                       precision=jax.lax.Precision.HIGHEST)
        return jnp.concatenate([y, jnp.zeros((M - held, f))])
    y, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, sizes), x, w)
    y_ref, vjp_ref = jax.vjp(dense, x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
    for got, want in zip(vjp(g), vjp_ref(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_route_weights_are_renormalised_and_scaled(scoring):
    """The top-k weights are the chosen experts' scores, summing to
    ``routed_scaling`` when renormalised; the ids are the top-k scores'."""
    from repro.models.moe import route
    key = jax.random.PRNGKey(5)
    p = init_moe_params(key, 16, 32, 8, jnp.float32)
    xt = jax.random.normal(key, (10, 16))
    w, ids = route(p, xt, top_k=3, scoring=scoring, routed_scaling=2.5)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)
    logits = np.asarray(xt @ p["router"])
    top = np.argsort(-logits, axis=-1)[:, :3]
    assert (np.sort(np.asarray(ids), -1) == np.sort(top, -1)).all()
    w_raw, _ = route(p, xt, top_k=3, scoring=scoring, norm_topk=False)
    scores = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1)
                        if scoring == "softmax"
                        else jax.nn.sigmoid(jnp.asarray(logits)))
    np.testing.assert_allclose(
        np.asarray(w_raw), np.take_along_axis(scores, np.asarray(ids), -1),
        rtol=1e-6)
