"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.causal_flash import causal_flash_bwd, causal_flash_fwd
from repro.kernels.decode_attention import paged_decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.gossip_mix import (flatten_for_kernel, gossip_mix_update,
                                     gossip_mix_update_flat)
from repro.kernels.ops import (causal_flash_attention, dpsgd_fused_update,
                               flash_attention, flat_gossip_update,
                               paged_decode_attention)
from repro.models.attention import chunked_attention


@pytest.mark.parametrize("T,K", [(256, 1), (512, 2), (1024, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_gossip_kernel_sweep(T, K, dtype):
    key = jax.random.PRNGKey(T + K)
    ks = jax.random.split(key, 4)
    w = jax.random.normal(ks[0], (T, 128), dtype)
    nb = jax.random.normal(ks[1], (K, T, 128), dtype)
    g = jax.random.normal(ks[2], (T, 128), dtype)
    mu = jax.random.normal(ks[3], (T, 128), dtype)
    coefs = jnp.concatenate([jnp.array([0.5]),
                             jnp.full((K,), 0.5 / K)]).astype(jnp.float32)
    w1, m1 = gossip_mix_update(w, nb, g, mu, coefs, lr=0.1, beta=0.9,
                               interpret=True)
    w2, m2 = ref.gossip_mix_update_ref(w, nb, g, mu, coefs, lr=0.1, beta=0.9)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-5)


@pytest.mark.parametrize("S,hd,H,KV", [(128, 64, 4, 4), (256, 64, 4, 2),
                                       (256, 128, 2, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kw", [dict(causal=True),
                                dict(causal=True, window=64),
                                dict(causal=False),
                                dict(causal=True, attn_softcap=50.0)])
def test_flash_attention_sweep(S, hd, H, KV, dtype, kw):
    key = jax.random.PRNGKey(S + hd)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, H, S, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (1, KV, S, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (1, KV, S, hd)).astype(dtype)
    o1 = flash_attention_fwd(q, k, v, block_q=64, block_k=64, interpret=True,
                             **kw)
    o2 = ref.flash_attention_ref(q, k, v, **kw)
    atol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=atol)


def _chunked_bhsd(q, k, v):
    t = lambda x: x.transpose(0, 2, 1, 3)
    pos = jnp.arange(q.shape[2])
    return t(chunked_attention(t(q), t(k), t(v), q_positions=pos,
                               k_positions=pos, chunk=64))


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# The kernels round each dot's operands to bfloat16 once (XLA's DEFAULT
# precision for an f32 matmul on the TPU): 2^-8 relative per operand.
CAUSAL_FLASH_REL = 1e-2


@pytest.mark.parametrize("B,H,S,dk,dv,bq,bk", [
    (1, 1, 256, 48, 32, 64, 64),
    (1, 2, 256, 48, 32, 128, 64),
    (1, 2, 256, 64, 32, 64, 128),
    (1, 1, 64, 48, 32, 512, 512),
    (2, 3, 128, 48, 48, 64, 64),
], ids=["qk_ne_v", "bq_gt_bk", "bq_lt_bk", "single_block", "batch_heads"])
def test_causal_flash_matches_oracles(B, H, S, dk, dv, bq, bk):
    """Forward output and the gradients of q, k and v of the causal flash
    kernels (interpret mode) against ``chunked_attention`` and a plain
    softmax, at a tolerance set by the bf16 rounding; and nothing after a
    query reaches it."""
    ks = jax.random.split(jax.random.PRNGKey(S + dk + bq), 5)
    q = jax.random.normal(ks[0], (B, H, S, dk))
    k = jax.random.normal(ks[1], (B, H, S, dk))
    v = jax.random.normal(ks[2], (B, H, S, dv))
    w = jax.random.normal(ks[3], (B, H, S, dv))
    kw = dict(block_q=bq, block_k=bk, interpret=True)
    o, lse = causal_flash_fwd(q, k, v, **kw)
    got = (o,) + causal_flash_bwd(q, k, v, o, lse, w, **kw)
    for oracle in (ref.causal_flash_ref, _chunked_bhsd):
        want = (oracle(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(oracle(*a) * w), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert _rel(a, b) < CAUSAL_FLASH_REL, (oracle.__name__, name,
                                                   _rel(a, b))
    t = S // 2 + 3
    later = jnp.arange(S)[None, None, :, None] >= t
    o2, lse2 = causal_flash_fwd(q, jnp.where(later, 7.0, k),
                                jnp.where(later, -7.0, v), **kw)
    np.testing.assert_array_equal(np.asarray(o2[:, :, :t]),
                                  np.asarray(o[:, :, :t]))
    np.testing.assert_array_equal(np.asarray(lse2[..., :t]),
                                  np.asarray(lse[..., :t]))


def test_causal_flash_attention_model_layout_grad():
    """``ops.causal_flash_attention`` in the model layout (B, S, H, d):
    ``jax.grad`` through its custom VJP matches ``chunked_attention``."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (2, 128, 2, 48))
    k = jax.random.normal(ks[1], (2, 128, 2, 48))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    w = jax.random.normal(ks[3], (2, 128, 2, 32))
    pos = jnp.arange(128)

    def loss(f):
        return lambda *a: jnp.sum(f(*a) * w)
    chunked = lambda *a: chunked_attention(*a, q_positions=pos,
                                           k_positions=pos, chunk=64)
    got = jax.value_and_grad(loss(causal_flash_attention),
                             argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(chunked), argnums=(0, 1, 2))(q, k, v)
    assert abs(float(got[0] - want[0])) < CAUSAL_FLASH_REL * abs(
        float(want[0])) + 1e-3
    for a, b in zip(got[1], want[1]):
        assert _rel(a, b) < CAUSAL_FLASH_REL


PER_LEARNER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels.ops import causal_flash_attention
from repro.launch.mesh import make_mesh
from repro.models.shard_hints import vmapped_learner_axes

mesh = make_mesh((2, 1), ("data", "model"), devices=jax.devices()[:2])
ks = jax.random.split(jax.random.PRNGKey(0), 4)
q = jax.random.normal(ks[0], (2, 1, 128, 2, 48))
k = jax.random.normal(ks[1], (2, 1, 128, 2, 48))
v = jax.random.normal(ks[2], (2, 1, 128, 2, 32))
w = jax.random.normal(ks[3], (2, 1, 128, 2, 32))

def one(q, k, v, w):
    return jax.value_and_grad(
        lambda *a: jnp.sum(causal_flash_attention(*a) * w),
        argnums=(0, 1, 2))(q, k, v)

def learners(q, k, v, w):
    with vmapped_learner_axes(("data",)):
        return jax.vmap(one, spmd_axis_name=("data",))(q, k, v, w)

shd = NamedSharding(mesh, P("data"))
with jax.set_mesh(mesh):
    fn = jax.jit(learners, in_shardings=(shd,) * 4)
    got = fn(q, k, v, w)
    text = fn.lower(q, k, v, w).as_text()
want = [one(q[i], k[i], v[i], w[i]) for i in range(2)]
gap = max(float(jnp.max(jnp.abs(a[i] - b)))
          for i in range(2)
          for a, b in zip(jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want[i])))
print(json.dumps({"gap": gap, "shard_map": "manual_computation" in text}))
"""


def test_causal_flash_per_learner_vmap_on_a_mesh():
    """Under a 2-learner vmap sharded over a 2-device CPU mesh (the DPSGD
    steps' ``spmd_axis_name``), the kernels run once per learner in
    ``shard_map`` and match the unbatched call, value and gradients."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [p for p in ("src", os.environ.get("PYTHONPATH")) if p]))
    out = subprocess.run([sys.executable, "-c", PER_LEARNER_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["shard_map"]
    assert res["gap"] < 1e-5, res


def _paged_operands(S, hd, H, KV, page, max_pages, lengths, seed=0):
    """Random paged K/V pool + a shuffled (non-identity) page table."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    n_pages = 1 + S * max_pages            # page 0 = scratch, never mapped
    q = jax.random.normal(ks[0], (S, H, hd))
    kp = jax.random.normal(ks[1], (n_pages, KV, page, hd))
    vp = jax.random.normal(ks[2], (n_pages, KV, page, hd))
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                        .reshape(S, max_pages), jnp.int32)
    return q, kp, vp, table, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (4, 1)])
@pytest.mark.parametrize("kw", [dict(), dict(attn_softcap=30.0),
                                dict(window=6)])
def test_paged_decode_attention_kernel_vs_oracle(H, KV, kw):
    """Serving decode grid: ragged per-slot lengths (including an empty
    slot), page-table indirection, GQA/MQA grouping, softcap and sliding
    window — Pallas (interpret) against the jnp oracle."""
    page, max_pages, hd = 4, 4, 16
    lengths = [1, 5, 12, 0]                 # ragged; slot 3 is length-0
    q, kp, vp, table, ln = _paged_operands(len(lengths), hd, H, KV,
                                           page, max_pages, lengths)
    o1 = paged_decode_attention_fwd(q, kp, vp, table, ln, interpret=True,
                                    **kw)
    o2 = ref.paged_decode_attention_ref(q, kp, vp, table, ln, **kw)
    live = np.array(lengths) > 0
    np.testing.assert_allclose(np.asarray(o1)[live], np.asarray(o2)[live],
                               atol=1e-5)
    # length-0 slots: both sides produce the same finite filler (a uniform
    # average of the scratch page) that the scheduler never reads
    assert np.isfinite(np.asarray(o1)).all()


def test_paged_decode_attention_dispatcher_backends():
    """ops.paged_decode_attention: auto resolves to the oracle on CPU and
    the forced-pallas path (interpret) agrees with it."""
    page, max_pages, hd, H, KV = 4, 2, 8, 4, 2
    q, kp, vp, table, ln = _paged_operands(2, hd, H, KV, page, max_pages,
                                           [3, 7], seed=4)
    auto = paged_decode_attention(q, kp, vp, table, ln, backend="auto")
    oracle = paged_decode_attention(q, kp, vp, table, ln, backend="ref")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(oracle))
    kernel = paged_decode_attention(q, kp, vp, table, ln, backend="pallas")
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(oracle),
                               atol=1e-5)


def test_flash_attention_model_layout_and_grad():
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32))
    k = jax.random.normal(ks[1], (2, 64, 2, 32))
    v = jax.random.normal(ks[2], (2, 64, 2, 32))

    def f(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=True) ** 2)
    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for gi in g:
        assert bool(jnp.isfinite(gi).all())


@pytest.mark.parametrize("n,T,K", [(4, 256, 1), (5, 336, 1), (8, 512, 2),
                                   (6, 336, 4), (8, 512, 4)])
@pytest.mark.parametrize("has_mu,wd", [(True, 0.0), (False, 0.0),
                                       (True, 0.01)])
def test_batched_gossip_kernel_sweep(n, T, K, has_mu, wd):
    """Learner-major batched kernel (scalar-prefetch neighbor gather) vs the
    jnp oracle at arbitrary static K (pairwise, ring, torus-like K=4):
    momentum on/off, weight decay, per-learner lr scale, a solo learner and
    an inactive (straggler) learner, non-multiple-of-block T."""
    key = jax.random.PRNGKey(n * T + K)
    ks = jax.random.split(key, 5)
    w = jax.random.normal(ks[0], (n, T, 128))
    remote = jax.random.normal(ks[1], (n, T, 128))
    g = jax.random.normal(ks[2], (n, T, 128))
    mu = jax.random.normal(ks[3], (n, T, 128)) if has_mu else None
    if K == 1:
        partner = jnp.roll(jnp.arange(n), 1).at[0].set(0)   # learner 0 solo
        partners = partner[None].astype(jnp.int32)
        self_c = jnp.where(partner == jnp.arange(n), 1.0, 0.5)
        mix = jnp.stack([self_c, 1.0 - self_c], axis=1)
    else:
        idx = jnp.arange(n)
        partners = jnp.stack([(idx + s) % n
                              for s in range(1, K + 1)]).astype(jnp.int32)
        mix = jnp.full((n, K + 1), 1.0 / (K + 1))
    scale = jnp.linspace(0.5, 1.5, n)[:, None]              # per-learner lr
    active = jnp.ones((n,)).at[n - 1].set(0.0)[:, None]     # straggler
    coefs = jnp.concatenate([mix, scale, active], axis=1).astype(jnp.float32)

    w1, m1 = flat_gossip_update(w, remote, g, mu, partners, coefs,
                                lr=0.1, beta=0.9, weight_decay=wd,
                                backend="pallas")
    w2, m2 = flat_gossip_update(w, remote, g, mu, partners, coefs,
                                lr=0.1, beta=0.9, weight_decay=wd,
                                backend="ref")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), atol=1e-5)
    if has_mu:
        np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-5)
        # the inactive learner's momentum streams through untouched
        np.testing.assert_array_equal(np.asarray(m1[n - 1]),
                                      np.asarray(mu[n - 1]))
    # inactive learner's weights unchanged; solo learner mixes with itself
    np.testing.assert_array_equal(np.asarray(w1[n - 1]), np.asarray(w[n - 1]))


@pytest.mark.parametrize("has_mu", [True, False])
def test_batched_kernel_publish_mode(has_mu):
    """AD-PSGD publish mode: stale-remote select + published-buffer rewrite
    in the same pass, kernel vs oracle, and against the unfused reference
    composition (where -> plain kernel -> where)."""
    n, T = 6, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    w = jax.random.normal(ks[0], (n, T, 128))
    buf = jax.random.normal(ks[1], (n, T, 128))
    g = jax.random.normal(ks[2], (n, T, 128))
    mu = jax.random.normal(ks[3], (n, T, 128)) if has_mu else None
    partner = jnp.array([1, 0, 3, 2, 5, 4])
    partners = partner[None].astype(jnp.int32)
    mix = jnp.tile(jnp.array([0.5, 0.5]), (n, 1))
    scale = jnp.ones((n, 1))
    active = jnp.ones((n,)).at[0].set(0.0)
    fresh = jnp.zeros((n,)).at[2].set(1.0).at[3].set(1.0)
    coefs = jnp.concatenate(
        [mix, scale, active[:, None], fresh[partner][:, None],
         jnp.maximum(active, fresh)[:, None]], axis=1).astype(jnp.float32)

    outs = {}
    for backend in ("pallas", "ref"):
        outs[backend] = flat_gossip_update(
            w, w, g, mu, partners, coefs, lr=0.1, beta=0.9, buffer=buf,
            backend=backend)
    for a, b in zip(outs["pallas"], outs["ref"]):
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
    # unfused reference composition
    remote = jnp.where(fresh[:, None, None] > 0.5, w, buf)
    mixed = 0.5 * w + 0.5 * remote[partner]
    mu_new = (0.9 * mu + g) if has_mu else g
    stepped = mixed - 0.1 * mu_new
    w_exp = jnp.where(active[:, None, None] > 0.5, stepped, w)
    buf_exp = jnp.where(jnp.maximum(active, fresh)[:, None, None] > 0.5,
                        w_exp, buf)
    np.testing.assert_allclose(np.asarray(outs["ref"][0]), np.asarray(w_exp),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(outs["ref"][2]),
                               np.asarray(buf_exp), atol=1e-5)
    # inactive learner 0: weights and momentum untouched, nothing published
    np.testing.assert_array_equal(np.asarray(outs["pallas"][0][0]),
                                  np.asarray(w[0]))
    np.testing.assert_array_equal(np.asarray(outs["pallas"][2][0]),
                                  np.asarray(buf[0]))


@pytest.mark.parametrize("name", ["ring", "torus", "full", "hierarchical",
                                  "exp", "one_peer_exp", "random_pair",
                                  "random_matching"])
def test_schedule_tables_drive_kernel_parity(name):
    """Fused-vs-oracle parity on the EXACT tables every compiled schedule
    emits (K=1..5 across the set, multi-round cycles, padded self-loop
    slots), with the straggler mask and a per-learner lr scale folded in —
    the operands the flat engine really dispatches (DESIGN §12)."""
    from repro.core.schedule import make_schedule
    n, T = 8, 336                                     # non-multiple-of-256 T
    s = make_schedule(name, n, rounds=2)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    w = jax.random.normal(ks[0], (n, T, 128))
    g = jax.random.normal(ks[1], (n, T, 128))
    mu = jax.random.normal(ks[2], (n, T, 128))
    scale = jnp.linspace(0.5, 1.5, n)[:, None]
    active = jnp.ones((n,)).at[n - 1].set(0.0)[:, None]     # straggler
    for step in range(max(2, s.period)):
        for partners, coefs in s.step_rounds(jax.random.fold_in(ks[3], step),
                                             step):
            full = jnp.concatenate(
                [coefs, scale, active], axis=1).astype(jnp.float32)
            outs = [flat_gossip_update(w, w, g, mu, partners, full,
                                       lr=0.1, beta=0.9, backend=b)
                    for b in ("pallas", "ref")]
            for a, b in zip(*outs):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, err_msg=name)
            # straggler streams through untouched under every schedule
            np.testing.assert_array_equal(np.asarray(outs[0][0][n - 1]),
                                          np.asarray(w[n - 1]))
            w, mu = outs[0][0], outs[0][1]


def test_batched_kernel_solo_learner_keeps_self_mix():
    """coefs [1, 0]: the solo learner's 'mix' is exactly its own weights
    (the update still applies) — mirrors mix_pair_gather semantics."""
    n, T = 4, 256
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    w = jax.random.normal(ks[0], (n, T, 128))
    g = jax.random.normal(ks[1], (n, T, 128))
    partners = jnp.array([[1, 0, 3, 2]], jnp.int32)
    coefs = jnp.tile(jnp.array([1.0, 0.0, 1.0, 1.0], jnp.float32), (n, 1))
    w1, _ = flat_gossip_update(w, w, g, None, partners, coefs, lr=0.1,
                               backend="pallas")
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w - 0.1 * g),
                               atol=1e-6)


# T = 8 x 131: the only 8-aligned divisors are 8 and T itself, as
# transformer-100m's store admits only 8 and 40 below T
PRIME_T = 1048


@pytest.mark.parametrize("K,publish,has_mu,inactive", [
    (1, False, True, False), (2, False, True, False), (1, True, True, False),
    (1, False, False, False), (2, False, True, True)],
    ids=["K1", "K2", "publish", "no_momentum", "inactive"])
def test_flat_kernel_partial_block_is_bitwise_divisor_tiling(
        K, publish, has_mu, inactive):
    """A block that leaves a partial last block (256 rows: 4 whole + 24)
    gives outputs bitwise equal to a block that divides T (8 rows, the
    tiling a divisor-of-T rule takes here), and both match the oracle."""
    n, T = 4, PRIME_T
    ks = jax.random.split(jax.random.PRNGKey(K + 2 * publish + 4 * has_mu), 5)
    w = jax.random.normal(ks[0], (n, T, 128))
    g = jax.random.normal(ks[1], (n, T, 128))
    mu = jax.random.normal(ks[2], (n, T, 128))
    buf = jax.random.normal(ks[3], (n, T, 128)) if publish else None
    idx = jnp.arange(n)
    partners = jnp.stack([(idx + s) % n
                          for s in range(1, K + 1)]).astype(jnp.int32)
    mix = jnp.full((n, K + 1), 1.0 / (K + 1))
    scale = jnp.linspace(0.5, 1.5, n)[:, None]
    active = jnp.ones((n,))
    if inactive:
        active = active.at[n - 1].set(0.0)
    cols = [mix, scale, active[:, None]]
    if publish:
        fresh = jnp.zeros((n,)).at[1].set(1.0)
        cols += [fresh[partners[0]][:, None], jnp.maximum(active, fresh)[:, None]]
    coefs = jnp.concatenate(cols, axis=1).astype(jnp.float32)
    kw = dict(lr=0.1, beta=0.9, weight_decay=0.01, has_momentum=has_mu,
              buffer=buf)

    outs = {rows: gossip_mix_update_flat(w, w, g, mu, partners, coefs,
                                         interpret=True, block_rows=rows,
                                         **kw)
            for rows in (8, 256)}
    expect = ref.gossip_mix_update_flat_ref(w, w, g, mu, partners, coefs,
                                            **kw)
    assert T % 8 == 0 and T % 256
    for divides, partial, oracle in zip(outs[8], outs[256], expect):
        np.testing.assert_array_equal(np.asarray(partial),
                                      np.asarray(divides))
        np.testing.assert_allclose(np.asarray(partial), np.asarray(oracle),
                                   atol=1e-5)
    if inactive:
        np.testing.assert_array_equal(np.asarray(outs[256][0][n - 1]),
                                      np.asarray(w[n - 1]))


def test_gossip_kernel_partial_block_is_bitwise_divisor_tiling():
    """The single-learner kernel under the same two tilings of T = 8 x 131."""
    T, K = PRIME_T, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    w = jax.random.normal(ks[0], (T, 128))
    nb = jax.random.normal(ks[1], (K, T, 128))
    g = jax.random.normal(ks[2], (T, 128))
    mu = jax.random.normal(ks[3], (T, 128))
    coefs = jnp.array([0.5, 0.25, 0.25], jnp.float32)
    divides, partial = (gossip_mix_update(w, nb, g, mu, coefs, lr=0.1,
                                          beta=0.9, interpret=True,
                                          block_rows=rows)
                        for rows in (8, 256))
    expect = ref.gossip_mix_update_ref(w, nb, g, mu, coefs, lr=0.1, beta=0.9)
    for a, b, c in zip(divides, partial, expect):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
        np.testing.assert_allclose(np.asarray(b), np.asarray(c), atol=1e-5)


def test_flatten_roundtrip():
    tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 5))}}
    view, unflatten = flatten_for_kernel(tree)
    assert view.shape[1] == 128
    back = unflatten(view)
    np.testing.assert_allclose(np.asarray(back["a"]), np.asarray(tree["a"]))
    np.testing.assert_allclose(np.asarray(back["b"]["c"]),
                               np.asarray(tree["b"]["c"]))


def test_dpsgd_fused_update_tree():
    key = jax.random.PRNGKey(10)
    tree = {"w": jax.random.normal(key, (33, 7)), "b": jnp.ones((5,))}
    nbr = jax.tree_util.tree_map(lambda x: x + 1.0, tree)
    g = jax.tree_util.tree_map(jnp.ones_like, tree)
    mu = jax.tree_util.tree_map(jnp.zeros_like, tree)
    new_w, new_mu = dpsgd_fused_update(tree, [nbr], g, mu, [0.5, 0.5],
                                       lr=0.1, beta=0.9)
    # mixed = (w + (w+1))/2 = w + 0.5 ; mu = g = 1 ; new = mixed - 0.1
    np.testing.assert_allclose(np.asarray(new_w["w"]),
                               np.asarray(tree["w"] + 0.5 - 0.1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_mu["b"]), 1.0, atol=1e-6)
