"""Kernel microbench: wall time of the pure-jnp oracle vs the Pallas kernel
in interpret mode (CPU container — interpret mode measures CORRECTNESS cost,
not TPU speed), plus the derived HBM-traffic model ratio that motivates the
fusion (DESIGN.md §7)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import (flat_gossip_update, gossip_mix_update, ref,
                           reorth_pass)
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ops import dpsgd_fused_update

from .common import parse_smoke, write_table


def timeit(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def main(argv=None):
    smoke = parse_smoke(argv)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    rows = []

    T, K = (1024 if smoke else 4096), 2
    w = jax.random.normal(ks[0], (T, 128))
    nb = jax.random.normal(ks[1], (K, T, 128))
    g = jax.random.normal(ks[2], (T, 128))
    mu = jax.random.normal(ks[3], (T, 128))
    coefs = jnp.array([0.5, 0.25, 0.25])
    us_ref = timeit(lambda *a: ref.gossip_mix_update_ref(
        *a, lr=0.1, beta=0.9)[0], w, nb, g, mu, coefs)
    us_int = timeit(lambda *a: gossip_mix_update(
        *a, lr=0.1, beta=0.9, interpret=True)[0], w, nb, g, mu, coefs)
    # HBM traffic model: unfused 3 passes (mix, momentum, apply) vs fused 1
    unfused = (1 + K + 1) * 4 + (1 + 1) * 4 + (2 + 1) * 4   # per elem bytes
    fused = (1 + K + 1 + 1) * 4 + 2 * 4
    rows.append(["gossip_mix", us_ref, us_int, unfused / fused])

    # end-to-end engine step: the per-call flatten wrapper (re-flattens every
    # pytree on every call — the pre-PR3 hot-path overhead) vs the flat
    # engine's persistent (n, T, 128) store feeding the batched update
    # directly (DESIGN §11).  Timing this end to end keeps the removed
    # flatten regression visible if it ever sneaks back.
    n = 4
    tree = {"w1": jax.random.normal(ks[0], (512, 96)),
            "b1": jnp.ones((96,)),
            "w2": jax.random.normal(ks[1], (96, 48))}
    nbr = jax.tree_util.tree_map(lambda x: x + 1.0, tree)
    gt = jax.tree_util.tree_map(jnp.ones_like, tree)
    mt = jax.tree_util.tree_map(jnp.zeros_like, tree)
    us_wrap = timeit(lambda *a: dpsgd_fused_update(
        *a, [0.5, 0.5], lr=0.1, beta=0.9)[0]["w1"], tree, [nbr], gt, mt)
    from repro.core.flatstate import flat_meta
    meta = flat_meta(tree)
    Tn = meta.rows
    wf = jax.random.normal(ks[2], (n, Tn, 128))
    gf = jnp.ones((n, Tn, 128))
    mf = jnp.zeros((n, Tn, 128))
    partners = jnp.array([[1, 0, 3, 2]], jnp.int32)
    coefs = jnp.tile(jnp.array([0.5, 0.5, 1.0, 1.0], jnp.float32), (n, 1))
    flat_step = jax.jit(lambda w, g, mu: flat_gossip_update(
        w, w, g, mu, partners, coefs, lr=0.1, beta=0.9, backend="pallas")[0])
    us_flat = timeit(flat_step, wf, gf, mf)
    # traffic model, K=1: wrapper re-flattens {w, nbr, g, mu} (2 passes
    # each) + kernel (4r+2w) + unflattens {w, mu} (2 passes each) vs the
    # persistent store's bare kernel passes
    rows.append(["gossip_mix_e2e", us_wrap, us_flat / n,
                 (2 * 4 + 6 + 2 * 2) / 6])

    # Lanczos full-reorth sweep (landscape probe inner loop, DESIGN §10):
    # fused dots+axpy streams {V, w} once per pass vs once per basis vector
    M = 4 if smoke else 8
    V = jax.random.normal(ks[0], (M, T, 128))
    wv = jax.random.normal(ks[1], (T, 128))
    mask = jnp.ones((M,), jnp.float32)
    us_ref3 = timeit(lambda *a: ref.reorth_ref(*a)[0], V, wv, mask)
    us_int3 = timeit(lambda *a: reorth_pass(*a, interpret=True)[0],
                     V, wv, mask)
    # traffic model: unfused 2M passes over w + 2 over V vs fused 2 + 2
    rows.append(["reorth", us_ref3, us_int3, (2 * M + 2) / 4])

    S, hd = (256 if smoke else 512), 64
    q = jax.random.normal(ks[0], (1, 4, S, hd))
    k = jax.random.normal(ks[1], (1, 2, S, hd))
    v = jax.random.normal(ks[2], (1, 2, S, hd))
    us_ref2 = timeit(lambda *a: ref.flash_attention_ref(*a, causal=True),
                     q, k, v)
    us_int2 = timeit(lambda *a: flash_attention_fwd(
        *a, causal=True, block_q=128, block_k=128, interpret=True), q, k, v)
    # derived: causal tile skipping -> ~2x fewer score flops + no S^2 matrix
    rows.append(["flash_attention", us_ref2, us_int2, 2.0])

    write_table("bench_kernels",
                ["kernel", "ref_us", "interpret_us", "derived_traffic_ratio"],
                rows)
    for name, us_ref_, us_int_, ratio in rows:
        print(f"bench_kernel_{name},{us_ref_:.0f},"
              f"traffic_ratio={ratio:.2f} interpret_us={us_int_:.0f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
