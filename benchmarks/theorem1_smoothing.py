"""Theorem 1 verification.

(a) Analytic non-smooth case: L(w) = G*||w||_1 is G-Lipschitz with unbounded
    gradient-Lipschitz constant at the kinks.  Nesterov-Spokoiny Lemma 2
    (used by the paper) bounds the smoothed landscape at 2G/sigma — we
    measure the empirical l_s of L~ for a sweep of sigma and check the
    ~1/sigma decay.  This is the regime the theorem addresses (the paper
    invokes it for ReLU nets whose raw l_s can be "close to +inf").
(b) FC-net data point: the same probe on the paper's MNIST net at init
    (reported, not asserted: at generic points the raw landscape is locally
    smooth and the MC estimator variance dominates — an honest limitation
    of sampling-based smoothness probes, noted in EXPERIMENTS.md).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.smoothing import estimate_smoothness
from repro.data import TemplateImages
from repro.models import fcnet

from .common import parse_smoke, write_table

G = 1.0


def rough_loss(params, batch):
    return G * jnp.sum(jnp.abs(params["w"])) + 0.0 * jnp.sum(batch["x"])


def main(argv=None):
    smoke = parse_smoke(argv)
    t0 = time.perf_counter()
    params = {"w": jnp.full((64,), 0.01)}
    batch = {"x": jnp.zeros((1,))}
    key = jax.random.PRNGKey(0)
    rows = []
    ls_raw = float(estimate_smoothness(rough_loss, params, batch, key,
                                       sigma=0.0, n_pairs=6,
                                       probe_radius=0.02))
    rows.append(["l1_analytic", 0.0, ls_raw, float("nan")])
    for sigma in (0.1, 0.8) if smoke else (0.1, 0.2, 0.4, 0.8):
        ls = float(estimate_smoothness(rough_loss, params, batch, key,
                                       sigma=sigma, n_pairs=6, n_mc=64,
                                       probe_radius=0.02))
        rows.append(["l1_analytic", sigma, ls, 2 * G / sigma])

    # FC-net data point (reported, not asserted)
    ds = TemplateImages()
    fb = ds.sample(jax.random.PRNGKey(1), 256)
    fp = fcnet.init_params(jax.random.PRNGKey(2), in_dim=784, hidden=50)
    for sigma in (0.2,) if smoke else (0.0, 0.2):
        ls = float(estimate_smoothness(fcnet.loss_fn, fp, fb,
                                       jax.random.PRNGKey(3), sigma=sigma,
                                       n_pairs=4, n_mc=32,
                                       probe_radius=0.02))
        rows.append(["fcnet_init", sigma, ls, float("nan")])

    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    write_table("theorem1_smoothing",
                ["landscape", "sigma_w", "empirical_l_s", "bound_2G_over_s"],
                rows)
    sm = [r for r in rows if r[0] == "l1_analytic" and r[1] > 0]
    decays = all(sm[i][2] > sm[i + 1][2] for i in range(len(sm) - 1))
    within = all(r[2] <= r[3] * 1.5 for r in sm)
    derived = (f"raw l_s={ls_raw:.1f}; smoothed l_s "
               f"{sm[0][2]:.2f}@s=0.1 -> {sm[-1][2]:.2f}@s=0.8 "
               f"monotone={decays} within 1.5x of 2G/sigma={within}")
    print(f"theorem1_smoothing,{us:.0f},{derived}")
    assert decays, sm


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
