"""Landscape probe engine + closed-loop AutoLR (DESIGN §10).

Pinned against a quadratic with a KNOWN (rotated, non-diagonal) Hessian:
  * Lanczos top eigenvalue and Hutchinson Tr(H) within 5% of analytic,
  * Tr(H C) exact against the explicit covariance contraction,
  * Pallas and ref reorthogonalization bitwise-close,
  * Eq. 4 predictor algebra,
and the headline closed-loop scenario: at alpha * lambda_max = 2.4 (beyond
the stability edge) SSGD diverges while SSGD+AutoLR converges to a loss
threshold — on BOTH the vmap research trainer and the launch/train.py
(pjit) path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AlgoConfig, MultiLearnerTrainer
from repro.kernels import ref, reorth_pass, reorthogonalize
from repro.landscape import (AutoLRController, ProbeSchedule,
                             hutchinson_trace, lanczos_pytree,
                             make_trainer_probe, predict_alpha_e,
                             probe_landscape, sharpness, trace_hc)
from repro.optim import (controller_scale, scale_by_controller,
                         set_controller_scale, sgd)

# ---------------------------------------------------------------------------
# the analytic fixture: L(w) = 0.5 w^T A w, A = Q diag(lam) Q^T
# ---------------------------------------------------------------------------

D = 16
LAM = jnp.concatenate([jnp.linspace(1.0, 10.0, D - 1), jnp.array([25.0])])
_Q, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(7), (D, D)))
A = _Q @ jnp.diag(LAM) @ _Q.T


def quad_loss(params, batch):
    w = params["w"]
    return 0.5 * w @ A @ w + 0.0 * jnp.sum(batch["x"])


def make_batch(n, b=2):
    return {"x": jnp.zeros((n, b, 1))}


# ---------------------------------------------------------------------------
# estimator accuracy (acceptance: within 5% of analytic)
# ---------------------------------------------------------------------------

def test_lanczos_top_eigenvalue_within_5pct():
    params = {"w": jnp.ones((D,))}
    r = lanczos_pytree(quad_loss, params, make_batch(1), m=10,
                       key=jax.random.PRNGKey(0))
    top = float(sharpness(r))
    assert abs(top - 25.0) / 25.0 < 0.05
    # with full reorthogonalization the whole Ritz spectrum stays inside
    # the true spectral interval (no spurious copies outside [min, max])
    assert float(r.eigenvalues[0]) > 0.5
    assert float(r.eigenvalues[-1]) < 25.0 * 1.05


def test_hutchinson_trace_within_5pct():
    params = {"w": jnp.ones((D,))}
    tr = float(hutchinson_trace(quad_loss, params, make_batch(1),
                                jax.random.PRNGKey(1), n_samples=64))
    true = float(jnp.sum(LAM))
    assert abs(tr - true) / true < 0.05


def test_trace_hc_exact_against_explicit_contraction():
    n = 4
    ws = jax.random.normal(jax.random.PRNGKey(2), (n, D)) * 0.3
    got = float(trace_hc(quad_loss, {"w": ws}, make_batch(n)))
    dev = ws - jnp.mean(ws, axis=0, keepdims=True)
    want = float(jnp.mean(jax.vmap(lambda v: v @ A @ v)(dev)))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_probe_landscape_bundle_and_predictor():
    n = 4
    ws = jax.random.normal(jax.random.PRNGKey(3), (n, D)) * 0.2
    r = probe_landscape(quad_loss, {"w": ws}, make_batch(n),
                        jax.random.PRNGKey(4), alpha=0.05, lanczos_iters=10,
                        hutchinson_samples=32)
    assert abs(float(r.sharpness) - 25.0) / 25.0 < 0.05
    sig = float(jnp.sum(jnp.var(ws, axis=0)))
    np.testing.assert_allclose(float(r.sigma_w_sq), sig, rtol=1e-5)
    # Eq. 4: alpha_e_pred == alpha (1 - alpha/2 * TrHC / sigma_w^2)
    want = 0.05 * (1.0 - 0.025 * float(r.trace_hc) / sig)
    np.testing.assert_allclose(float(r.alpha_e_pred), want, rtol=1e-5)
    # identical learners: spread terms vanish, prediction collapses to alpha
    same = {"w": jnp.broadcast_to(ws[0], (n, D))}
    r0 = probe_landscape(quad_loss, same, make_batch(n), jax.random.PRNGKey(4),
                         alpha=0.05, lanczos_iters=8, hutchinson_samples=4)
    np.testing.assert_allclose(float(r0.alpha_e_pred), 0.05, rtol=1e-6)
    assert float(predict_alpha_e(0.1, 0.0, 0.0)) == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# Pallas vs ref reorthogonalization (acceptance: bitwise-close)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,M,live", [(2, 4, 4), (512, 6, 3), (331, 8, 8)])
def test_reorth_pallas_vs_ref(T, M, live):
    """One CGS sweep through the fused kernels == the jnp oracle, including
    masking of the dead basis suffix and non-block-multiple row counts."""
    key = jax.random.PRNGKey(T + M)
    basis_raw = jax.random.normal(key, (M, T * 128))
    q, _ = jnp.linalg.qr(basis_raw.T)
    basis = q.T.reshape(M, T, 128)
    w = jax.random.normal(jax.random.fold_in(key, 1), (T, 128))
    mask = (jnp.arange(M) < live).astype(jnp.float32)

    w_k, d_k = reorth_pass(basis, w, mask, interpret=True)
    w_r, d_r = ref.reorth_ref(basis, w, mask)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r), atol=1e-5)

    # CGS2 wrapper: output is orthogonal to the live basis prefix
    w2 = reorthogonalize(basis, w, mask)
    resid = jnp.einsum("mtl,tl->m", basis, w2) * mask
    assert float(jnp.max(jnp.abs(resid))) < 1e-4 * float(jnp.linalg.norm(w2))


# ---------------------------------------------------------------------------
# schedule / controller / optimizer-adapter units
# ---------------------------------------------------------------------------

def test_probe_schedule_due():
    s = ProbeSchedule(every=10, start=20)
    assert [i for i in range(45) if s.due(i)] == [20, 30, 40]
    assert not any(ProbeSchedule(every=0).due(i) for i in range(5))


def test_autolr_controller_clamps_and_releases():
    ctl = AutoLRController(alpha0=0.1, rho=1.8, min_scale=0.05, ema=0.0)

    def probe_with(sharp):
        z = jnp.zeros(())
        from repro.landscape import ProbeResult
        return ProbeResult(jnp.float32(sharp), z, z, z, z, z, z)

    assert ctl.update(probe_with(180.0)) == pytest.approx(0.1)   # 1.8/(0.1*180)
    assert ctl.update(probe_with(1e6)) == 0.05                   # min clamp
    assert ctl.update(probe_with(1.0)) == 1.0                    # max clamp
    assert ctl.update(probe_with(0.0)) == 1.0                    # flat: release


def test_scale_by_controller_adapter():
    opt = scale_by_controller(sgd(1.0))
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)
    grads = {"w": jnp.ones((4,))}
    upd, state = opt.update(grads, state, params)
    np.testing.assert_allclose(np.asarray(upd["w"]), -1.0)
    state = set_controller_scale(state, 0.25)
    assert float(controller_scale(state)) == 0.25
    upd, state = opt.update(grads, state, params)
    np.testing.assert_allclose(np.asarray(upd["w"]), -0.25)
    # survives apply and a stacked (vmapped) state
    stacked = jax.vmap(opt.init)({"w": jnp.ones((3, 4))})
    stacked = set_controller_scale(stacked, 0.5)
    assert stacked["scale"].shape == (3,)
    # composes in either wrap order: the setter finds the controller layer
    # through outer wrappers (scale_by_schedule adds an "inner" level)
    from repro.optim import constant_schedule, scale_by_schedule
    nested = scale_by_schedule(scale_by_controller(sgd(1.0)),
                               constant_schedule(2.0))
    st = set_controller_scale(nested.init(params), 0.3)
    assert float(controller_scale(st)) == pytest.approx(0.3)
    upd, _ = nested.update(grads, st, params)
    np.testing.assert_allclose(np.asarray(upd["w"]), -0.6)  # 2.0 * 0.3 * -1


# ---------------------------------------------------------------------------
# the headline scenario: SSGD diverges, SSGD+AutoLR converges
# ---------------------------------------------------------------------------

ALPHA = 0.096          # alpha * lambda_max = 2.4 > 2: SSGD diverges
N_STEPS = 120


def _mean_loss(w_stacked):
    w = jnp.mean(w_stacked, axis=0)
    return float(0.5 * w @ A @ w)


def test_ssgd_autolr_beats_ssgd_on_vmap_trainer():
    n = 2
    batch = make_batch(n)
    init = {"w": jnp.ones((D,))}
    loss0 = _mean_loss(jnp.broadcast_to(init["w"], (n, D)))

    # plain SSGD at alpha: the lambda_max mode grows by |1 - 2.4| per step
    tr = MultiLearnerTrainer(quad_loss, sgd(ALPHA),
                             AlgoConfig(algo="ssgd", n_learners=n))
    st = tr.init(jax.random.PRNGKey(0), init)
    for _ in range(60):
        st, m = tr.train_step(st, batch)
    diverged = _mean_loss(st.params["w"])
    assert not np.isfinite(diverged) or diverged > 1e4 * loss0

    # SSGD+AutoLR: probe-driven clamp pulls alpha*lambda inside the edge
    ctl = AutoLRController(alpha0=ALPHA)
    tr2 = MultiLearnerTrainer(quad_loss, scale_by_controller(sgd(ALPHA)),
                              AlgoConfig(algo="ssgd", n_learners=n))
    probe_fn = make_trainer_probe(quad_loss, alpha=ALPHA, lanczos_iters=10,
                                  hutchinson_samples=4)

    def on_probe(state, r):
        return state._replace(opt_state=set_controller_scale(
            state.opt_state, ctl.update(r)))

    tr2.add_probe("landscape", ProbeSchedule(every=10), probe_fn,
                  on_result=on_probe)
    st2 = tr2.init(jax.random.PRNGKey(0), init)
    for i in range(N_STEPS):
        if tr2.probes_due(i):
            st2, _ = tr2.run_probes(st2, batch, step=i)
        st2, m = tr2.train_step(st2, batch)
    final = _mean_loss(st2.params["w"])
    assert np.isfinite(final) and final < 1e-3 * loss0
    # the controller actually intervened (scale strictly below 1)
    assert ctl.scale < 1.0
    # the controlled effective step sits inside the stability edge
    assert 0.5 < ALPHA * ctl.sharpness_ema * ctl.scale < 2.0


def test_ssgd_autolr_beats_ssgd_on_launch_path():
    """Same scenario through the pjit/shard_map production path: the
    launch/train.py SSGD step + the sharded probe entry point
    (make_probe_step, stacked=False) + the controller closing the loop
    through set_controller_scale."""
    from types import SimpleNamespace

    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import (PjitTrainState, jit_train_step,
                                    make_probe_step, make_ssgd_train_step)

    mesh = make_test_mesh(1, 1)
    api = SimpleNamespace(loss_fn=quad_loss)
    batch = {"x": jnp.zeros((2, 1))}        # (GB, ...) with L=1 learner
    init = {"w": jnp.ones((D,))}
    loss0 = float(0.5 * init["w"] @ A @ init["w"])

    def run(optimizer, with_autolr, steps):
        step_fn = jit_train_step(make_ssgd_train_step(api, optimizer, mesh))
        probe_fn = jax.jit(make_probe_step(api, mesh, alpha=ALPHA,
                                           stacked=False, lanczos_iters=10,
                                           hutchinson_samples=4))
        ctl = AutoLRController(alpha0=ALPHA)
        # the jitted step donates its state: give each run its own buffers
        init_run = jax.tree_util.tree_map(jnp.copy, init)
        state = PjitTrainState(params=init_run,
                               opt_state=optimizer.init(init_run),
                               step=jnp.zeros((), jnp.int32),
                               rng=jax.random.PRNGKey(0))
        with jax.set_mesh(mesh):
            for i in range(steps):
                if with_autolr and i % 10 == 0:
                    r = probe_fn(state.params, batch,
                                 jax.random.fold_in(jax.random.PRNGKey(5), i))
                    state = state._replace(opt_state=set_controller_scale(
                        state.opt_state, ctl.update(r)))
                state, metrics = step_fn(state, batch)
        w = state.params["w"]
        return float(0.5 * w @ A @ w), ctl

    diverged, _ = run(sgd(ALPHA), with_autolr=False, steps=60)
    assert not np.isfinite(diverged) or diverged > 1e4 * loss0

    final, ctl = run(scale_by_controller(sgd(ALPHA)), with_autolr=True,
                     steps=N_STEPS)
    assert np.isfinite(final) and final < 1e-3 * loss0
    assert ctl.scale < 1.0
