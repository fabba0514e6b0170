"""DPSGD training of a DeepSeek-V3-class share (MLA, sigmoid-routed held
experts, shared experts) through the program's launch step.

One learner per chip, gossip by collective-permute
(``repro.launch.train.make_dpsgd_train_step``), as ``train.py``'s launch
engine, with the step's whole metrics kept: the MoE counter
``moe_held_assignments`` rides with the loss.  Set-up drives the check
steps and takes ``train.program_readings``; the run then measures the
window, and after it, with the program's state freed, the plain reference
(``reference_mla_moe``) starts from the same weights and batches and is
compared by ``train.compare`` against the cell's limits.  The initial
weights wait on the host while the window runs.

A program without latent attention fails at once, in ``mla_moe.build``.
"""
from __future__ import annotations

import functools

from . import cell as C
from . import drive
from . import mla_moe as MM
from . import reference as R
from . import reference_mla_moe as RM
from . import train


class _Metrics:
    __slots__ = ("loss", "held")

    def __init__(self, m):
        self.loss = m["loss"]
        self.held = m.get("moe_held_assignments")


def _launch(cell, api, params0, seed, devices, count):
    """(state, step, view, batches, info) for one learner per chip."""
    import jax
    import jax.numpy as jnp
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (jit_train_step, make_dpsgd_train_step,
                                    train_state_shardings, train_state_specs)
    tr = cell.traffic
    L = len(devices)
    mesh = make_mesh((L, 1), ("data", "model"), devices=devices)
    opt = train.optimizer(tr)
    fn = make_dpsgd_train_step(api, opt, mesh, topology=tr["topology"],
                               gossip_backend=tr["gossip_backend"])
    specs = train_state_specs(api, opt, mesh, algo="dpsgd")
    shds = shd.named_shardings(
        train_state_shardings(specs, mesh, algo="dpsgd"), mesh)

    def init(p0):
        params = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), p0)
        return type(specs)(params=params,
                           opt_state=jax.vmap(opt.init)(params),
                           step=jnp.zeros((), jnp.int32),
                           rng=train.train_key(seed))
    state = jax.jit(init, out_shardings=shds)(params0)
    GB = L * tr["local_batch"]
    bshd = shd.named_shardings(shd.batch_sharding(
        api.train_batch_spec(GB, tr["seq"]), mesh, stacked=False), mesh)
    pool_shd = jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, *s.spec)), bshd)
    batches = train.make_batches(seed, count, (GB,), tr["seq"],
                                 api.cfg.vocab, sharding=pool_shd)
    batches = [jax.device_put(b, bshd) for b in batches]
    with jax.set_mesh(mesh):
        step = jit_train_step(fn, in_shardings=(shds, bshd),
                              out_shardings=(shds, None))

    def run_step(st, b):
        with jax.set_mesh(mesh):
            st, m = step(st, b)
        return st, _Metrics(m)

    def view(st):
        return st.params, st.opt_state["inner"]["mu"]
    return state, run_step, view, batches, {"learners": L}


def reference_step(w, mu, batch, mix, lr, beta, share, chips=1):
    """``reference.dpsgd_step`` with the MLA/MoE loss: each learner's
    gradient at its own weights (one learner a chip at a time), momentum,
    the mixing, the step."""
    import jax
    import jax.numpy as jnp

    def one(args):
        p, b = args
        return jax.value_and_grad(lambda q: RM.loss(q, b, share))(p)
    losses, g = jax.lax.map(one, (w, batch), batch_size=chips)
    mu = jax.tree_util.tree_map(lambda m, gg: beta * m + gg, mu, g)
    mixed = jax.tree_util.tree_map(
        lambda a: jnp.einsum("ij,j...->i...", mix, a, precision=R.HIGHEST),
        w)
    w = jax.tree_util.tree_map(lambda a, u: a - lr * u, mixed, mu)
    return w, mu, jnp.mean(losses), R.leaf_norms(g)


def _half_batch(batch, n):
    """The batch with the second half of each learner's rows masked out of
    the loss: the loss over the first half, at the same shapes."""
    import jax.numpy as jnp
    rows = batch["mask"].shape[0] // n
    keep = (jnp.arange(rows * n) % rows) < rows // 2
    return dict(batch, mask=batch["mask"] * keep[:, None])


def _no_mixing(tr, key, step, n):
    import jax.numpy as jnp
    return jnp.eye(n, dtype=jnp.float32)


def _reference(cell, params0, batches, seed, n, devices, fault=None):
    """The reference's readings; ``fault`` plants one where the reference
    stands in for the program: 'half_batch' takes the loss over the first
    half of each learner's rows, 'no_exchange' leaves the mixing out.  Both
    feed the sound reference's compiled step other data (a mask, an
    identity mixing), so a fault compiles nothing more."""
    step = functools.partial(
        reference_step, beta=cell.traffic["optimizer"]["momentum"],
        share=MM.share_of(cell.config), chips=len(devices))
    if fault == "half_batch":
        batches = [_half_batch(b, n) for b in batches]
    elif fault is not None and fault != "no_exchange":
        raise ValueError(f"unknown fault {fault!r}")
    return drive.reference_readings(
        cell, step, params0, batches, seed, n, devices,
        mixing=_no_mixing if fault == "no_exchange" else R.mixing)


def _setup(cell, seed, devices, dtype, count):
    """The model, the initial weights (moved to the host) and the engine."""
    import jax
    api = MM.build(cell.config, dtype)
    params0 = MM.make_params(api, seed)
    state, step, view, batches, info = _launch(cell, api, params0, seed,
                                               devices, count)
    host = jax.device_get(params0)
    del params0
    return api, host, state, step, view, batches, info


def run(cell: C.Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, profiler=None, dtype=None) -> C.Run:
    tr = cell.traffic
    share = MM.share_of(cell.config)
    api, params0, state, step, view, batches, info = _setup(
        cell, seed, devices, dtype, tr["batches"])
    n = info["learners"]
    state, prog = train.program_readings(cell, step, view, state, batches,
                                         params0)
    run = C.Run(cell=cell)
    state, m, steps = drive.window(run, step, state, batches, seconds,
                                   trace, profiler, t_start, devices)
    tokens_per_step = n * tr["local_batch"] * tr["seq"]
    run.counters.update(
        steps=steps, tokens=steps * tokens_per_step,
        tokens_per_step=tokens_per_step, mla_moe=share, seq=tr["seq"],
        chips=len(devices), learners=n,
        # held (token, expert) assignments a learner computed, summed over
        # the MoE layers, in the window's last step
        held_assignments=None if m.held is None else float(m.held))
    del state, step, m
    ref = _reference(cell, params0, batches, seed, n, devices)
    run.extra["readings"] = {"program": train._plain(prog),
                             "reference": train._plain(ref)}
    run.checks = train.checks(cell, train.compare(prog, ref))
    return run


def calibration(cell: C.Cell, seed: int, devices, dtype=None,
                fault=None) -> dict:
    """As ``train.calibration``: the numbers compared for one seed, with
    no window; the program (``dtype``: the control), or with ``fault`` the
    reference with that fault planted against the reference."""
    tr = cell.traffic
    api, params0, state, step, view, batches, info = _setup(
        cell, seed, devices, dtype, tr["check_steps"])
    n = info["learners"]
    if fault is None:
        state, prog = train.program_readings(cell, step, view, state,
                                             batches, params0)
        del state, step
    else:
        del state, step
        prog = _reference(cell, params0, batches, seed, n, devices,
                          fault=fault)
    ref = _reference(cell, params0, batches, seed, n, devices)
    return train.compare(prog, ref)

