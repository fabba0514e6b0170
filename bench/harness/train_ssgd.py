"""Synchronous SGD (the paper's baseline) through the program's research
trainer: ``repro.core.MultiLearnerTrainer`` with ``algo="ssgd"``, n learners
stacked on one chip in the pytree layout (SSGD bypasses the flat engine and
its fused gossip kernel).

Each step the learners take their gradients at the mean weights on their
own batches; the mean gradient feeds every learner's momentum alike, and
the step keeps the copies equal.  The reference (``reference_step``)
computes the same in float32 at ``highest`` precision from the same weights
and batches; its ``no_exchange`` fault keeps each learner's own gradient,
its ``half_batch`` fault takes the loss over half of each learner's rows.
The run and the comparison are ``train.py``'s.
"""
from __future__ import annotations

import functools

from . import cell as C
from . import drive
from . import model as M
from . import reference as R
from . import train


def _stacked(cell, api, params0, seed, devices, count):
    from repro.core import AlgoConfig, MultiLearnerTrainer
    tr = cell.traffic
    n = tr["learners"]
    trainer = MultiLearnerTrainer(
        api.loss_fn, train.optimizer(tr),
        AlgoConfig(algo=tr["algo"], topology=tr["topology"], n_learners=n))
    state = trainer.init(train.train_key(seed), params0)
    batches = train.make_batches(seed, count, (n, tr["local_batch"]),
                                 tr["seq"], api.cfg.vocab)

    def view(st):
        v = trainer.state_view(st)
        return v.params, v.opt_state["inner"]["mu"]
    return state, trainer.train_step, view, batches, {"learners": n}


def reference_step(w, mu, batch, mix, lr, beta, shard, fault=None,
                   chips=1):
    """One SSGD step on stacked (n, ...) weights and momentum: every
    learner's gradient at the mean weights, their mean into every
    learner's momentum, the step.  ``mix`` is unused (SSGD averages)."""
    import jax
    import jax.numpy as jnp
    del mix
    if fault == "half_batch":
        half = batch["tokens"].shape[1] // 2
        batch = jax.tree_util.tree_map(lambda a: a[:, :half], batch)
    exchange = fault != "no_exchange"
    mean = lambda a: jnp.broadcast_to(jnp.mean(a, axis=0), a.shape)
    w_a = jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), w)

    def one(b):
        return jax.value_and_grad(lambda q: R.loss(q, b, shard))(w_a)
    losses, g = jax.lax.map(one, batch, batch_size=chips)
    if exchange:
        g = jax.tree_util.tree_map(mean, g)
    mu = jax.tree_util.tree_map(lambda m, gg: beta * m + gg, mu, g)
    w = jax.tree_util.tree_map(lambda a, u: a - lr * u, w, mu)
    if exchange:
        w = jax.tree_util.tree_map(mean, w)
    return w, mu, jnp.mean(losses), R.leaf_norms(g)


def _reference(cell, params0, batches, seed, n, devices, fault=None):
    step = functools.partial(
        reference_step, beta=cell.traffic["optimizer"]["momentum"],
        shard=M.shard_of(cell.config), fault=fault, chips=len(devices))
    return drive.reference_readings(cell, step, params0, batches, seed, n,
                                    devices)


def run(cell: C.Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, profiler=None, dtype=None) -> C.Run:
    tr = cell.traffic
    shard = M.shard_of(cell.config)
    api = M.build(cell.config, dtype)
    params0 = M.make_params(api, seed)
    state, step, view, batches, info = _stacked(cell, api, params0, seed,
                                                devices, tr["batches"])
    n = info["learners"]
    state, prog = train.program_readings(cell, step, view, state, batches,
                                         params0)
    run = C.Run(cell=cell)
    state, m, steps = drive.window(run, step, state, batches, seconds,
                                   trace, profiler, t_start, devices)
    tokens_per_step = n * tr["local_batch"] * tr["seq"]
    run.counters.update(steps=steps, tokens=steps * tokens_per_step,
                        tokens_per_step=tokens_per_step, shard=shard,
                        seq=tr["seq"], chips=len(devices), learners=n)
    del state, step, m
    ref = _reference(cell, params0, batches, seed, n, devices)
    run.extra["readings"] = {"program": train._plain(prog),
                             "reference": train._plain(ref)}
    run.checks = train.checks(cell, train.compare(prog, ref))
    return run


def calibration(cell: C.Cell, seed: int, devices, dtype=None,
                fault=None) -> dict:
    """As ``train.calibration``, for SSGD."""
    tr = cell.traffic
    api = M.build(cell.config, dtype)
    params0 = M.make_params(api, seed)
    state, step, view, batches, info = _stacked(
        cell, api, params0, seed, devices, tr["check_steps"])
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
