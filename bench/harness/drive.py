"""What the training drivers beyond ``train.py`` share: the timed window
and the reference's readings, for any step.

``window`` is ``train.run``'s measured loop: the step is called with the
cell's batches in turn, at most two steps in flight, the last
``trace_seconds`` under the profiler where the run is traced.
``reference_readings`` drives a plain reference step (weights and momentum
stacked over the learners, spread over the chips as the program's are)
through the check steps and takes ``train.program_readings``' readings
from it.
"""
from __future__ import annotations

import collections
import time

from . import reference as R
from . import train


def window(run, step, state, batches, seconds: float, trace: bool,
           profiler, t_start: float, devices):
    """Run the timed window from set-up's end; fills ``run.setup_s``,
    ``run.window_s``, ``run.attempted``, ``run.trace`` and the devices'
    memory peak.  Returns (state, the metrics of the last step, steps)."""
    import jax
    tr = run.cell.traffic
    k = tr["check_steps"]
    inflight = collections.deque()
    steps = traced_from = 0
    trace_at = seconds - tr["trace_seconds"] if trace else float("inf")
    ann = jax.profiler.TraceAnnotation
    run.setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    while True:
        b = batches[(k + steps) % len(batches)]
        with ann("bench.step") if trace else train._nothing():
            state, m = step(state, b)
        steps += 1
        inflight.append(m.loss)
        if len(inflight) > 2:
            with ann("bench.wait") if trace else train._nothing():
                inflight.popleft().block_until_ready()
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if trace and not traced_from and now >= trace_at:
            profiler.start()
            traced_from = steps
    jax.block_until_ready(state)
    run.window_s = time.perf_counter() - t0
    if traced_from:
        run.trace = profiler.stop()
    run.attempted = steps
    run.device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices)
    return state, m, steps


def reference_readings(cell, ref_step, params0, batches, seed, n, devices,
                       mixing=R.mixing):
    """Readings of ``ref_step(w, mu, batch, mix, lr) -> (w, mu, mean loss,
    per-leaf gradient norms)`` from ``params0`` on every learner, over the
    cell's check steps (the mixing of ``mixing(traffic, key, step, n)``,
    ``reference.mixing`` by default, and the learning rate of
    ``train.lr_at``).  Weights and momentum are donated from step to
    step."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    tr = cell.traffic
    key = train.train_key(seed)
    kw, place = {}, (lambda b: b)
    if len(devices) > 1:
        mesh = jax.sharding.Mesh(np.asarray(devices), ("learner",))
        lead = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("learner"))
        kw["out_shardings"] = lead
        place = functools.partial(jax.device_put, device=lead)

    def start(p0):
        w = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a.astype(jnp.float32)[None],
                                       (n,) + a.shape), p0)
        return w, jax.tree_util.tree_map(jnp.zeros_like, w)
    w, mu = jax.jit(start, **kw)(params0)
    step = jax.jit(ref_step, donate_argnums=(0, 1))
    losses, g1 = [], None
    for t in range(tr["check_steps"]):
        mix = mixing(tr, key, t, n)
        w, mu, lo, gn = step(w, mu, place(train.per_learner(batches[t], n)),
                             mix, train.lr_at(tr, t))
        losses.append(float(lo))
        if t == 0:
            g1 = jax.device_get(gn)
    dw = jax.device_get(jax.jit(R.change_norms)(w, params0))
    return {"loss": losses, "grad": g1, "change": dw}
