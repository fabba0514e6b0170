"""DPSGD training through the program's own step.

Two engines, named by the traffic file's ``engine``:

* ``stacked`` -- ``repro.core.MultiLearnerTrainer`` with n learners stacked
  on one chip (the flat engine and its fused gossip kernel);
* ``launch`` -- ``repro.launch.train.make_dpsgd_train_step`` with one
  learner per chip and gossip by collective-permute.

Set-up builds one compiled step with its state and drives it through its
first ``check_steps`` steps, on distinct batches, through the same call and
feed the window uses.  The readings taken there (each step's loss, the first
gradient as the momentum holds it after one step, and each leaf's change
after the last check step) are compared, once the window has closed and the
program's state is freed, with a plain float32 DPSGD reference that starts
from the same weights and batches.
"""
from __future__ import annotations

import collections
import functools
import time

import numpy as np

from . import cell as C
from . import model as M
from . import reference as R


def train_key(seed: int):
    """The trainer's own PRNG key (it draws the gossip matchings)."""
    import jax
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, (seed >> 31) % (1 << 31))


def make_batches(seed: int, count: int, lead: tuple, seq: int, vocab: int,
                 sharding=None):
    """``count`` distinct batches of uniform random tokens, made on the
    device in one call; leaves (count, *lead, seq)."""
    import jax
    import jax.numpy as jnp

    def make(key):
        t = jax.random.randint(key, (count,) + lead + (seq + 1,), 0, vocab,
                               jnp.int32)
        return {"tokens": t[..., :-1], "labels": t[..., 1:],
                "mask": jnp.ones((count,) + lead + (seq,), jnp.float32)}
    kw = {} if sharding is None else {"out_shardings": sharding}
    pool = jax.jit(make, **kw)(M.seed_key(seed, 0xDA7A))
    return [jax.tree_util.tree_map(lambda a: a[i], pool)
            for i in range(count)]


def optimizer(tr: dict):
    from repro.optim import scale_by_schedule, sgd, warmup_linear_scale
    o = tr["optimizer"]
    return scale_by_schedule(sgd(o["lr"], momentum=o["momentum"]),
                             warmup_linear_scale(o["warmup_steps"],
                                                 o["warmup_scale"]))


def lr_at(tr: dict, step: int) -> float:
    o = tr["optimizer"]
    frac = min(step / max(o["warmup_steps"], 1), 1.0)
    return o["lr"] * (1.0 + (o["warmup_scale"] - 1.0) * frac)


# ---------------------------------------------------------------------------
# the two engines: (state, step, view, batches, tokens per step, stats)
# ---------------------------------------------------------------------------

def _stacked(cell, api, params0, seed, devices, count):
    from repro.core import AlgoConfig, MultiLearnerTrainer
    tr = cell.traffic
    n = tr["learners"]
    trainer = MultiLearnerTrainer(
        api.loss_fn, optimizer(tr),
        AlgoConfig(algo="dpsgd", topology=tr["topology"], n_learners=n))
    if not trainer.is_flat:
        raise RuntimeError("DPSGD did not resolve to the flat engine")
    state = trainer.init(train_key(seed), params0)
    batches = make_batches(seed, count, (n, tr["local_batch"]), tr["seq"],
                           api.cfg.vocab)

    def view(st):
        v = trainer.state_view(st)
        return v.params, v.opt_state["inner"]["mu"]
    info = {"gossip_rows": int(state.params.shape[1]), "learners": n,
            "neighbours": 1}
    return state, trainer.train_step, view, batches, info


def _launch(cell, api, params0, seed, devices, count):
    import jax
    import jax.numpy as jnp
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (jit_train_step, make_dpsgd_train_step,
                                    train_state_shardings, train_state_specs)
    tr = cell.traffic
    L = len(devices)
    mesh = make_mesh((L, 1), ("data", "model"), devices=devices)
    opt = optimizer(tr)
    fn = make_dpsgd_train_step(api, opt, mesh, topology=tr["topology"],
                               gossip_backend=tr["gossip_backend"])
    specs = train_state_specs(api, opt, mesh, algo="dpsgd")
    shds = shd.named_shardings(
        train_state_shardings(specs, mesh, algo="dpsgd"), mesh)
    key = train_key(seed)

    def init(p0):
        params = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (L,) + a.shape), p0)
        return type(specs)(params=params,
                           opt_state=jax.vmap(opt.init)(params),
                           step=jnp.zeros((), jnp.int32), rng=key)
    state = jax.jit(init, out_shardings=shds)(params0)
    GB = L * tr["local_batch"]
    bshd = shd.named_shardings(shd.batch_sharding(
        api.train_batch_spec(GB, tr["seq"]), mesh, stacked=False), mesh)
    pool_shd = jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, *s.spec)), bshd)
    batches = make_batches(seed, count, (GB,), tr["seq"], api.cfg.vocab,
                           sharding=pool_shd)
    batches = [jax.device_put(b, bshd) for b in batches]
    with jax.set_mesh(mesh):
        step = jit_train_step(fn, in_shardings=(shds, bshd),
                              out_shardings=(shds, None))

    def run_step(st, b):
        with jax.set_mesh(mesh):
            st, m = step(st, b)
        return st, _Loss(m["loss"])

    def view(st):
        return st.params, st.opt_state["inner"]["mu"]
    return state, run_step, view, batches, {"learners": L}


class _Loss:
    __slots__ = ("loss",)

    def __init__(self, loss):
        self.loss = loss


ENGINES = {"stacked": _stacked, "launch": _launch}


def per_learner(batch, n: int):
    """A feed batch as (n, rows, seq) per-learner leaves."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n, -1) + a.shape[-1:]), batch)


# ---------------------------------------------------------------------------
# readings and their comparison
# ---------------------------------------------------------------------------

def program_readings(cell, step, view, state, batches, params0):
    """Drive the first check steps; returns (state, readings)."""
    import jax
    k = cell.traffic["check_steps"]
    grad_norms = jax.jit(lambda st: R.leaf_norms(view(st)[1]))
    change = jax.jit(lambda st, p0: R.change_norms(view(st)[0], p0))
    losses, g1 = [], None
    for i in range(k):
        state, m = step(state, batches[i])
        losses.append(float(m.loss))
        if i == 0:
            g1 = jax.device_get(grad_norms(state))
    dw = jax.device_get(change(state, params0))
    return state, {"loss": losses, "grad": g1, "change": dw}


def reference_readings(cell, shard, params0, batches, seed, n, devices,
                       fault=None):
    """The same readings from the plain reference (optionally with a
    fault planted in it, where it stands in for the program).  Where the
    learners are spread over several chips, so are the reference's."""
    import jax
    import jax.numpy as jnp
    tr = cell.traffic
    key = train_key(seed)
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
    beta = tr["optimizer"]["momentum"]

    step = jax.jit(functools.partial(R.dpsgd_step, beta=beta, shard=shard,
                                     fault=fault, chips=len(devices)))

    losses, g1 = [], None
    for t in range(tr["check_steps"]):
        mix = R.mixing(tr, key, t, n)
        w, mu, lo, gn = step(w, mu, place(per_learner(batches[t], n)),
                             mix, lr_at(tr, t))
        losses.append(float(lo))
        if t == 0:
            g1 = jax.device_get(gn)
    dw = jax.device_get(jax.jit(R.change_norms)(w, params0))
    return {"loss": losses, "grad": g1, "change": dw}


def _worst_leaf(prog, ref, keep=None) -> float:
    """max over leaves and learners of |prog - ref| / max(ref, median ref
    over leaves), each leaf a (n,) vector of norms."""
    import jax
    p = jax.tree_util.tree_leaves(prog)
    r = jax.tree_util.tree_leaves(ref)
    k = jax.tree_util.tree_leaves(keep) if keep is not None else None
    r_all = np.stack([np.asarray(x) for x in r])            # (leaves, n)
    med = np.median(r_all, axis=0)
    worst = 0.0
    for i, (a, b) in enumerate(zip(p, r)):
        if k is not None and not np.all(k[i]):
            continue
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        worst = max(worst, float(np.max(np.abs(a - b)
                                        / np.maximum(b, med))))
    return worst


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers compared: loss, first gradient, change."""
    import jax
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    g = jax.tree_util.tree_leaves(ref["grad"])
    med = np.median(np.stack([np.asarray(x) for x in g]), axis=0)
    # leaves whose reference gradient is nought to rounding move by
    # round-off alone: left out of the change, by a rule on the reference
    keep = jax.tree_util.tree_map(lambda x: np.asarray(x) >= 1e-3 * med,
                                  ref["grad"])
    return {"loss_rel_gap": float(loss),
            "grad_norm_gap": _worst_leaf(prog["grad"], ref["grad"]),
            "change_norm_gap": _worst_leaf(prog["change"], ref["change"],
                                           keep)}


def checks(cell, gaps: dict) -> list:
    return [C.Check(k, v, cell.limits[k]) for k, v in gaps.items()]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell: C.Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, profiler=None, dtype=None) -> C.Run:
    import jax
    tr, cfg = cell.traffic, cell.config
    shard = M.shard_of(cfg)
    api = M.build(cfg, dtype)
    params0 = M.make_params(api, seed)
    state, step, view, batches, info = ENGINES[tr["engine"]](
        cell, api, params0, seed, devices, tr["batches"])
    n = info["learners"]
    state, prog = program_readings(cell, step, view, state, batches, params0)

    run = C.Run(cell=cell)
    tokens_per_step = n * tr["local_batch"] * tr["seq"]
    k = tr["check_steps"]
    inflight = collections.deque()
    steps = traced_from = 0
    trace_at = seconds - tr["trace_seconds"] if trace else float("inf")
    ann = jax.profiler.TraceAnnotation
    run.setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    while True:
        b = batches[(k + steps) % len(batches)]
        with ann("bench.step") if trace else _nothing():
            state, m = step(state, b)
        steps += 1
        inflight.append(m.loss)
        if len(inflight) > 2:
            with ann("bench.wait") if trace else _nothing():
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
    run.counters.update(steps=steps, tokens=steps * tokens_per_step,
                        tokens_per_step=tokens_per_step, shard=shard,
                        seq=tr["seq"], chips=len(devices), **info)
    run.device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices)
    del state, step, m, inflight
    ref = reference_readings(cell, shard, params0, batches, seed, n,
                             devices)
    run.extra["readings"] = {"program": _plain(prog), "reference": _plain(ref)}
    run.checks = checks(cell, compare(prog, ref))
    return run


class _nothing:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _plain(readings):
    import jax
    return {"loss": readings["loss"],
            "grad": [float(np.max(x)) for x in
                     jax.tree_util.tree_leaves(readings["grad"])],
            "change": [float(np.max(x)) for x in
                       jax.tree_util.tree_leaves(readings["change"])]}


def calibration(cell: C.Cell, seed: int, devices, dtype=None,
                fault=None) -> dict:
    """The numbers compared, for one seed, with no measured window: the
    program (``dtype`` overrides the configuration's: the control), or,
    with ``fault``, the reference with that fault planted against the
    reference."""
    tr = cell.traffic
    shard = M.shard_of(cell.config)
    api = M.build(cell.config, dtype)
    params0 = M.make_params(api, seed)
    state, step, view, batches, info = ENGINES[tr["engine"]](
        cell, api, params0, seed, devices, tr["check_steps"])
    n = info["learners"]
    if fault is None:
        state, prog = program_readings(cell, step, view, state, batches,
                                       params0)
        del state, step
    else:
        del state, step
        prog = reference_readings(cell, shard, params0, batches, seed, n,
                                  devices, fault=fault)
    ref = reference_readings(cell, shard, params0, batches, seed, n, devices)
    return compare(prog, ref)
