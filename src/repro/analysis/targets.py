"""The repo's audit targets: trainer, launch step, serve decode (DESIGN §16).

Each ``audit_*`` function builds the smallest real instance of one hot
path — the same fixtures the tier-1 tests train/serve for parity — then
runs every applicable jaxpr/donation/retrace rule against it and returns
the findings.  ``make lint`` runs all three through ``repro.analysis.run``
(which re-execs the jaxpr stage under 8 forced host devices so the pjit
target lowers like the launch tests do).

The point of auditing *live* objects rather than golden jaxpr dumps: a rule
here fails when the contract breaks, not when an unrelated refactor perturbs
the trace — the bounds come from the object itself (param store size, cache
pool bytes, schedule live-slot tables), never from frozen constants.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from .jaxpr_audit import (collective_count, donation_honored,
                          no_host_callback, no_param_concat, wire_dtype)
from .report import Finding
from .retrace import RetraceSentinel

__all__ = ["audit_trainer", "audit_launch", "audit_serve", "audit_all"]


def _bytes_of(tree) -> int:
    return int(sum(np.prod(x.shape, dtype=np.int64)
                   * np.dtype(x.dtype).itemsize
                   for x in jax.tree_util.tree_leaves(tree)))


def live_slots(schedule) -> int:
    """Distinct live neighbor permutations per round, summed over a compiled
    schedule's period — the exact collective budget (one permute per
    permutation: padded slots cost nothing, slots repeating a permutation
    share one, and leaf count does not multiply it; see
    tests/test_gossip_schedule_launch.py)."""
    idx = np.arange(schedule.n)
    return int(sum(
        len({tuple(schedule.partners[r, k]) for k in range(schedule.K)
             if not ((schedule.partners[r, k] == idx).all()
                     and not schedule.coefs[r][:, 1 + k].any())})
        for r in range(schedule.period)))


# ---------------------------------------------------------------------------
# vmap trainer (the research path)
# ---------------------------------------------------------------------------

def audit_trainer(n: int = 4, hidden: int = 32) -> List[Finding]:
    """Audit the flat fused vmap trainer: ``train_step`` and the
    ``run_steps`` scan driver carry no param-sized concat and no host
    callback; donation survives compilation; stepping, controller scale
    writes, and membership swaps never retrace."""
    from repro.core import AlgoConfig, Membership, MultiLearnerTrainer
    from repro.data import ShardedLoader, TemplateImages
    from repro.models import fcnet
    from repro.optim import scale_by_controller, set_controller_scale, sgd

    loader = ShardedLoader(TemplateImages(), n_learners=n, local_batch=16,
                           seed=0)
    params = fcnet.init_params(jax.random.PRNGKey(0), in_dim=784,
                               hidden=hidden)
    tr = MultiLearnerTrainer(
        fcnet.loss_fn, scale_by_controller(sgd(0.1, momentum=0.9)),
        AlgoConfig(algo="dpsgd", topology="ring", n_learners=n),
        engine="flat")
    st = tr.set_membership(tr.init(jax.random.PRNGKey(1), params),
                           Membership(n))
    batch = loader.batch(0)

    findings: List[Finding] = []
    bound = int(st.params.size) // 100
    for name, jxp in [
            ("trainer.train_step", jax.make_jaxpr(tr._train_step)(st, batch)),
            ("trainer.run_steps", jax.make_jaxpr(tr._run_steps)(
                st, jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs),
                    *(loader.batch(i) for i in range(2)))))]:
        findings += no_param_concat(jxp, bound=bound, target=name)
        findings += no_host_callback(jxp, target=name)

    compiled = tr.train_step.lower(st, batch).compile()
    findings += donation_honored(
        compiled, min_bytes=_bytes_of(st.params),
        target="trainer.train_step")

    # warm the cache, then swap every operand the design says is swappable
    st, _ = tr.train_step(st, loader.batch(0))
    with RetraceSentinel(tr.train_step, strict=False,
                         labels=["trainer.train_step"]) as sentinel:
        st, _ = tr.train_step(st, loader.batch(1))
        st = st._replace(opt_state=set_controller_scale(st.opt_state, 0.5))
        st, _ = tr.train_step(st, loader.batch(2))
        mem = Membership(n)
        mem.crash(n - 1)
        st = tr.set_membership(st, mem)           # same-shape table swap
        st, _ = tr.train_step(st, loader.batch(3))
    findings += sentinel.findings
    return findings


# ---------------------------------------------------------------------------
# pjit launch step (the scale path) — needs >= 8 devices
# ---------------------------------------------------------------------------

def audit_launch(arch: str = "transformer-100m") -> List[Finding]:
    """Audit the pjit dpsgd step on a (4, 2) mesh with the ppermute
    backend: collective count == the schedule's live slots (in the jaxpr
    AND the compiled HLO), the wire carries the params' wire dtype, no
    param-sized concat, no host callback, donation honored.

    Requires 8+ devices (``XLA_FLAGS=--xla_force_host_platform_device_count
    =8`` before the jax import); ``repro.analysis.run`` handles that."""
    if len(jax.devices()) < 8:
        raise RuntimeError(
            "audit_launch needs 8 devices — run through `python -m "
            "repro.analysis.run`, which forces the host device count")
    from repro.configs import get_config
    from repro.core.flatstate import flat_meta
    from repro.core.schedule import make_schedule
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import (jit_train_step, make_dpsgd_train_step,
                                    train_state_specs, train_state_shardings)

    mesh = make_test_mesh(4, 2)
    cfg = get_config(arch).smoke_config()
    from repro.models.model import build_model
    from repro.optim import sgd
    api = build_model(cfg)
    opt = sgd(0.1, momentum=0.9)
    L = mesh.shape["data"]
    specs = train_state_specs(api, opt, mesh, algo="dpsgd")
    shds = train_state_shardings(specs, mesh, algo="dpsgd")
    bspecs = api.train_batch_spec(8, 64)
    bshd = shd.batch_sharding(bspecs, mesh, stacked=False)
    step = make_dpsgd_train_step(api, opt, mesh, gossip_backend="ppermute")

    sched = make_schedule("ring", L)
    expected = live_slots(sched)
    one_learner = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    meta = flat_meta(one_learner)
    wire = meta.wire_dtype()

    findings: List[Finding] = []
    jxp = jax.make_jaxpr(step)(specs, bspecs)
    target = "launch.dpsgd_step[ppermute]"
    # the ppermute-flat backend concatenates ONE wire buffer per mix (at
    # most a learner's padded flat size; model sharding only shrinks it) —
    # that's the design.  1.5x that bound catches what must never appear:
    # a fleet-sized (L x) gather or a per-leaf pad-and-concat blowup.
    findings += no_param_concat(
        jxp, bound=3 * meta.padded // 2, target=target)
    findings += no_host_callback(jxp, target=target)
    findings += collective_count(jxp, expected=expected, target=target)
    findings += wire_dtype(jxp, expected=wire, target=target)

    with jax.set_mesh(mesh):
        compiled = jit_train_step(
            step, in_shardings=shd.named_shardings((shds, bshd), mesh),
            out_shardings=shd.named_shardings((shds, None), mesh),
        ).lower(specs, bspecs).compile()
    findings += collective_count(
        jxp, expected=expected, target=target + ".hlo",
        hlo_text=compiled.as_text())
    # the compiled module is the per-device SPMD program: its entry layout
    # (and so the aliased bytes) are the sharded shapes — scale the floor
    findings += donation_honored(
        compiled, min_bytes=_bytes_of(specs.params) // mesh.size,
        target=target)
    return findings


# ---------------------------------------------------------------------------
# serve decode step (the inference path)
# ---------------------------------------------------------------------------

def audit_serve(arch: str = "transformer-100m") -> List[Finding]:
    """Audit the paged decode step: no param-sized concat, no host
    callback, the K/V page pools are donated and aliased in place, and
    admissions / mid-flight joins / evictions never retrace."""
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.serve import ServeEngine

    cfg = get_config(arch).smoke_config()
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, n_slots=2, page_size=4, max_len=16)
    S = eng.n_slots
    operands = (params, eng.cache, jnp.zeros((S, 1), jnp.int32),
                jnp.zeros((S,), jnp.int32), jnp.asarray(eng.page_table),
                jnp.zeros((S,), bool))

    findings: List[Finding] = []
    target = f"serve.paged_decode_step[{arch}]"
    jxp = jax.make_jaxpr(api.paged_decode_step)(*operands)
    findings += no_param_concat(
        jxp, bound=max(1, _bytes_of(params) // 4 // 100), target=target)
    findings += no_host_callback(jxp, target=target)

    compiled = eng._step_fn.lower(*operands).compile()
    findings += donation_honored(
        compiled, min_bytes=_bytes_of(eng.cache), target=target)

    eng.warmup()
    with RetraceSentinel(eng._step_fn, strict=False,
                         labels=[target]) as sentinel:
        eng.submit([3, 1, 4], 4)
        for _ in range(3):
            eng.step()
        eng.submit([2, 7], 5)                 # mid-flight join
        eng.submit([5], 3)
        eng.run()
    findings += sentinel.findings
    return findings


def audit_all() -> List[Finding]:
    """Everything, in the order the contracts layer: research trainer,
    launch step, serve engine."""
    return audit_trainer() + audit_launch() + audit_serve()
