"""Production (pjit/shard_map) step builders.

Training (the paper's setting):
  * DPSGD  — params carry a leading learner axis sharded over the learner
    mesh axes; gradients are purely local (NO gradient collective — the
    paper's point); the only cross-learner traffic is the gossip mix.
       gossip_backend='einsum'   : paper-faithful reference (L x L mixing
                                   matrix; XLA emits an all-gather over the
                                   learner axis — O(L*P) traffic, DESIGN §2)
       gossip_backend='ppermute' : TPU-native ring gossip via shard_map +
                                   collective-permute — O(P) traffic
                                   (beyond-paper optimization, DESIGN §2)
  * AD-PSGD — straggler-tolerant pairwise gossip against a stale published
    weight buffer (staleness-bounded, explicit per-learner age/clock so the
    step is one jitted SPMD program); reuses mix_ppermute_pair — ONE
    collective-permute per step (DESIGN §3).
  * SSGD   — classic data parallelism: replicated params, psum'd grads
    (the baseline the paper compares against).

Serving: prefill (full forward) and decode (one token vs a rotating KV
cache) with the inference sharding rules from launch/sharding.py.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.dpsgd import (member_active_mask, mix_einsum, mix_ppermute_pair,
                          mix_ppermute_pair_flat, mix_ppermute_ring,
                          mix_ppermute_ring_flat, mix_ppermute_schedule,
                          mix_ppermute_schedule_flat, straggler_active_mask)
from ..core.schedule import make_schedule
from ..models.model import ModelAPI
from ..models.shard_hints import activation_batch_axes, vmapped_learner_axes
from ..obs import phase
from ..optim import Optimizer, apply_updates
from . import sharding as shd
from .mesh import learner_axes, n_learners


def jit_train_step(step_fn: Callable, **jit_kwargs) -> Callable:
    """jit a ``(state, batch) -> (state, metrics)`` step with state donation.

    All production step builders below are pure; donating the state argument
    lets XLA update the parameter / momentum / published-buffer arrays in
    place (no double-buffering of model-sized state).  A consumed state must
    not be reused — rebind it: ``state, m = step(state, batch)``.  Probe
    entry points (make_probe_step) deliberately do NOT donate: the state
    outlives a measurement pass.
    """
    return jax.jit(step_fn, donate_argnums=(0,), **jit_kwargs)


class PjitTrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray
    rng: jax.Array
    # -- adpsgd only (None otherwise) --------------------------------------
    buffer: Any = None    # last-published weights, stacked like params
    age: Any = None       # (L,) int32 ticks since each learner published
    # -- elastic membership operands (None for a static fleet; DESIGN §15) --
    active: Any = None       # (L,) bool — live fleet members
    slow_every: Any = None   # (L,) int32 — completes a step every k ticks
    drop_round: Any = None   # () bool — this tick's gossip round is dropped


def membership_operands(membership, drop_round: bool = False) -> dict:
    """The launch-layer half of ``MultiLearnerTrainer.set_membership``:
    device operands for a host-side ``core.membership.Membership``, to be
    swapped in between steps with ``state._replace(**...)`` — same shapes,
    so the compiled step is never invalidated."""
    return dict(active=jnp.asarray(membership.active),
                slow_every=jnp.asarray(membership.slow_every, jnp.int32),
                drop_round=jnp.asarray(bool(drop_round)))


# ---------------------------------------------------------------------------
# DPSGD
# ---------------------------------------------------------------------------

def make_dpsgd_train_step(api: ModelAPI, optimizer: Optimizer, mesh,
                          topology: str = "random_pair",
                          gossip_backend: str = "einsum",
                          gossip_fuse: str = "flat",
                          gossip_rounds: int = 1) -> Callable:
    """``topology`` is compiled through core.schedule.make_schedule, so the
    SPMD path runs the same GossipSchedule tables as the research trainer
    (DESIGN §12).  ``gossip_backend='ppermute'``: deterministic schedules
    (ring/torus/full/hierarchical/exp/one_peer_exp) derive their
    collective-permute sequence straight from the schedule — K permutes per
    round, parity-pinned against the einsum step matrix; random matchings
    cannot be a compiled collective schedule, so they substitute the ring
    (the pre-schedule behavior — use the einsum backend for true random
    pairing under pjit).  The step's metrics hold the mean loss and the
    forward's counters (``api.loss_and_stats``), mean over learners.
    ``gossip_fuse``: 'flat' permutes each device's
    LOCAL parameter shard as one lane-aligned (T_local, 128) buffer —
    collectives per step independent of leaf count (DESIGN §11); 'leaf' is
    the per-leaf reference collective schedule."""
    L = n_learners(mesh)
    l_axes = learner_axes(mesh)
    assert gossip_fuse in ("flat", "leaf"), gossip_fuse
    sched = make_schedule(topology, L, rounds=gossip_rounds)
    if (getattr(optimizer, "wants_mixed", False)
            and getattr(optimizer, "static_mixing_only", False)
            and sched is not None and sched.time_varying):
        raise ValueError(
            "optimizer assumes a static mixing matrix but "
            f"topology='{topology}' compiles to a time-varying "
            "GossipSchedule (see optim/decentlam.py)")

    def gossip(params, key, step):
        if sched is None:                      # solo: no mixing
            return params
        if gossip_backend == "einsum":
            return mix_einsum(params, sched.step_matrix(key, step))
        # schedule-driven gossip inside shard_map (only the learner axes
        # are mapped)
        specs = shd.params_sharding(params, mesh, stacked=True)

        def local(p):
            if sched.randomized:               # ring stand-in (docstring)
                return (mix_ppermute_ring_flat(p, l_axes)
                        if gossip_fuse == "flat"
                        else mix_ppermute_ring(p, l_axes))
            if gossip_fuse == "flat":
                return mix_ppermute_schedule_flat(p, l_axes, step, sched)
            return mix_ppermute_schedule(p, l_axes, step, sched)

        # the flat view concatenates leaves with different model-axis
        # replication into one buffer, which defeats shard_map's static
        # replication inference — the mix itself never touches the model
        # axes (every model shard runs the identical elementwise program),
        # so the check is soundly skipped (DESIGN §11)
        return jax.shard_map(local, mesh=mesh, in_specs=(specs,),
                             out_specs=specs,
                             check_vma=gossip_fuse != "flat")(params)

    def train_step(state: PjitTrainState, batch):
        # batch leaves: (GB, ...) -> (L, B_local, ...)
        stacked_batch = jax.tree_util.tree_map(
            lambda x: x.reshape((L, x.shape[0] // L) + x.shape[1:]), batch)
        # spmd_axis_name: in-model activation constraints (residual_hint)
        # see the learner dim sharded over the learner mesh axes; the
        # per-learner batch itself is unsharded -> batch axes context ()
        with activation_batch_axes(()), vmapped_learner_axes(l_axes), \
                phase("fwd_bwd"):
            (losses, stats), grads = jax.vmap(
                jax.value_and_grad(api.loss_and_stats, has_aux=True),
                in_axes=(0, 0), spmd_axis_name=l_axes)(
                state.params, stacked_batch)
        key = jax.random.fold_in(state.rng, state.step)
        with phase("gossip"):                          # paper Eq. 2 ordering
            mixed = gossip(state.params, key, state.step)
        with phase("update"):
            if getattr(optimizer, "wants_mixed", False):  # decentlam
                updates, opt_state = jax.vmap(optimizer.update)(
                    grads, state.opt_state, state.params, mixed)
            else:
                updates, opt_state = jax.vmap(optimizer.update)(
                    grads, state.opt_state, state.params)
            new_params = apply_updates(mixed, updates)
        with phase("stats"):
            metrics = {"loss": jnp.mean(losses)}
            # the forward's counters (MoE: held assignments), mean over
            # learners; none for a dense model
            metrics.update({k: jnp.mean(v.astype(jnp.float32))
                            for k, v in stats.items()})
        return PjitTrainState(new_params, opt_state, state.step + 1,
                              state.rng), metrics

    return train_step


# ---------------------------------------------------------------------------
# AD-PSGD: straggler-tolerant pairwise gossip against a stale buffer
# ---------------------------------------------------------------------------

def make_adpsgd_train_step(api: ModelAPI, optimizer: Optimizer, mesh, *,
                           max_staleness: int = 4, slow_learner: int = -1,
                           slow_factor: int = 1,
                           gossip_fuse: str = "flat",
                           elastic: bool = False) -> Callable:
    """One asynchronous-gossip tick as an SPMD program (DESIGN §3).

    Same simulation contract as the vmap research path: each learner mixes
    its live weights with ONE partner's last-*published* weights (hypercube
    ppermute schedule, one collective-permute), the partner's buffer may lag
    by up to ``max_staleness`` ticks, and an injected straggler only
    completes (and publishes) every ``slow_factor`` ticks.  With
    ``max_staleness=0`` and no straggler this is synchronous pairwise DPSGD.

    ``elastic=True`` (DESIGN §15): the state carries membership OPERANDS
    (``active``/``slow_every``/``drop_round`` — see
    :func:`membership_operands`); liveness and per-learner tick divisors
    replace the single static straggler, a hypercube pair mixes only when
    both endpoints are live (the gate ppermutes alongside the buffer), a
    dead learner's rows stay quarantined bitwise, and the loss averages
    the active learners only.  A membership change is a same-shape operand
    swap — no retrace.
    """
    L = n_learners(mesh)
    l_axes = learner_axes(mesh)
    assert gossip_fuse in ("flat", "leaf"), gossip_fuse
    if (getattr(optimizer, "wants_mixed", False)
            and getattr(optimizer, "static_mixing_only", False)):
        raise ValueError("optimizer assumes a static mixing matrix but "
                         "AD-PSGD gossips over a time-varying pairwise "
                         "schedule (see optim/decentlam.py)")
    if elastic and getattr(optimizer, "wants_mixed", False):
        raise ValueError("a mixing-matrix-corrected optimizer (decentlam) "
                         "assumes a static fleet (see core/trainer.py)")

    def gossip(params, buffer, age, step, act, drop):
        specs = shd.params_sharding(params, mesh, stacked=True)
        age_spec = P(tuple(l_axes))

        def local(p, buf, a, *rest):
            fresh = a[0] >= max_staleness          # forced publish (bound)
            remote = jax.tree_util.tree_map(
                lambda w, b: jnp.where(fresh, w, b), p, buf)
            gate = None
            if rest:    # elastic: liveness x not-dropped gates the mix
                gate = (rest[0][0].astype(jnp.float32)
                        * (1.0 - rest[1].astype(jnp.float32)))
            if gossip_fuse == "flat":
                return mix_ppermute_pair_flat(p, l_axes, step, remote=remote,
                                              gate=gate)
            return mix_ppermute_pair(p, l_axes, step, remote=remote,
                                     gate=gate)

        in_specs = (specs, specs, age_spec)
        args = (params, buffer, age)
        if act is not None:
            in_specs += (age_spec, P())
            args += (act, drop)
        # check_vma: see make_dpsgd_train_step — the flat view breaks static
        # replication inference, not actual replication
        return jax.shard_map(local, mesh=mesh,
                             in_specs=in_specs,
                             out_specs=specs,
                             check_vma=gossip_fuse != "flat")(*args)

    def train_step(state: PjitTrainState, batch):
        stacked_batch = jax.tree_util.tree_map(
            lambda x: x.reshape((L, x.shape[0] // L) + x.shape[1:]), batch)
        with activation_batch_axes(()), vmapped_learner_axes(l_axes), \
                phase("fwd_bwd"):
            losses, grads = jax.vmap(jax.value_and_grad(api.loss_fn),
                                     in_axes=(0, 0),
                                     spmd_axis_name=l_axes)(
                state.params, stacked_batch)
        if elastic:
            live = state.active
            active = member_active_mask(state.step, live, state.slow_every)
            fresh = (state.age >= max_staleness) & live
            with phase("gossip"):
                mixed = gossip(state.params, state.buffer, state.age,
                               state.step, live, state.drop_round)
        else:
            active = straggler_active_mask(state.step, L, slow_learner,
                                           slow_factor)
            fresh = state.age >= max_staleness
            with phase("gossip"):
                mixed = gossip(state.params, state.buffer, state.age,
                               state.step, None, None)

        def sel(mask):
            return lambda a, b: jnp.where(
                mask.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
        with phase("update"):
            if getattr(optimizer, "wants_mixed", False):  # decentlam
                updates, opt_state_new = jax.vmap(optimizer.update)(
                    grads, state.opt_state, state.params, mixed)
            else:
                updates, opt_state_new = jax.vmap(optimizer.update)(
                    grads, state.opt_state, state.params)
            stepped = apply_updates(mixed, updates)
            new_params = jax.tree_util.tree_map(sel(active), stepped,
                                                state.params)
            opt_state = jax.tree_util.tree_map(sel(active), opt_state_new,
                                               state.opt_state)
        # active learners publish their new weights; forced-fresh inactive
        # ones re-publish their (unchanged) in-progress weights — both read
        # off new_params
        with phase("gossip"):
            buffer = jax.tree_util.tree_map(sel(active | fresh), new_params,
                                            state.buffer)
        age = jnp.where(active | fresh, 0, state.age + 1)
        with phase("stats"):
            if elastic:
                nact = jnp.maximum(jnp.sum(state.active),
                                   1).astype(jnp.float32)
                loss = jnp.sum(jnp.where(state.active, losses, 0.0)) / nact
                metrics = {"loss": loss, "n_active": nact,
                           "staleness_max": jnp.max(jnp.where(
                               fresh | ~state.active, 0, state.age))}
            else:
                metrics = {"loss": jnp.mean(losses),
                           "staleness_max": jnp.max(jnp.where(fresh, 0,
                                                              state.age))}
        return PjitTrainState(new_params, opt_state, state.step + 1,
                              state.rng, buffer=buffer, age=age,
                              active=state.active,
                              slow_every=state.slow_every,
                              drop_round=state.drop_round), metrics

    return train_step


# ---------------------------------------------------------------------------
# SSGD baseline
# ---------------------------------------------------------------------------

def make_ssgd_train_step(api: ModelAPI, optimizer: Optimizer, mesh) -> Callable:
    def train_step(state: PjitTrainState, batch):
        # the gradient all-reduce is XLA's, inserted inside the backward
        # pass by the sharding: it has no gossip scope of its own
        with phase("fwd_bwd"):
            loss, grads = jax.value_and_grad(api.loss_fn)(state.params,
                                                          batch)
        with phase("update"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            new_params = apply_updates(state.params, updates)
        return PjitTrainState(new_params, opt_state, state.step + 1,
                              state.rng), {"loss": loss}

    return train_step


# ---------------------------------------------------------------------------
# landscape probe (sharded entry point, DESIGN §10)
# ---------------------------------------------------------------------------

def make_probe_step(api: ModelAPI, mesh, *, alpha: float, stacked: bool,
                    lanczos_iters: int = 8,
                    hutchinson_samples: int = 4) -> Callable:
    """(params, batch, key) -> landscape.ProbeResult under the mesh.

    The HVPs are plain jvp-of-grad through ``api.loss_fn``, so under jit
    they inherit exactly the step's parameter/activation shardings — no
    extra sharding rules.  ``stacked`` mirrors the train-step layout:
    True for DPSGD/AD-PSGD ((L, ...) params — covariance terms measured
    across learners), False for the SSGD path (single replica — the
    spread terms are 0 and the probe feeds the AutoLR controller with
    sharpness + gradient noise scale only).

    One SPMD caveat: the Lanczos basis lives on the flat (T, 128) view,
    which XLA must regather from model-sharded params; the reorth loop
    therefore runs through the jnp oracle (``reorth='ref'``) so the probe
    stays a legal single program on any mesh.  At probe cadence (every
    10-100 steps) the regather is noise; the fused Pallas path is for the
    research trainer and single-device probes.
    """
    L = n_learners(mesh)

    def probe(params, batch, key):
        stacked_batch = jax.tree_util.tree_map(
            lambda x: x.reshape((L, x.shape[0] // L) + x.shape[1:]), batch)
        from ..landscape import probe_landscape
        return probe_landscape(api.loss_fn, params, stacked_batch, key,
                               alpha=alpha, lanczos_iters=lanczos_iters,
                               hutchinson_samples=hutchinson_samples,
                               stacked=stacked, reorth="ref")

    return probe


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def make_prefill_step(api: ModelAPI) -> Callable:
    def prefill(params, batch):
        return api.apply(params, batch)
    return prefill


def make_decode_step(api: ModelAPI) -> Callable:
    def decode(params, cache, tokens, pos):
        return api.decode_step(params, cache, tokens, pos)
    return decode


# ---------------------------------------------------------------------------
# spec builders (shapes only — nothing allocated; dryrun + tests share these)
# ---------------------------------------------------------------------------

def stacked_param_specs(api: ModelAPI, L: int):
    single = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((L,) + s.shape, s.dtype), single)


def train_state_specs(api: ModelAPI, optimizer: Optimizer, mesh, *,
                      algo: str, elastic: bool = False):
    L = n_learners(mesh)
    buffer = age = None
    active = slow_every = drop_round = None
    if algo in ("dpsgd", "adpsgd"):
        p = stacked_param_specs(api, L)
        o = jax.eval_shape(lambda q: jax.vmap(optimizer.init)(q), p)
        if algo == "adpsgd":
            buffer = p
            age = jax.ShapeDtypeStruct((L,), jnp.int32)
        if elastic:
            active = jax.ShapeDtypeStruct((L,), jnp.bool_)
            slow_every = jax.ShapeDtypeStruct((L,), jnp.int32)
            drop_round = jax.ShapeDtypeStruct((), jnp.bool_)
    else:
        p = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        o = jax.eval_shape(optimizer.init, p)
    return PjitTrainState(
        params=p, opt_state=o,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32),
        buffer=buffer, age=age, active=active, slow_every=slow_every,
        drop_round=drop_round)


def train_state_shardings(state_specs: PjitTrainState, mesh, *, algo: str):
    stacked = algo in ("dpsgd", "adpsgd")
    p = shd.params_sharding(state_specs.params, mesh, stacked=stacked)
    # optimizer state mirrors params (momentum etc.), scalars replicated
    def opt_spec(path, leaf):
        if leaf.ndim <= 1:
            return P(*([None] * leaf.ndim))
        return shd.leaf_spec(path, leaf, mesh.shape["model"],
                             learner_axes=(tuple(
                                 a for a in mesh.axis_names if a != "model")
                                 if stacked else None))
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_specs.opt_state)
    o = jax.tree_util.tree_unflatten(
        treedef, [opt_spec(pa, l) for pa, l in flat])
    buffer = age = None
    active = slow_every = drop_round = None
    if algo == "adpsgd":
        buffer = p
        age = P(learner_axes(mesh))
    if state_specs.active is not None:   # elastic membership operands
        active = P(learner_axes(mesh))
        slow_every = P(learner_axes(mesh))
        drop_round = P()
    return PjitTrainState(params=p, opt_state=o, step=P(), rng=P(),
                          buffer=buffer, age=age, active=active,
                          slow_every=slow_every, drop_round=drop_round)
