"""MultiLearnerTrainer — research driver for SSGD / SSGD* / DPSGD / AD-PSGD.

Semantics (paper Sec. 2 + Lian et al. 2018 for the async variant):
  SSGD   : g_j = grad L^{mu_j}(w_a);          w_a <- w_a + opt(mean_j g_j)
  SSGD*  : g_j = grad L^{mu_j}(w_a + delta_j) with delta_j ~ N(0, sigma0^2 I)
  DPSGD  : g_j = grad L^{mu_j}(w_j);          w_j <- mix(w)_j + opt_j(g_j)
  AD-PSGD: like DPSGD with pairwise gossip, but the partner's contribution is
           its last *published* weights (stale by up to ``max_staleness``
           ticks), and an injected straggler only completes a step every
           ``slow_factor`` ticks.  Modeled with explicit per-learner
           buffer/age/clock state so the step stays one jitted function.

Two interchangeable engines (DESIGN §11):

  * ``engine='flat'`` (the default for DPSGD/AD-PSGD) keeps the stacked
    parameters as ONE persistent (n, T, 128) f32 buffer (core/flatstate.py),
    flattened exactly once at init.  Gradients are taken with respect to the
    flat buffer through cheap per-leaf unflatten views, the gossip + SGD
    update runs as the batched Pallas kernel (kernels/ops.flat_gossip_update,
    jnp ``ref`` oracle selectable), and no parameter-sized concatenate ever
    appears in the traced step (guard-tested).
  * ``engine='pytree'`` is the paper-faithful reference: stacked pytrees and
    unfused tree_map updates.  The flat engine is pinned against it by
    parity tests (tests/test_flat_engine.py).

``train_step`` and the ``run_steps`` lax.scan driver donate the state
argument (the old buffers are reused in place — do not touch a state after
passing it in).  Probe/diagnostic jits deliberately do NOT donate: the state
outlives a measurement pass by construction.

This module is the CPU-scale research path (vmap over learners on one
device).  The production pjit/shard_map path lives in repro/launch/train.py
and reuses the same pure update functions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs import phase
from ..optim import Optimizer, apply_updates
from . import schedule as gsched
from . import topology as topo
from .diagnostics import DiagStats, compute_diagnostics
from .dpsgd import (AlgoConfig, mean_broadcast, member_active_mask,
                    mix_einsum, mix_pair_gather, pair_partners,
                    perturb_weights, straggler_active_mask)
from .flatstate import LANE, FlatMeta, flat_meta
from .membership import Membership, MemberState
from .util import (learner_mean, learner_var, masked_learner_mean,
                   masked_learner_var)


class TrainState(NamedTuple):
    params: Any           # stacked: leaves (n, ...) — or (n, T, 128) flat
    opt_state: Any        # stacked per-learner
    step: jnp.ndarray
    rng: jax.Array
    # -- adpsgd only (None otherwise) --------------------------------------
    buffer: Any = None    # last-published weights, stacked like params
    age: Any = None       # (n,) int32 ticks since each learner published
    clock: Any = None     # (n,) int32 completed local steps per learner
    # -- elastic membership (None = legacy fixed fleet; DESIGN §15) --------
    members: Any = None   # MemberState: masks/tables as jit OPERANDS


class StepMetrics(NamedTuple):
    loss: jnp.ndarray          # mean per-learner minibatch loss (active only)
    grad_norm: jnp.ndarray     # ||g_a|| (consensus gradient, active only)
    sigma_w_sq: jnp.ndarray    # weight variance across (active) learners
    staleness_mean: jnp.ndarray  # mean buffer age seen at gossip (adpsgd)
    staleness_max: jnp.ndarray   # max buffer age seen at gossip (adpsgd)
    # -- elastic/AdaScale statistics (zero-filled on the ssgd paths) -------
    n_active: jnp.ndarray = 0.0      # live learner count this tick
    grad_sq_mean: jnp.ndarray = 0.0  # mean_i ||g_i||^2 over active learners


def _select(mask, new, old):
    """Per-learner select: leaf[j] = new[j] if mask[j] else old[j]."""
    def _sel(a, b):
        m = mask.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree_util.tree_map(_sel, new, old)


def _per_learner_grad_sq(grads):
    """(n,) f32: ||g_i||^2 per learner (the AdaScale gain statistic)."""
    return sum(jnp.sum(jnp.square(g.astype(jnp.float32)),
                       axis=tuple(range(1, g.ndim)))
               for g in jax.tree_util.tree_leaves(grads))


@dataclasses.dataclass
class ProbeHook:
    """One scheduled measurement pass (the probe seam, DESIGN §10).

    ``schedule`` is anything with ``due(step) -> bool`` (typically
    landscape.ProbeSchedule); ``fn(state, batch) -> result`` is the
    measurement (trainer.diagnostics, a landscape probe, ...);
    ``on_result(state, result) -> state`` optionally closes a control loop
    (e.g. AutoLR writing its multiplier into the optimizer state).
    """
    name: str
    schedule: Any
    fn: Callable
    on_result: Optional[Callable] = None


@dataclasses.dataclass
class MultiLearnerTrainer:
    loss_fn: Callable          # (params, batch) -> scalar, one learner's minibatch
    optimizer: Optimizer
    algo: AlgoConfig
    alpha_for_diag: float = 1.0   # alpha used in the alpha_e instrument
    hooks: list = dataclasses.field(default_factory=list)  # [ProbeHook]
    engine: str = "auto"       # auto | flat | pytree (DESIGN §11)
    kernel_backend: str = "auto"   # auto | pallas | ref (flat-engine dispatch)

    def __post_init__(self):
        # compile the topology into its GossipSchedule (DESIGN §12): the
        # per-round static-K partner/coef tables every mixing path — fused
        # kernel, einsum fallback, SPMD ppermute — derives from.  None for
        # 'solo' (identity mixing); unknown topologies raise here.
        self._schedule = gsched.make_schedule(
            self.algo.topology, self.algo.n_learners,
            rounds=self.algo.gossip_rounds)
        if (getattr(self.optimizer, "wants_mixed", False)
                and self.algo.gossip_order != "mix_then_descend"):
            raise ValueError("decentlam-style optimizers need the gossip "
                             "average: use gossip_order='mix_then_descend'")
        if (getattr(self.optimizer, "wants_mixed", False)
                and getattr(self.optimizer, "static_mixing_only", False)
                and self._schedule is not None
                and self._schedule.time_varying):
            raise ValueError(
                "this optimizer's correction assumes a STATIC mixing "
                f"matrix, but topology='{self.algo.topology}' compiles to a "
                "time-varying GossipSchedule — the exact DecentLaM drift "
                "diverges under switching matchings (see optim/decentlam.py)."
                " Use drift_scale=1-momentum, a static topology, or "
                "unsafe_switching=True to demonstrate the divergence")
        assert self.engine in ("auto", "flat", "pytree"), self.engine
        assert self.kernel_backend in ("auto", "pallas", "ref"), \
            self.kernel_backend
        layout_sensitive = getattr(self.optimizer, "layout_sensitive", False)
        if self.engine == "auto":
            # the flat fused engine is the default hot path for the
            # decentralized algorithms; SSGD/SSGD* keep the reference layout
            # (no gossip to fuse; SSGD* draws per-leaf weight noise), and so
            # does a layout-sensitive optimizer (lamb's layer-wise trust
            # ratio would silently collapse on the single flat leaf)
            self._flat = (self.algo.algo in ("dpsgd", "adpsgd")
                          and not layout_sensitive)
        else:
            if self.engine == "flat" and self.algo.algo == "ssgd_star":
                raise ValueError("ssgd_star draws per-leaf weight noise; "
                                 "use engine='pytree'")
            if self.engine == "flat" and layout_sensitive:
                raise ValueError(
                    "this optimizer's update depends on the per-leaf "
                    "structure (layout_sensitive=True, e.g. lamb's "
                    "layer-wise trust ratio) — the flat engine would "
                    "silently change its semantics; use engine='pytree'")
            self._flat = self.engine == "flat"
        # fused kernel path: plain (momentum-)SGD on ANY compiled gossip
        # schedule — every topology make_schedule covers dispatches the
        # batched Pallas/oracle kernel, multi-round schedules running their
        # leading rounds as mixing-only kernel passes (DESIGN §12).  SSGD
        # has no gossip to fuse (generic flat step); 'solo' has no schedule;
        # a wants_mixed optimizer (decentlam) needs the unfused update.
        f = getattr(self.optimizer, "fused", None)
        self._fused = None
        if (self._flat and f is not None
                and self.algo.algo in ("dpsgd", "adpsgd")
                and not getattr(self.optimizer, "wants_mixed", False)
                and self.algo.gossip_order == "mix_then_descend"
                and self._schedule is not None):
            self._fused = f
        self._meta: Optional[FlatMeta] = None   # set at init()
        # jit once per trainer instance (self is not hashable -> close over
        # it).  The step and the scan driver donate the state: the flat
        # buffers are updated in place, so a consumed state must not be
        # reused (tests pin this).
        self.train_step = jax.jit(self._train_step, donate_argnums=(0,))
        self._run_steps_jit = jax.jit(self._run_steps, donate_argnums=(0,))
        self.diagnostics = jax.jit(self._diagnostics)
        self.eval_loss = jax.jit(self._eval_loss)

    # -- engine helpers -------------------------------------------------------
    @property
    def is_flat(self) -> bool:
        return self._flat

    def _params_any(self, params):
        """Accept either layout: unflatten a flat buffer, pass trees through."""
        if self._flat and isinstance(params, jax.Array):
            return self._meta.unflatten(params)
        return params

    def params_tree(self, state_or_params):
        """The stacked parameter pytree view of a state (cheap slices)."""
        p = (state_or_params.params if isinstance(state_or_params, TrainState)
             else state_or_params)
        return self._params_any(p)

    def state_view(self, state: TrainState) -> TrainState:
        """Pytree-layout view of a (possibly flat) state.

        Parameters/buffer and any (n, T, 128) optimizer leaves (momentum)
        come back as stacked pytrees; scalar opt leaves (controller scale,
        schedule step) pass through.  Probe hooks receive this view so
        measurement code is engine-agnostic.
        """
        if not self._flat:
            return state
        meta = self._meta

        def leafview(x):
            if (isinstance(x, jax.Array) and x.ndim >= 2
                    and x.shape[-2:] == (meta.rows, LANE)):
                return meta.unflatten(x)
            return x

        return state._replace(
            params=meta.unflatten(state.params),
            buffer=(None if state.buffer is None
                    else meta.unflatten(state.buffer)),
            opt_state=jax.tree_util.tree_map(leafview, state.opt_state))

    def state_from_view(self, view: TrainState) -> TrainState:
        """Inverse of ``state_view``: re-flatten a pytree-layout state.

        Lets checkpoints stay layout-stable across engines: save
        ``state_view(state)``, restore it with the view as template, and
        feed the result back through here.  Any subtree matching the
        parameter structure (params, buffer, momentum leaves the view
        expanded) is flattened back into the (n, T, 128) store; everything
        else passes through.
        """
        if not self._flat:
            return view
        meta = self._meta

        def is_param_subtree(x):
            try:
                return jax.tree_util.tree_structure(x) == meta.treedef
            except Exception:
                return False

        def reflatten(x):
            return meta.flatten(x) if is_param_subtree(x) else x

        return view._replace(
            params=meta.flatten(view.params),
            buffer=(None if view.buffer is None
                    else meta.flatten(view.buffer)),
            opt_state=jax.tree_util.tree_map(reflatten, view.opt_state,
                                             is_leaf=is_param_subtree))

    def _loss_flat(self, w_flat, batch):
        return self.loss_fn(self._meta.unflatten(w_flat), batch)

    # -- init ---------------------------------------------------------------
    def init(self, key: jax.Array, params_single) -> TrainState:
        n = self.algo.n_learners
        stacked = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape).copy(), params_single)
        if self._flat:
            self._meta = flat_meta(params_single)
            stacked = self._meta.flatten(stacked)   # the ONE flatten
        opt_state = jax.vmap(self.optimizer.init)(stacked)
        buffer = age = clock = None
        if self.algo.algo == "adpsgd":
            buffer = jax.tree_util.tree_map(jnp.copy, stacked)
            age = jnp.zeros((n,), jnp.int32)
            clock = jnp.zeros((n,), jnp.int32)
        return TrainState(stacked, opt_state, jnp.zeros((), jnp.int32), key,
                          buffer=buffer, age=age, clock=clock)

    @phase("fwd_bwd")
    def _fwd_bwd(self, params, stacked_batch, in_axes=0):
        """Per-learner (losses, gradients), on the flat store where the
        engine keeps one."""
        loss_fn = self._loss_flat if self._flat else self.loss_fn
        return jax.vmap(jax.value_and_grad(loss_fn),
                        in_axes=in_axes)(params, stacked_batch)

    # -- optimizer call (decentlam-aware) -------------------------------------
    @phase("update")
    def _opt_update(self, grads, opt_state, params, mixed):
        if getattr(self.optimizer, "wants_mixed", False):
            return jax.vmap(self.optimizer.update)(grads, opt_state, params,
                                                   mixed)
        return jax.vmap(self.optimizer.update)(grads, opt_state, params)

    @phase("update")
    def _apply(self, params, updates):
        return apply_updates(params, updates)

    # -- flat-engine pieces ---------------------------------------------------
    @phase("gossip")
    def _fused_step(self, w, remote, grads, opt_state, partners, coefs,
                    active=None, buffer=None, nbr_fresh=None, publish=None,
                    weight_decay=None):
        """Dispatch the batched gossip+SGD kernel and thread the opt state.

        ``active`` (adpsgd): the kernel applies the straggler select to the
        weights and momentum in the same pass; the caller reverts the small
        non-flat opt leaves with ``_select_nonflat``.  ``buffer`` +
        ``nbr_fresh``/``publish`` switch on the AD-PSGD publish mode: the
        stale-remote select and the published-buffer rewrite also happen
        inside the kernel, so the tick makes one pass over the parameters.
        ``weight_decay`` overrides the optimizer's static recipe (the
        multi-round path passes 0 after folding the decay of the PRE-mix
        weights into the gradients — the kernel only sees the mixed w).
        Returns (w_new, opt_state[, buffer_new]).  It runs under the
        ``gossip`` phase: the kernel mixes and updates in one pass.
        """
        from ..kernels import ops as kops
        f = self._fused
        n = w.shape[0]
        scale = jnp.broadcast_to(
            jnp.asarray(f.scale(opt_state), jnp.float32), (n,))
        act = (jnp.ones((n,), jnp.float32) if active is None
               else active.astype(jnp.float32))
        cols = [coefs, scale[:, None], act[:, None]]
        if buffer is not None:
            cols += [nbr_fresh.astype(jnp.float32)[:, None],
                     publish.astype(jnp.float32)[:, None]]
        coefs = jnp.concatenate(cols, axis=1)
        mu = f.read_mu(opt_state)
        wd = f.weight_decay if weight_decay is None else weight_decay
        out = kops.flat_gossip_update(
            w, remote, grads, mu, partners, coefs, lr=f.lr, beta=f.beta,
            weight_decay=wd, buffer=buffer,
            backend=self.kernel_backend)
        w_new, mu_new = out[0], out[1]
        opt_state = f.bump(opt_state)
        if mu_new is not None:
            opt_state = f.write_mu(opt_state, mu_new)
        if buffer is not None:
            return w_new, opt_state, out[2]
        return w_new, opt_state

    @phase("gossip")
    def _select_nonflat(self, mask, new, old):
        """Per-learner select skipping (T, 128) leaves the kernel already
        selected in-pass (the momentum buffer)."""
        meta = self._meta

        def _sel(a, b):
            if (isinstance(a, jax.Array) and a.ndim >= 2
                    and a.shape[-2:] == (meta.rows, LANE)):
                return a
            m = mask.reshape((-1,) + (1,) * (a.ndim - 1))
            return jnp.where(m, a, b)
        return jax.tree_util.tree_map(_sel, new, old)

    @phase("gossip")
    def _mix_sched(self, stacked, key, step):
        """Schedule-driven gossip for the UNFUSED paths (pytree engine and
        the flat engine's generic-optimizer fallback) — works on stacked
        pytrees and on the raw (n, T, 128) buffer alike.

        Random matchings keep the O(P) gather form (round 0 draws from the
        raw step key, so sync pairwise DPSGD stays bitwise-stable vs PR 1);
        deterministic schedules multiply by the compiled per-step matrix
        (the whole multi-round product in ONE einsum).
        """
        s = self._schedule
        if s is None:                     # solo: identity mixing
            return stacked
        if s.randomized:
            out = stacked
            for j in range(s.rounds_per_step):
                kj = key if j == 0 else jax.random.fold_in(key, j)
                out = mix_pair_gather(
                    out, pair_partners(kj, self.algo.n_learners))
            return out
        return mix_einsum(stacked, s.step_matrix(key, step))

    # -- elastic membership (DESIGN §15) --------------------------------------
    def membership_state(self, membership: Membership, *,
                         drop_round: bool = False) -> MemberState:
        """Device-side membership bundle for THIS trainer's topology:
        deterministic DPSGD schedules embed their ``reschedule`` tables,
        randomized matchings and AD-PSGD draw from the mask in-step."""
        topo_name = None
        if (self.algo.algo == "dpsgd" and self._schedule is not None
                and not self._schedule.randomized):
            topo_name = self.algo.topology
        return membership.member_state(
            topo_name, gossip_rounds=self.algo.gossip_rounds,
            drop_round=drop_round)

    def set_membership(self, state: TrainState, membership: Membership, *,
                       drop_round: bool = False) -> TrainState:
        """Swap the current membership into a state (a table/operand swap:
        same-shape swaps reuse the compiled step — never a retrace)."""
        if self.algo.algo not in ("dpsgd", "adpsgd"):
            raise ValueError("elastic membership rides the decentralized "
                             f"paths, not {self.algo.algo}")
        if getattr(self.optimizer, "wants_mixed", False):
            raise ValueError(
                "a mixing-matrix-corrected optimizer (decentlam) assumes a "
                "static fleet — its drift term diverges when membership "
                "changes the realized matrix; use plain (momentum-)SGD")
        assert membership.capacity == self.algo.n_learners, \
            (membership.capacity, self.algo.n_learners)
        return state._replace(
            members=self.membership_state(membership,
                                          drop_round=drop_round))

    @phase("gossip")
    def _member_rounds(self, mem: MemberState, key, step):
        """The elastic analogue of ``schedule.step_rounds``: per-round
        (partners (K, n), coefs (n, K+1)) tables for this step, built from
        the ``members`` OPERANDS (mask / reschedule tables) so a membership
        change never invalidates a jit cache through a stale closure.
        A dropped gossip round degrades every row to the identity."""
        n = self.algo.n_learners
        if mem.partners is None:     # randomized: only-active matching
            rps = (max(1, self.algo.gossip_rounds)
                   if self.algo.topology == "random_matching" else 1)
            out = []
            for j in range(rps):
                kj = key if j == 0 else jax.random.fold_in(key, j)
                partner = topo.masked_pair_partners(kj, mem.active,
                                                    drop=mem.drop_round)
                solo = partner == jnp.arange(n)
                self_c = jnp.where(solo, 1.0, 0.5).astype(jnp.float32)
                out.append((partner[None].astype(jnp.int32),
                            jnp.stack([self_c, 1.0 - self_c], axis=1)))
            return out
        period, K = mem.partners.shape[0], mem.partners.shape[1]
        # rps is derivable from the OPERAND shape (rps == period for every
        # deterministic schedule except one_peer_exp's one-round-per-step),
        # so a resize that changes the table shape retraces with the right
        # round structure by construction
        rps = 1 if self.algo.topology == "one_peer_exp" else period
        id_c = jnp.concatenate(
            [jnp.ones((n, 1), jnp.float32), jnp.zeros((n, K), jnp.float32)],
            axis=1)
        out = []
        for j in range(rps):
            if rps % period == 0:
                p, c = mem.partners[j % period], mem.coefs[j % period]
            else:                    # time-varying (one_peer_exp)
                ridx = (step * rps + j) % period
                p, c = mem.partners[ridx], mem.coefs[ridx]
            out.append((p, jnp.where(mem.drop_round, id_c, c)))
        return out

    @phase("gossip")
    def _mix_member_rounds(self, stacked, rounds, active):
        """Unfused elastic mixing: apply ``_member_rounds`` tables to a
        stacked tree / flat buffer.  Randomized matchings keep the O(P)
        pair-gather form (solo rows — including every inactive one —
        bitwise untouched); deterministic rounds realize the round matrix.
        Quarantined rows are zeroed before the einsum and restored after,
        so even a non-finite parked row cannot bleed through the 0-weight
        columns (0 * NaN is NaN in an einsum, not in a where)."""
        out = stacked
        randomized = self._schedule is not None and self._schedule.randomized
        for partners, coefs in rounds:
            if randomized:      # drop/solo already folded into the partners
                out = mix_pair_gather(out, partners[0])
                continue
            n = partners.shape[1]
            m = jnp.zeros((n, n), jnp.float32)
            m = m.at[jnp.arange(n), jnp.arange(n)].add(coefs[:, 0])
            for k in range(partners.shape[0]):
                m = m.at[jnp.arange(n), partners[k]].add(coefs[:, 1 + k])
            safe = _select(active, out,
                           jax.tree_util.tree_map(jnp.zeros_like, out))
            out = _select(active, mix_einsum(safe, m), out)
        return out

    # -- one training step ----------------------------------------------------
    def _train_step(self, state: TrainState, stacked_batch):
        """stacked_batch leaves: (n, B_local, ...)."""
        if self._flat:
            return self._train_step_flat(state, stacked_batch)
        return self._train_step_tree(state, stacked_batch)

    def _train_step_tree(self, state: TrainState, stacked_batch):
        algo = self.algo
        key = jax.random.fold_in(state.rng, state.step)
        k_mix, k_noise = jax.random.split(key)

        zero = jnp.zeros((), jnp.float32)
        stale_mean, stale_max = zero, zero
        buffer, age, clock = state.buffer, state.age, state.clock

        if algo.algo == "ssgd":
            with phase("gossip"):
                w_a = learner_mean(state.params)
            losses, grads = self._fwd_bwd(w_a, stacked_batch,
                                          in_axes=(None, 0))
            with phase("gossip"):
                g_mean = learner_mean(grads)
                # identical update on every learner keeps copies in sync
                g_stacked = jax.tree_util.tree_map(
                    lambda g: jnp.broadcast_to(g[None], (algo.n_learners,)
                                               + g.shape), g_mean)
            updates, opt_state = self._opt_update(
                g_stacked, state.opt_state, state.params, state.params)
            new_params = self._apply(state.params, updates)
            with phase("gossip"):
                new_params = mean_broadcast(new_params)

        elif algo.algo == "ssgd_star":
            with phase("gossip"):
                w_a = learner_mean(state.params)
            noisy = perturb_weights(
                k_noise,
                jax.tree_util.tree_map(
                    lambda p: jnp.broadcast_to(p[None],
                                               (algo.n_learners,) + p.shape), w_a),
                algo.noise_std)
            losses, grads = self._fwd_bwd(noisy, stacked_batch)
            with phase("gossip"):
                g_mean = learner_mean(grads)
                g_stacked = jax.tree_util.tree_map(
                    lambda g: jnp.broadcast_to(g[None], (algo.n_learners,)
                                               + g.shape), g_mean)
            updates, opt_state = self._opt_update(
                g_stacked, state.opt_state, state.params, state.params)
            new_params = self._apply(state.params, updates)
            with phase("gossip"):
                new_params = mean_broadcast(new_params)

        elif algo.algo == "dpsgd":
            # gradients at LOCAL weights (the whole point of the paper)
            losses, grads = self._fwd_bwd(state.params, stacked_batch)
            mem = state.members
            if mem is not None:       # elastic fleet (DESIGN §15)
                act = mem.active
                rounds = ([] if self._schedule is None
                          else self._member_rounds(mem, k_mix, state.step))
                if algo.gossip_order == "mix_then_descend":
                    mixed = self._mix_member_rounds(state.params, rounds, act)
                    updates, opt_new = self._opt_update(
                        grads, state.opt_state, state.params, mixed)
                    stepped = self._apply(mixed, updates)
                else:
                    updates, opt_new = self._opt_update(
                        grads, state.opt_state, state.params, state.params)
                    stepped = self._mix_member_rounds(
                        self._apply(state.params, updates), rounds, act)
                # dead learners' quarantined rows stay bitwise frozen
                with phase("update"):
                    new_params = _select(act, stepped, state.params)
                    opt_state = _select(act, opt_new, state.opt_state)
            elif algo.gossip_order == "mix_then_descend":   # paper Eq. 2
                # _mix_sched keeps the gather form for random matchings
                # (O(P), and the reference AD-PSGD reduces to it at
                # staleness 0 — bitwise, asserted in tests) and the
                # compiled per-step matrix for everything else
                mixed = self._mix_sched(state.params, k_mix, state.step)
                updates, opt_state = self._opt_update(
                    grads, state.opt_state, state.params, mixed)
                new_params = self._apply(mixed, updates)
            else:                                          # descend_then_mix
                updates, opt_state = self._opt_update(
                    grads, state.opt_state, state.params, state.params)
                new_params = self._mix_sched(
                    self._apply(state.params, updates), k_mix, state.step)

        elif algo.algo == "adpsgd":
            # Async pairwise gossip, simulated one global tick at a time:
            #   active  — learners that finish a local step this tick (the
            #             injected straggler finishes every slow_factor ticks)
            #   remote  — what partners read: the last-published buffer, or
            #             the live weights once the staleness bound is hit
            n = algo.n_learners
            mem = state.members
            if mem is not None:       # elastic fleet (DESIGN §15)
                # a dead learner is a permanently-inactive straggler: never
                # active, never force-published, never matched
                active = member_active_mask(state.step, mem.active,
                                            mem.slow_every)
                fresh = (age >= algo.max_staleness) & mem.active
                stale_seen = jnp.where(fresh | ~mem.active, 0, age)
                partner = topo.masked_pair_partners(k_mix, mem.active,
                                                    drop=mem.drop_round)
            else:
                active = straggler_active_mask(state.step, n,
                                               algo.slow_learner,
                                               algo.slow_factor)
                fresh = age >= algo.max_staleness   # forced publish (tau)
                stale_seen = jnp.where(fresh, 0, age)
                partner = pair_partners(k_mix, n)
            stale_mean = jnp.mean(stale_seen.astype(jnp.float32))
            stale_max = jnp.max(stale_seen).astype(jnp.float32)

            losses, grads = self._fwd_bwd(state.params, stacked_batch)
            with phase("gossip"):
                remote = _select(fresh, state.params, buffer)
                mixed = mix_pair_gather(state.params, partner, remote)
            updates, opt_state_new = self._opt_update(
                grads, state.opt_state, state.params, mixed)
            stepped = self._apply(mixed, updates)

            # inactive learners are mid-step: weights and momentum unchanged
            with phase("update"):
                new_params = _select(active, stepped, state.params)
                opt_state = _select(active, opt_state_new, state.opt_state)
            # publishing: completing a step publishes the new weights; a
            # forced-fresh learner re-publishes its (unchanged) in-progress
            # weights — both cases read off new_params
            with phase("gossip"):
                buffer = _select(active | fresh, new_params, buffer)
            age = jnp.where(active | fresh, 0, age + 1)
            clock = clock + active.astype(jnp.int32)
        else:
            raise ValueError(algo.algo)

        mem = state.members
        with phase("stats"):
            gsq = _per_learner_grad_sq(grads)
            if mem is None:
                nact = jnp.float32(algo.n_learners)
                loss = jnp.mean(losses)
                g_mean = learner_mean(grads)
                gsq_mean = jnp.mean(gsq)
                sigma = learner_var(new_params)
            else:    # active-only statistics: evicted rows are bitwise-absent
                act = mem.active
                nact = jnp.maximum(jnp.sum(act), 1).astype(jnp.float32)
                loss = jnp.sum(jnp.where(act, losses, 0.0)) / nact
                g_mean = masked_learner_mean(grads, act)
                gsq_mean = jnp.sum(jnp.where(act, gsq, 0.0)) / nact
                sigma = masked_learner_var(new_params, act)
            metrics = StepMetrics(
                loss=loss,
                grad_norm=jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(g_mean))),
                sigma_w_sq=sigma,
                staleness_mean=stale_mean,
                staleness_max=stale_max,
                n_active=nact,
                grad_sq_mean=gsq_mean,
            )
        return TrainState(new_params, opt_state, state.step + 1, state.rng,
                          buffer=buffer, age=age, clock=clock,
                          members=state.members), metrics

    def _train_step_flat(self, state: TrainState, stacked_batch):
        """The flat-engine step: same algorithm semantics, (n, T, 128) state.

        Gradients are taken with respect to the flat buffer (chain rule
        through the unflatten views — their transpose is pad-and-add), so no
        parameter-sized flatten/concatenate is traced; the fused path then
        streams {w, remote, g, mu} through the batched Pallas kernel once.
        """
        algo = self.algo
        n = algo.n_learners
        key = jax.random.fold_in(state.rng, state.step)
        k_mix, _ = jax.random.split(key)

        zero = jnp.zeros((), jnp.float32)
        stale_mean, stale_max = zero, zero
        buffer, age, clock = state.buffer, state.age, state.clock
        w = state.params

        if algo.algo == "ssgd":
            with phase("gossip"):
                w_a = jnp.mean(w, axis=0)
            losses, grads = self._fwd_bwd(w_a, stacked_batch,
                                          in_axes=(None, 0))
            with phase("gossip"):
                g_mean = jnp.mean(grads, axis=0)
                g_stacked = jnp.broadcast_to(g_mean[None], w.shape)
            updates, opt_state = self._opt_update(g_stacked, state.opt_state,
                                                  w, w)
            new_params = self._apply(w, updates)
            with phase("gossip"):
                new_params = jnp.broadcast_to(
                    jnp.mean(new_params, axis=0)[None], w.shape)

        elif algo.algo == "dpsgd":
            mem = state.members
            losses, grads = self._fwd_bwd(w, stacked_batch)
            if self._fused is not None:
                # the compiled schedule's per-step rounds: leading rounds
                # run as mixing-only kernel passes (multi-round schedules —
                # full-as-rounds, hierarchical, random_matching), the LAST
                # round fuses the momentum-SGD update into the same pass.
                # Elastic fleets swap in the membership-operand tables plus
                # the kernel's active column (dead rows stay bitwise put).
                from ..kernels import ops as kops
                act = None if mem is None else mem.active
                with phase("gossip"):
                    rounds = (self._schedule.step_rounds(k_mix, state.step)
                              if mem is None else
                              self._member_rounds(mem, k_mix, state.step))
                g_upd, wd = grads, None
                if len(rounds) > 1 and self._fused.weight_decay:
                    # weight decay regularizes the PRE-mix local weights
                    # (what the pytree reference does); once the leading
                    # rounds overwrite w the kernel would decay the mixed
                    # buffer instead — fold it into the gradients here and
                    # zero the kernel's own decay term (grads itself stays
                    # raw: the grad_norm metric reads it below)
                    with phase("update"):
                        g_upd = grads + self._fused.weight_decay * w
                    wd = 0.0
                with phase("gossip"):
                    for partners, coefs in rounds[:-1]:
                        w = kops.flat_gossip_mix(w, partners, coefs,
                                                 active=act,
                                                 backend=self.kernel_backend)
                partners, coefs = rounds[-1]
                new_params, opt_state_new = self._fused_step(
                    w, w, g_upd, state.opt_state, partners, coefs,
                    active=act, weight_decay=wd)
                opt_state = (opt_state_new if mem is None else
                             self._select_nonflat(act, opt_state_new,
                                                  state.opt_state))
            elif mem is not None:
                act = mem.active
                rounds = ([] if self._schedule is None
                          else self._member_rounds(mem, k_mix, state.step))
                if algo.gossip_order == "mix_then_descend":
                    mixed = self._mix_member_rounds(w, rounds, act)
                    updates, opt_state_new = self._opt_update(
                        grads, state.opt_state, w, mixed)
                    stepped = self._apply(mixed, updates)
                else:                                   # descend_then_mix
                    updates, opt_state_new = self._opt_update(
                        grads, state.opt_state, w, w)
                    stepped = self._mix_member_rounds(
                        self._apply(w, updates), rounds, act)
                with phase("update"):
                    new_params = jnp.where(act[:, None, None], stepped, w)
                    opt_state = _select(act, opt_state_new, state.opt_state)
            elif algo.gossip_order == "mix_then_descend":
                mixed = self._mix_sched(w, k_mix, state.step)
                updates, opt_state = self._opt_update(grads, state.opt_state,
                                                      w, mixed)
                new_params = self._apply(mixed, updates)
            else:                                       # descend_then_mix
                updates, opt_state = self._opt_update(grads, state.opt_state,
                                                      w, w)
                new_params = self._mix_sched(self._apply(w, updates),
                                             k_mix, state.step)

        elif algo.algo == "adpsgd":
            mem = state.members
            if mem is None:
                active = straggler_active_mask(state.step, n,
                                               algo.slow_learner,
                                               algo.slow_factor)
                fresh = age >= algo.max_staleness
                stale_seen = jnp.where(fresh, 0, age)
            else:
                # elastic: liveness AND the per-learner tick divisor gate
                # the step; a dead learner can neither step nor be forced
                # to publish stale quarantined rows
                active = member_active_mask(state.step, mem.active,
                                            mem.slow_every)
                fresh = (age >= algo.max_staleness) & mem.active
                stale_seen = jnp.where(fresh | ~mem.active, 0, age)
            stale_mean = jnp.mean(stale_seen.astype(jnp.float32))
            stale_max = jnp.max(stale_seen).astype(jnp.float32)

            losses, grads = self._fwd_bwd(w, stacked_batch)
            if self._fused is not None:
                # the matching + solo-aware coefs come from the compiled
                # schedule — ONE source of truth with the DPSGD fused path
                # (the round-0 draw is the raw-key pair_partners, so the
                # bitwise sync==async(tau=0) contract is table-for-table).
                # Elastic fleets draw the only-active matching from the
                # membership mask instead.
                with phase("gossip"):
                    (partners, coefs), = (
                        self._schedule.step_rounds(k_mix, state.step)
                        if mem is None else
                        self._member_rounds(mem, k_mix, state.step))
                partner = partners[0]
                # publish-mode kernel: stale-remote select, straggler select
                # AND the published-buffer rewrite all happen in the one
                # parameter pass; only the small non-flat opt leaves (scale,
                # schedule counters) still need the revert outside
                new_params, opt_state_new, buffer = self._fused_step(
                    w, w, grads, state.opt_state, partners, coefs,
                    active=active, buffer=buffer,
                    nbr_fresh=fresh[partner], publish=active | fresh)
                opt_state = self._select_nonflat(active, opt_state_new,
                                                 state.opt_state)
            else:
                if mem is None:
                    partner = pair_partners(k_mix, n)
                else:
                    partner = topo.masked_pair_partners(
                        k_mix, mem.active, drop=mem.drop_round)
                with phase("gossip"):
                    remote = jnp.where(fresh[:, None, None], w, buffer)
                    mixed = mix_pair_gather(w, partner, remote)
                updates, opt_state_new = self._opt_update(
                    grads, state.opt_state, w, mixed)
                stepped = self._apply(mixed, updates)
                with phase("update"):
                    new_params = jnp.where(active[:, None, None], stepped, w)
                    opt_state = _select(active, opt_state_new,
                                        state.opt_state)
                with phase("gossip"):
                    buffer = jnp.where((active | fresh)[:, None, None],
                                       new_params, buffer)
            age = jnp.where(active | fresh, 0, age + 1)
            clock = clock + active.astype(jnp.int32)
        else:
            raise ValueError(f"flat engine does not run {algo.algo}; "
                             "use engine='pytree'")

        mem = state.members
        # centered two-pass variance on the single flat buffer: same value
        # as the per-leaf learner_var (pads contribute exactly 0) at about
        # half jnp.var's cost, and numerically safe at consensus (the
        # E[x^2]-E[x]^2 shortcut is NOT — it cancels catastrophically there)
        with phase("stats"):
            gsq = jnp.sum(jnp.square(grads), axis=(1, 2))
            if mem is None:
                nact = jnp.float32(n)
                loss = jnp.mean(losses)
                g_mean = jnp.mean(grads, axis=0)
                gsq_mean = jnp.mean(gsq)
                dev = new_params - jnp.mean(new_params, axis=0)
                sigma = jnp.sum(jnp.square(dev)) / n
            else:    # active-only statistics: quarantined rows are excluded
                act = mem.active
                nact = jnp.maximum(jnp.sum(act), 1).astype(jnp.float32)
                m3 = act[:, None, None]
                loss = jnp.sum(jnp.where(act, losses, 0.0)) / nact
                g_mean = jnp.sum(jnp.where(m3, grads, 0.0), axis=0) / nact
                gsq_mean = jnp.sum(jnp.where(act, gsq, 0.0)) / nact
                w_mean = jnp.sum(jnp.where(m3, new_params, 0.0),
                                 axis=0) / nact
                dev = jnp.where(m3, new_params - w_mean[None], 0.0)
                sigma = jnp.sum(jnp.square(dev)) / nact
            metrics = StepMetrics(
                loss=loss,
                grad_norm=jnp.sqrt(jnp.sum(jnp.square(g_mean))),
                sigma_w_sq=sigma,
                staleness_mean=stale_mean,
                staleness_max=stale_max,
                n_active=nact,
                grad_sq_mean=gsq_mean,
            )
        return TrainState(new_params, opt_state, state.step + 1, state.rng,
                          buffer=buffer, age=age, clock=clock,
                          members=state.members), metrics

    # -- multi-step scan driver (DESIGN §11) ----------------------------------
    def _run_steps(self, state: TrainState, stacked_batches):
        return jax.lax.scan(self._train_step, state, stacked_batches)

    def run_steps(self, state: TrainState, stacked_batches, k: int = None):
        """Run ``k`` fused steps under one lax.scan dispatch.

        stacked_batches leaves: (k, n, B_local, ...) — k prefetched
        minibatches per learner.  Returns (final state, StepMetrics with a
        leading (k,) axis).  The state argument is donated; between probe
        boundaries this is the preferred driver (no host round-trip per
        step).  ``k`` is optional validation sugar.
        """
        if k is not None:
            lead = jax.tree_util.tree_leaves(stacked_batches)[0].shape[0]
            if lead != k:
                raise ValueError(f"stacked_batches carry {lead} steps, "
                                 f"expected k={k}")
        return self._run_steps_jit(state, stacked_batches)

    # -- probe seam (replaces ad-hoc diag_every loops; DESIGN §10) ------------
    def add_probe(self, name: str, schedule, fn,
                  on_result: Optional[Callable] = None) -> None:
        """Register a scheduled probe.  ``schedule.due(step)`` gates it;
        ``fn(state, batch) -> result``; optional ``on_result(state, result)
        -> state`` feeds a controller back into the training state."""
        self.hooks.append(ProbeHook(name, schedule, fn, on_result))

    def probes_due(self, step: int) -> bool:
        """True if any registered probe fires at ``step`` (lets the host
        loop skip fetching a probe superbatch on quiet steps)."""
        return any(h.schedule.due(step) for h in self.hooks)

    def run_probes(self, state: TrainState, stacked_batch, step: int = None):
        """Run every due probe; returns (possibly updated state, {name: result}).

        Pass the same ``step`` you gated on with ``probes_due`` — a host
        loop counter can lag ``state.step`` (e.g. after a warm-up compile
        step) and silently firing on the wrong one would no-op the probes.
        Defaults to ``int(state.step)``.

        Probe fns receive the pytree ``state_view`` (engine-agnostic
        measurement code); ``on_result`` receives the REAL state so
        controllers write straight into the live (possibly flat) optimizer
        state.  Probes never donate the state — it outlives them.
        """
        step = int(state.step) if step is None else step
        results = {}
        for h in self.hooks:
            if not h.schedule.due(step):
                continue
            # view rebuilt per hook: a later hook's fn must observe state an
            # earlier hook's on_result already wrote (e.g. a controller
            # scale) — state_view is the identity on the pytree engine and
            # cheap slices on the flat one
            r = h.fn(self.state_view(state), stacked_batch)
            results[h.name] = r
            if h.on_result is not None:
                state = h.on_result(state, r)
        return state, results

    # -- diagnostics (paper Fig. 2b / Fig. 4) ---------------------------------
    def _diagnostics(self, state: TrainState, stacked_batch) -> DiagStats:
        return compute_diagnostics(self.loss_fn,
                                   self._params_any(state.params),
                                   stacked_batch,
                                   self.alpha_for_diag, age=state.age)

    # -- eval ----------------------------------------------------------------
    def _eval_loss(self, state: TrainState, batch):
        """Loss of the average model on a (B, ...) batch (heldout metric)."""
        if self._flat and isinstance(state.params, jax.Array):
            w_a = self._meta.unflatten(jnp.mean(state.params, axis=0))
            return self.loss_fn(w_a, batch)
        return self.loss_fn(learner_mean(state.params), batch)
