"""Multi-learner update rules: SSGD, SSGD*, DPSGD (paper Eq. 1/2) and the
asynchronous AD-PSGD variant (Lian et al. 2018, staleness-bounded model).

All functions operate on *stacked* pytrees whose leaves carry a leading
learner axis of size n.  Two interchangeable gossip backends:

  * ``mix_einsum``   — w_i <- sum_j M_ij w_j, the paper-faithful reference.
    Under pjit the L x L einsum over the learner axis partitions into
    all-gather + local contraction.
  * ``mix_ppermute`` — ring / pairwise gossip via jax.lax.ppermute inside
    shard_map.  Moves O(P) bytes per learner instead of O(L*P): this is the
    TPU-native collective schedule (beyond-paper optimization, see DESIGN §2).

The semantics of one DPSGD step (paper Eq. 2, "mix then descend"):

    g_j   = grad L^{mu_j}(w_j)            # gradient at the LOCAL weights
    w_s,j = sum_k M_jk w_k                # gossip average of neighbors
    w_j   <- w_s,j - alpha * g_j

SSGD (Eq. 1): g_j = grad L^{mu_j}(w_a); w_a <- w_a - alpha * mean_j g_j.
SSGD* adds iid N(0, sigma0^2) weight noise before the gradient evaluation.

AD-PSGD replaces the synchronous pairwise mix by gossip against a possibly
*stale* published weight buffer: each learner averages with one partner's
last-published weights instead of blocking until the partner finishes its
step.  Staleness is bounded (``max_staleness`` ticks) and modeled with an
explicit per-learner clock so the whole thing stays jittable; with
``max_staleness=0`` the buffer is always fresh and AD-PSGD degenerates —
bitwise — to synchronous pairwise DPSGD (asserted in tests).  See DESIGN §3.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import topology as topo
from .flatstate import flat_meta
from .util import learner_mean, tree_gaussian_like

__all__ = ["AlgoConfig", "mix_einsum", "mix_ppermute_ring", "mix_ppermute_pair",
           "mix_ppermute_ring_flat", "mix_ppermute_pair_flat",
           "mix_ppermute_schedule", "mix_ppermute_schedule_flat",
           "perturb_weights", "pair_partners", "mix_pair_gather",
           "straggler_active_mask", "member_active_mask"]


@dataclasses.dataclass(frozen=True)
class AlgoConfig:
    """How the learners talk to each other.

    ``topology`` names a compiled GossipSchedule (core/schedule.py):
    static graphs (full | ring | torus | hierarchical | exp), time-varying
    ones (one_peer_exp | random_pair | random_matching), or solo (no
    mixing).  ``gossip_rounds`` is the multi-round mixing depth for
    ``random_matching`` (each round redraws the matching before the
    descent — Stich-style extra mixing for large-batch runs).
    """
    algo: str = "dpsgd"            # dpsgd | ssgd | ssgd_star | adpsgd
    topology: str = "random_pair"  # see core/schedule.SCHEDULED_TOPOLOGIES
    gossip_backend: str = "einsum"  # einsum | ppermute
    gossip_order: str = "mix_then_descend"  # paper Eq. 2; or descend_then_mix
    noise_std: float = 0.01        # sigma_0 for ssgd_star
    n_learners: int = 16
    gossip_rounds: int = 1         # mixing rounds per step (random_matching)
    # -- adpsgd only --------------------------------------------------------
    max_staleness: int = 0         # staleness bound tau (ticks); 0 == sync
    slow_learner: int = -1         # index of the injected straggler (-1: none)
    slow_factor: int = 1           # straggler finishes a step every k ticks

    def __post_init__(self):
        assert self.algo in ("dpsgd", "ssgd", "ssgd_star", "adpsgd"), self.algo
        assert self.gossip_order in ("mix_then_descend", "descend_then_mix")
        assert self.gossip_backend in ("einsum", "ppermute")
        assert self.gossip_rounds >= 1, self.gossip_rounds
        assert self.gossip_rounds == 1 or self.topology == "random_matching", \
            ("gossip_rounds only parameterizes random_matching — other "
             "schedules fix their own round structure (it would be "
             "silently ignored)")
        assert self.max_staleness >= 0, self.max_staleness
        assert self.slow_factor >= 1, self.slow_factor
        assert -1 <= self.slow_learner < self.n_learners, self.slow_learner
        if self.algo == "adpsgd":
            assert self.topology == "random_pair", \
                "adpsgd gossips pairwise; use topology='random_pair'"
            assert self.gossip_order == "mix_then_descend", \
                "adpsgd only supports the paper Eq. 2 ordering"
            assert self.gossip_rounds == 1, \
                "adpsgd's async tick is one pairwise exchange"


# ---------------------------------------------------------------------------
# gossip backends
# ---------------------------------------------------------------------------

def mix_einsum(stacked, m):
    """w_i <- sum_j M_ij w_j applied to every leaf (paper-faithful reference)."""
    def _mix(x):
        # ellipsis einsum keeps trailing (model-sharded) dims intact — a
        # flatten here would destroy the tensor-parallel sharding and force
        # XLA to replicate every leaf (measured: 96 GB -> 1.6 GB temp).
        # HIGHEST: at default precision the TPU's MXU rounds both the
        # weights and the mixing coefficients (1/3 on a ring) to bfloat16;
        # the average must stay f32-exact
        out = jnp.einsum("ij,j...->i...", m.astype(jnp.float32),
                         x.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        return out.astype(x.dtype)
    return jax.tree_util.tree_map(_mix, stacked)


def mix_ppermute_ring(stacked, axis_names, self_weight: float = 1.0 / 3.0):
    """Symmetric-ring gossip with two collective-permutes over the learner
    mesh axis (to be called inside shard_map; leaves have NO learner dim
    locally — the learner axis is the mesh axis itself)."""
    n = jax.lax.psum(1, axis_names)
    idx = jax.lax.axis_index(axis_names)
    del idx
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    side = (1.0 - self_weight) / 2.0

    def _mix(x):
        left = jax.lax.ppermute(x, axis_names, fwd)
        right = jax.lax.ppermute(x, axis_names, bwd)
        return (self_weight * x + side * (left + right)).astype(x.dtype)
    return jax.tree_util.tree_map(_mix, stacked)


def mix_ppermute_pair(stacked, axis_names, step, remote=None, gate=None):
    """Pairwise gossip: partner = index XOR (1 << (step % log2 n)) — a
    deterministic hypercube schedule whose per-step matching matches the
    paper's random-neighbor rule in expectation, with ONE collective-permute.
    Call inside shard_map.

    ``remote`` (default: ``stacked``) is the tree the partner's contribution
    is read from.  Synchronous pairwise DPSGD exchanges the live weights;
    AD-PSGD passes the stale *published* buffer here so a learner never
    blocks on a partner that is still mid-step (DESIGN §3).

    ``gate`` (scalar 0/1 per shard — elastic membership, DESIGN §15): a
    pair mixes only when BOTH endpoints gate on; otherwise each keeps its
    own weights bitwise (solo).  The gate travels over the same permute,
    so the realized matrix stays symmetric — and doubly stochastic over
    the gated-on (active) set.
    """
    n = jax.lax.psum(1, axis_names)
    assert n & (n - 1) == 0, "pairwise ppermute gossip needs power-of-two learners"
    import math
    log_n = int(math.log2(n))
    if remote is None:
        remote = stacked
    g = None if gate is None else jnp.asarray(gate, jnp.float32)
    # static schedule per step value is traced; build all log_n permutations and
    # select by step % log_n using lax.switch to stay jittable.
    def make_branch(bit):
        perm = [(i, i ^ (1 << bit)) for i in range(n)]
        def _b(xr):
            x, r = xr
            other = jax.lax.ppermute(r, axis_names, perm)
            mixed = (0.5 * (x + other)).astype(x.dtype)
            if g is None:
                return mixed
            both = (g * jax.lax.ppermute(g, axis_names, perm)) > 0.5
            return jnp.where(both, mixed, x)
        return _b

    branches = [make_branch(b) for b in range(log_n)]

    def _mix(x, r):
        return jax.lax.switch(step % log_n, branches, (x, r))
    return jax.tree_util.tree_map(_mix, stacked, remote)


def mix_ppermute_ring_flat(stacked, axis_names, self_weight: float = 1.0 / 3.0):
    """Ring gossip on the flat (T_local, 128) view of the LOCAL shard.

    Same semantics as mix_ppermute_ring, but the whole parameter shard is
    permuted as ONE lane-aligned buffer instead of one collective per leaf:
    2 collective-permutes total.  The buffer is flattened in the params'
    own wire dtype (a uniformly-bf16 model moves 2 bytes/element over the
    links, exactly like the per-leaf path; only a mixed-dtype tree falls
    back to f32), and the averaging arithmetic runs in f32 either way.
    Call inside shard_map; leaves have NO learner dim locally (the learner
    axis is the mesh axis itself).
    """
    meta = flat_meta(stacked)
    v = meta.flatten(stacked, dtype=meta.wire_dtype())
    n = jax.lax.psum(1, axis_names)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    side = (1.0 - self_weight) / 2.0
    left = jax.lax.ppermute(v, axis_names, fwd)
    right = jax.lax.ppermute(v, axis_names, bwd)
    mixed = (self_weight * v.astype(jnp.float32)
             + side * (left.astype(jnp.float32) + right.astype(jnp.float32)))
    return meta.unflatten(mixed)


def mix_ppermute_pair_flat(stacked, axis_names, step, remote=None, gate=None):
    """Pairwise hypercube gossip on the flat (T_local, 128) view.

    Flat-store variant of mix_ppermute_pair: ONE collective-permute moving
    one lane-aligned buffer per step (DESIGN §11), in the params' own wire
    dtype (see mix_ppermute_ring_flat).  ``remote`` is the tree the
    partner's contribution is read from (stale published buffer for
    AD-PSGD; defaults to the live weights).  ``gate``: see
    mix_ppermute_pair — pairs mix only when both endpoints gate on.
    """
    n = jax.lax.psum(1, axis_names)
    assert n & (n - 1) == 0, "pairwise ppermute gossip needs power-of-two learners"
    import math
    log_n = int(math.log2(n))
    meta = flat_meta(stacked)
    wire = meta.wire_dtype()
    v = meta.flatten(stacked, dtype=wire)
    r = v if remote is None else flat_meta(remote).flatten(remote, dtype=wire)
    g = None if gate is None else jnp.asarray(gate, jnp.float32)

    def make_branch(bit):
        perm = [(i, i ^ (1 << bit)) for i in range(n)]

        def _b(xr):
            x, rr = xr
            other = jax.lax.ppermute(rr, axis_names, perm)
            mixed = 0.5 * (x.astype(jnp.float32) + other.astype(jnp.float32))
            if g is None:
                return mixed
            both = (g * jax.lax.ppermute(g, axis_names, perm)) > 0.5
            return jnp.where(both, mixed, x.astype(jnp.float32))
        return _b

    branches = [make_branch(b) for b in range(log_n)]
    mixed = jax.lax.switch(step % log_n, branches, (v, r))
    return meta.unflatten(mixed)


def _schedule_perms(schedule):
    """Per (round, neighbor-slot) ppermute pair lists from a compiled
    deterministic schedule; ``None`` marks a padded self-loop slot (no
    collective is issued for it)."""
    assert not schedule.randomized, \
        "a random matching cannot be a compiled collective schedule"
    assert schedule.perm_rounds, schedule.name
    n = schedule.n
    idx = np.arange(n)
    perms = []
    for r in range(schedule.period):
        slots = []
        for k in range(schedule.K):
            p = np.asarray(schedule.partners[r, k])
            if (p == idx).all() and not schedule.coefs[r][:, 1 + k].any():
                slots.append(None)            # padding: skip the collective
            else:
                # dest i reads partners[k, i] -> perm pairs (src, dst)
                slots.append([(int(p[i]), i) for i in range(n)])
        perms.append(slots)
    return perms


def _schedule_round_mix(x, axis_names, schedule, perms, r: int, idx):
    """One STATIC round ``r`` of the schedule on a local array ``x``:
    gather each neighbor slot with a collective-permute and accumulate in
    f32 with the same term order as the fused kernel/einsum tables.  Slots
    sharing one permutation (a 2-row torus's up/down) share its collective:
    one permute per distinct live permutation."""
    coefs = jnp.asarray(schedule.coefs[r])
    acc = coefs[idx, 0] * x.astype(jnp.float32)
    received = {}
    for k, perm in enumerate(perms[r]):
        if perm is None:
            continue
        key = tuple(perm)
        if key not in received:
            received[key] = jax.lax.ppermute(x, axis_names, perm)
        acc = acc + coefs[idx, 1 + k] * received[key].astype(jnp.float32)
    return acc


def _schedule_mix_rounds(x, axis_names, step, schedule, perms, idx):
    """All rounds one step executes (f32 result).  Whole-cycle schedules
    unroll statically; a time-varying one (one-peer exponential) selects
    its round by ``step`` with lax.switch — same pattern as
    mix_ppermute_pair's hypercube branch table."""
    from functools import partial as _partial
    for j in range(schedule.rounds_per_step):
        if not schedule.time_varying:
            x = _schedule_round_mix(x, axis_names, schedule, perms,
                                    j % schedule.period, idx)
        else:
            r = (step * schedule.rounds_per_step + j) % schedule.period
            branches = [_partial(_schedule_round_mix, axis_names=axis_names,
                                 schedule=schedule, perms=perms, r=rr,
                                 idx=idx)
                        for rr in range(schedule.period)]
            x = jax.lax.switch(r, branches, x)
    return x


def mix_ppermute_schedule(stacked, axis_names, step, schedule):
    """Schedule-driven K-neighbor gossip via collective-permute, per leaf.

    The permutation sequence is derived from the SAME compiled tables the
    fused kernel consumes (every deterministic schedule guarantees each
    partner row is a permutation), so the SPMD path and the research path
    realize the identical mixing matrix — parity-pinned against
    ``schedule.step_matrix`` in tests.  Call inside shard_map; leaves have
    no learner dim locally.
    """
    perms = _schedule_perms(schedule)
    idx = jax.lax.axis_index(axis_names)

    def _mix(x):
        out = _schedule_mix_rounds(x, axis_names, step, schedule, perms, idx)
        return out.astype(x.dtype)
    return jax.tree_util.tree_map(_mix, stacked)


def mix_ppermute_schedule_flat(stacked, axis_names, step, schedule):
    """Flat-store variant of mix_ppermute_schedule (DESIGN §11/§12): one
    lane-aligned (T_local, 128) buffer per collective instead of one
    collective per leaf — K permutes per round regardless of leaf count.
    The first hop moves the params' own wire dtype; multi-round schedules
    keep the running mix in f32 between rounds (the arithmetic is f32
    everywhere, exactly like the per-leaf path)."""
    perms = _schedule_perms(schedule)
    idx = jax.lax.axis_index(axis_names)
    meta = flat_meta(stacked)
    v = meta.flatten(stacked, dtype=meta.wire_dtype())
    out = _schedule_mix_rounds(v, axis_names, step, schedule, perms, idx)
    return meta.unflatten(out)


# ---------------------------------------------------------------------------
# pairwise (matching-based) gossip — shared by sync DPSGD and AD-PSGD
# ---------------------------------------------------------------------------

pair_partners = topo.pair_partners     # re-export: the matching lives with
                                       # the other topology constructors


def mix_pair_gather(stacked, partner, remote=None):
    """w_i <- 0.5 * (w_i + remote[partner_i]); solo learners keep w_i.

    ``remote`` defaults to ``stacked`` (synchronous pairwise DPSGD).  AD-PSGD
    passes the stale published buffer so the partner's contribution may lag
    its live weights by up to the staleness bound.  Solo learners (odd n, or
    partner == self) are left bitwise untouched — critical so a stale *own*
    buffer never bleeds into a learner's weights.
    """
    if remote is None:
        remote = stacked

    def _mix(x, r):
        solo = (partner == jnp.arange(x.shape[0]))
        mask = solo.reshape((-1,) + (1,) * (x.ndim - 1))
        half = 0.5 * (x + r[partner])
        return jnp.where(mask, x, half).astype(x.dtype)
    return jax.tree_util.tree_map(_mix, stacked, remote)


def straggler_active_mask(step, n: int, slow_learner: int, slow_factor: int):
    """(n,) bool: which learners complete a local step this tick.

    The injected straggler (``slow_learner``) takes ``slow_factor`` ticks per
    step, so it is active only when ``step % slow_factor == 0``; everyone else
    is active every tick.  ``slow_learner < 0`` or ``slow_factor == 1``
    disables the injection (all active).
    """
    idx = jnp.arange(n)
    if slow_learner < 0 or slow_factor == 1:
        return jnp.ones((n,), bool)
    return (idx != slow_learner) | (step % slow_factor == 0)


def member_active_mask(step, active, slow_every):
    """(n,) bool: which fleet members complete a local step this tick.

    The elastic generalization of :func:`straggler_active_mask` — instead
    of one statically-configured straggler, every learner carries a dynamic
    ``slow_every`` tick divisor (1 = full speed, k = one completed step per
    k ticks, huge = wedged/hung) and a liveness bit.  Dead learners are
    never active; ``slow_every[i] == straggler``'s ``slow_factor``
    reproduces the legacy injection law exactly (``step % k == 0``).
    All inputs may be traced — this runs inside the jitted step with the
    membership arrays threaded as operands (DESIGN §15).
    """
    slow_every = jnp.asarray(slow_every, jnp.int32)
    gate = (slow_every <= 1) | (step % jnp.maximum(slow_every, 1) == 0)
    return jnp.asarray(active, bool) & gate


def perturb_weights(key, params, std):
    """SSGD*: w + delta, delta ~ N(0, std^2 I)."""
    noise = tree_gaussian_like(key, params, std)
    return jax.tree_util.tree_map(jnp.add, params, noise)


def mean_broadcast(stacked):
    """Replace every learner's weights by the global average (SSGD sync)."""
    mean = learner_mean(stacked)
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return jax.tree_util.tree_map(
        lambda m: jnp.broadcast_to(m[None], (n,) + m.shape), mean)
