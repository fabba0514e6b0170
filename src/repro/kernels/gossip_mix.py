"""Pallas TPU kernel: fused gossip-mix + momentum-SGD update.

The DPSGD inner loop per learner i is

    mixed_i = sum_j M_ij w_j            (neighbor average; j ranges over the
                                         few non-zero mixing weights)
    mu_i    = beta * mu_i + g_i         (momentum)
    w_i     = mixed_i - lr * mu_i

Unfused, XLA emits three separate HBM-bound passes over the full parameter
vector (mix read/write, momentum read/write, apply read/write) ≈ 8P moves.
The fused kernel streams each block of {w_self, w_neighbors, g, mu} through
VMEM once and writes {w_new, mu_new}: ≈ (3+k)P moves, a ~2.2x HBM-traffic
cut on the op that IS the paper's technique (arithmetic intensity < 1
flop/byte — pure bandwidth).

Tiling: a block is (rows, 128) f32 rows of the store, as many as fit a
fixed VMEM budget once every stream is double-buffered (``block_rows_for``);
the grid is ``cdiv(T, rows)`` blocks, and Pallas masks the out-of-bounds
rows of the partial last one.  The per-element arithmetic has no
cross-row term, so the block size never changes a result.  Large blocks
matter because each grid step has a fixed cost that small blocks cannot
hide behind their DMA: transformer-100m's T = 2^3 * 5 * 26,423 rows admit
no 8-aligned divisor between 40 and T, so a divisor-of-T block is 40 rows.

Layout: the parameter pytree is flattened to a (T, 128) f32 view (padded);
neighbor copies arrive as (K, T, 128) — on a real pod these are the
ppermute-received buffers, here they are explicit inputs so the kernel is
topology-agnostic (K = #non-zero off-diagonal mixing weights — any static
K: the compiled GossipSchedule tables in core/schedule.py pad every round
to one fixed neighbor count, so pair matchings, rings, tori, exponential
graphs and hierarchical rounds all dispatch the same kernel, DESIGN §12).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.flatstate import flat_meta

LANE = 128
MAX_BLOCK_ROWS = 2048       # (2048, 128) f32 block = 1 MiB per buffer in VMEM
VMEM_BUDGET = 12 * 2**20    # all streams' double buffers, under the 16 MiB
                            # of scoped VMEM Mosaic allows a kernel by default


def block_rows_for(T: int, streams: int,
                   cap: int = MAX_BLOCK_ROWS) -> int:
    """Rows of one (rows, 128) f32 block for a kernel that double-buffers
    ``streams`` parameter-sized operands and outputs: the largest multiple
    of 8 whose ``streams x 2`` buffers fit ``VMEM_BUDGET``, at most ``cap``
    and at most T rounded up to 8.  The grid takes ``cdiv(T, rows)``
    blocks; a partial last block is masked."""
    fit = VMEM_BUDGET // (streams * 2 * LANE * 4)
    rows = min(fit, cap, -(-T // 8) * 8)
    return max(8, rows - rows % 8)


def flat_block_rows(T: int, K: int, *, has_momentum: bool, publish: bool,
                    cap: int = MAX_BLOCK_ROWS) -> int:
    """``block_rows_for`` the batched kernel.  Its streams: w, K neighbors,
    the partner's published buffer (publish), g, mu (has_momentum), the
    learner's own buffer (publish); out w, mu (has_momentum), buffer
    (publish)."""
    streams = 3 + K + 2 * has_momentum + 3 * publish
    return block_rows_for(T, streams, cap)


def _kernel(w_ref, nbr_ref, g_ref, mu_ref, coef_ref, w_out_ref, mu_out_ref,
            *, n_neighbors: int, lr: float, beta: float):
    """One (rows, LANE) tile.

    coef_ref: (1 + K,) f32 in SMEM — [self_coef, neighbor coefs...].
    """
    w = w_ref[...]
    mixed = coef_ref[0] * w
    for k in range(n_neighbors):
        mixed += coef_ref[k + 1] * nbr_ref[k]
    mu_new = beta * mu_ref[...] + g_ref[...]
    w_out_ref[...] = mixed - lr * mu_new
    mu_out_ref[...] = mu_new


@functools.partial(jax.jit,
                   static_argnames=("lr", "beta", "interpret", "block_rows"))
def gossip_mix_update(w, neighbors, grads, momentum, coefs, *, lr: float,
                      beta: float = 0.9, interpret: bool = False,
                      block_rows: int = MAX_BLOCK_ROWS):
    """w, grads, momentum: (T, 128) f32; neighbors: (K, T, 128);
    coefs: (1 + K,) f32 mixing weights (self first).  Returns (w_new, mu_new).
    ``block_rows`` caps the block ``block_rows_for`` picks.
    """
    T, lane = w.shape
    assert lane == LANE, lane
    K = neighbors.shape[0]
    # streams: w, K neighbors, g, mu; w_new, mu_new
    rows = block_rows_for(T, 5 + K, block_rows)
    grid = (pl.cdiv(T, rows),)

    kern = functools.partial(_kernel, n_neighbors=K, lr=lr, beta=beta)
    block = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    nbr_block = pl.BlockSpec((K, rows, LANE), lambda i: (0, i, 0))
    coef_block = pl.BlockSpec((K + 1,), lambda i: (0,))

    return pl.pallas_call(
        kern,
        name="gossip_mix_update",
        grid=grid,
        in_specs=[block, nbr_block, block, block, coef_block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((T, LANE), w.dtype),
                   jax.ShapeDtypeStruct((T, LANE), momentum.dtype)],
        interpret=interpret,
    )(w, neighbors, grads, momentum, coefs)


# ---------------------------------------------------------------------------
# batched (learner-major) kernel: the flat-engine hot path
# ---------------------------------------------------------------------------
#
# One pallas_call updates ALL n learners: grid (n, cdiv(T, rows)),
# learner-major.
# The K neighbor operands are not gathered on the host — the partner indices
# ride in as scalar-prefetch operands and the neighbor BlockSpec index_map
# reads its learner row straight out of the published/remote buffer
# (``partners[k, i]``), so the only parameter-sized HBM traffic is the
# streamed blocks themselves: (3 + K) reads + 2 writes per element, with the
# momentum-SGD update (optional weight decay, per-learner lr scale for the
# AutoLR controller) fused into the same pass.

def _flat_kernel(part_ref, *refs, n_neighbors: int, lr: float, beta: float,
                 weight_decay: float, has_momentum: bool, publish: bool):
    """One (1, rows, LANE) tile of one learner.

    refs layout:
      w, nbr_w_0..K-1, [nbr_buf], g, [mu], [buf], coefs,
      w_out, [mu_out], [buf_out]
    coefs (SMEM, all n rows; row ``program_id(0)`` is this learner's):
    [self, neighbor..., controller scale, active] — plus, in publish mode,
    [nbr_fresh, publish].

    ``active`` (0/1) folds the AD-PSGD straggler select into the same pass:
    an inactive learner's weights and momentum stream through unchanged
    instead of costing two extra full-buffer select passes outside the
    kernel (sync paths pass 1).  ``publish`` mode (AD-PSGD, K=1) further
    folds the whole async tick in: the neighbor contribution is selected
    between the partner's live weights and its stale published buffer
    (``nbr_fresh``), and the learner's own published buffer is rewritten
    in-pass (``publish`` flag = active | forced-fresh) — the tick touches
    each parameter exactly once instead of three more select passes.
    """
    k = n_neighbors
    it = iter(refs)
    w_ref = next(it)
    nbr_refs = [next(it) for _ in range(k)]
    nbr_buf_ref = next(it) if publish else None
    g_ref = next(it)
    mu_ref = next(it) if has_momentum else None
    buf_ref = next(it) if publish else None
    coef_ref = next(it)
    w_out = next(it)
    mu_out = next(it) if has_momentum else None
    buf_out = next(it) if publish else None

    i = pl.program_id(0)                 # this tile's learner
    w = w_ref[0]
    mixed = coef_ref[i, 0] * w
    for j in range(k):
        nbr = nbr_refs[j][0]
        if publish:
            nbr = jnp.where(coef_ref[i, 3 + k] > 0.5, nbr, nbr_buf_ref[0])
        mixed += coef_ref[i, 1 + j] * nbr
    g = g_ref[0]
    if weight_decay:
        g = g + weight_decay * w
    lr_eff = lr * coef_ref[i, 1 + k]
    # where, not arithmetic blend: a mid-divergence NaN in the discarded
    # branch must not leak through 0 * NaN
    active = coef_ref[i, 2 + k] > 0.5
    if has_momentum:
        mu = mu_ref[0]
        mu_new = beta * mu + g
        new_w = jnp.where(active, mixed - lr_eff * mu_new, w)
        mu_out[0] = jnp.where(active, mu_new, mu)
    else:
        new_w = jnp.where(active, mixed - lr_eff * g, w)
    w_out[0] = new_w
    if publish:
        buf_out[0] = jnp.where(coef_ref[i, 4 + k] > 0.5, new_w, buf_ref[0])


@functools.partial(
    jax.jit, static_argnames=("lr", "beta", "weight_decay", "has_momentum",
                              "interpret", "block_rows"))
def gossip_mix_update_flat(w, remote, grads, momentum, partners, coefs, *,
                           lr: float, beta: float = 0.0,
                           weight_decay: float = 0.0,
                           has_momentum: bool = True,
                           buffer=None,
                           interpret: bool = False,
                           block_rows: int = MAX_BLOCK_ROWS):
    """Batched fused gossip + SGD update on the persistent flat store.

    w, grads: (n, T, 128) f32 live weights / gradients.
    remote:   (n, T, 128) buffer neighbor contributions are read from (the
              live weights for synchronous DPSGD — pass ``w`` itself to
              alias them).
    momentum: (n, T, 128) or ignored when ``has_momentum=False``.
    partners: (K, n) int32 — neighbor learner index per schedule row,
              consumed via scalar prefetch.  K is any static neighbor
              count: pair matching K=1, ring K=2, torus K=4, static
              exponential K=ceil(log2 n), full-as-one-round K=n-1 — one
              row of a compiled core/schedule.GossipSchedule (padded
              self-loop slots carry coefficient 0).
    coefs:    (n, K + 3) f32 — [self, neighbor..., lr scale, active] per
              learner: a solo learner carries [1, 0, ...]; ``lr scale`` is
              the controller/schedule multiplier (one compiled kernel
              serves every scale value); ``active`` (0/1) applies the
              AD-PSGD straggler select in the same pass (1 for sync paths).
    buffer:   (n, T, 128) published-weights buffer — enables the AD-PSGD
              publish mode (K=1): coefs grows two columns [nbr_fresh,
              publish]; the neighbor contribution reads
              ``where(nbr_fresh, remote[partner], buffer[partner])`` and a
              third output returns ``where(publish, w_new, buffer)`` — the
              whole async tick in one parameter pass.
    block_rows: caps the block ``flat_block_rows`` picks from the call's
              stream count.
    Returns (w_new, mu_new[, buffer_new]) — mu_new is ``momentum``
    untouched when ``has_momentum=False``; buffer_new only in publish mode.
    """
    n, T, lane = w.shape
    assert lane == LANE, lane
    K = partners.shape[0]
    publish = buffer is not None
    ncoef = K + (5 if publish else 3)
    assert not publish or K == 1, "publish mode is pairwise (AD-PSGD)"
    assert partners.shape == (K, n), (partners.shape, n)
    assert coefs.shape == (n, ncoef), (coefs.shape, K, publish)
    rows = flat_block_rows(T, K, has_momentum=has_momentum, publish=publish,
                           cap=block_rows)
    grid = (n, pl.cdiv(T, rows))

    block = pl.BlockSpec((1, rows, LANE), lambda i, j, p: (i, j, 0))

    def nbr_spec(k):
        return pl.BlockSpec((1, rows, LANE), lambda i, j, p: (p[k, i], j, 0))

    # the whole (n, ncoef) table sits in SMEM; the kernel reads its own
    # learner's row (a (1, ncoef) block would break the TPU block rule:
    # last two block dims divisible by (8, 128) or equal to the array's)
    coef_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    kern = functools.partial(_flat_kernel, n_neighbors=K, lr=lr, beta=beta,
                             weight_decay=weight_decay,
                             has_momentum=has_momentum, publish=publish)
    in_specs = [block] + [nbr_spec(k) for k in range(K)]
    operands = [w] + [remote] * K
    if publish:
        in_specs.append(nbr_spec(0))
        operands.append(buffer)
    in_specs.append(block)
    operands.append(grads)
    out_shape = [jax.ShapeDtypeStruct((n, T, LANE), w.dtype)]
    out_specs = [block]
    if has_momentum:
        in_specs.append(block)
        operands.append(momentum)
        out_shape.append(jax.ShapeDtypeStruct((n, T, LANE), jnp.float32))
        out_specs.append(block)
    if publish:
        in_specs.append(block)
        operands.append(buffer)
        out_shape.append(jax.ShapeDtypeStruct((n, T, LANE), w.dtype))
        out_specs.append(block)
    in_specs.append(coef_spec)
    operands.append(coefs)

    out = pl.pallas_call(
        kern,
        name="gossip_mix_update_flat",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=in_specs, out_specs=out_specs),
        out_shape=out_shape,
        interpret=interpret,
    )(partners, *operands)
    mu_new = out[1] if has_momentum else momentum
    if publish:
        return out[0], mu_new, out[-1]
    return out[0], mu_new


# ---------------------------------------------------------------------------
# pytree-level wrapper: flatten -> kernel -> unflatten
# ---------------------------------------------------------------------------

def flatten_for_kernel(tree):
    """Pytree -> ((T,128) f32 view, unflatten_fn).

    Thin shim over core.flatstate.FlatMeta (used by landscape/lanczos.py and
    the one-shot kernel wrappers): the metadata — per-leaf dtypes, sizes and
    offsets — is computed once per structure and cached, so repeated calls
    stop rebuilding offset lists; unflatten restores each leaf's original
    dtype from that metadata.  The flatten itself still concatenates — the
    flat *engine* (core/trainer.py) avoids even that by keeping the flat
    buffer persistent across steps.
    """
    meta = flat_meta(tree)
    return meta.flatten(tree), meta.unflatten
