"""Pallas TPU kernels: causal flash attention, forward and backward, with
q and k of one width and v of another (latent attention: 192 and 128).

Layout (B, H, S, D), float32 in HBM: q, k (B, H, S, dk); v (B, H, S, dv).
Three kernels, each over a grid (B, H, tiles) whose last axis walks the
(q block, k block) tiles on or below the diagonal, in an order read from
two scalar-prefetched tables, and accumulates in VMEM scratch:

* ``causal_flash_fwd``: q-major (each q block's k blocks in turn); the
  online softmax.  Writes the output and the per-row log-sum-exp
  (B, H, 1, S).
* ``causal_flash_dq``: q-major; recomputes the probabilities from q, k and
  the log-sum-exp, ``ds = p (dp - delta)`` with ``delta = rowsum(do . o)``,
  and accumulates ``dq``.
* ``causal_flash_dkv``: k-major; accumulates ``dk`` and ``dv`` from the
  transposed tile (keys on sublanes, queries on lanes), so the row
  statistics broadcast without a relayout.

Score and probability tiles never leave VMEM.  A tile above the diagonal
is not on the grid, so it costs neither a step nor a DMA; only the tiles
the diagonal crosses are masked.

Precision is XLA's DEFAULT for an f32 matmul on the TPU: each dot rounds
its operands to bfloat16 once and accumulates in f32.  The softmax
statistics, the output and the gradients are f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
BLOCK = 1024

_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_NN = (((1,), (0,)), ((), ()))        # a @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               dims, preferred_element_type=jnp.float32)


def _last_k_block(iq, bq, bk):
    """The last k block that q block ``iq`` attends to."""
    return ((iq + 1) * bq - 1) // bk


def _first_q_block(ik, bq, bk):
    """The first q block that attends to k block ``ik``."""
    return (ik * bk) // bq


def _tiles(S, bq, bk, *, k_major=False):
    """(q blocks, k blocks): the tiles on or below the diagonal, q-major
    (or k-major), as two int32 tables."""
    nq, nk = S // bq, S // bk
    if k_major:
        pairs = [(i, j) for j in range(nk)
                 for i in range(_first_q_block(j, bq, bk), nq)]
    else:
        pairs = [(i, j) for i in range(nq)
                 for j in range(_last_k_block(i, bq, bk) + 1)]
    qi, ki = np.asarray(pairs, np.int32).T
    return jnp.asarray(qi), jnp.asarray(ki)


def _masked_or_not(iq, ik, bq, bk, body):
    """``body(masked)``: masked where the diagonal crosses tile (iq, ik),
    unmasked below it."""
    crossed = ik * bk + bk - 1 > iq * bq

    @pl.when(jnp.logical_not(crossed))
    def _below():
        body(False)

    @pl.when(crossed)
    def _crossed():
        body(True)


def _causal(s, iq, ik, bq, bk, *, transposed=False):
    """Scores (bq, bk) (or (bk, bq) where ``transposed``) with the keys
    after their query set to NEG_INF."""
    rows, cols = s.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    if transposed:
        qpos, kpos = iq * bq + c, ik * bk + r
    else:
        qpos, kpos = iq * bq + r, ik * bk + c
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _column(row):
    """(1, n) -> (n, 1), by the (128, n) transpose the TPU does natively."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))[:, :1]


def _fwd_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr,
                l_scr, acc_scr, *, bq, bk, scale):
    t = pl.program_id(2)
    iq, ik = qi_ref[t], ki_ref[t]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s = _dot(q_ref[0, 0], k_ref[0, 0], _NT) * scale        # (bq, bk)
        if masked:
            s = _causal(s, iq, ik, bq, bk)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + _dot(p, v_ref[0, 0], _NN)
        m_scr[...] = m_new

    _masked_or_not(iq, ik, bq, bk, body)

    @pl.when(ik == _last_k_block(iq, bq, bk))
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)                          # (bq, 1)
        lse_ref[0, 0] = jnp.transpose(
            jnp.broadcast_to(lse, (bq, LANES)))[:1]


def _dq_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, acc_scr, lse_scr, delta_scr, *, bq, bk,
               scale):
    t = pl.program_id(2)
    iq, ik = qi_ref[t], ki_ref[t]

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        lse_scr[...] = _column(lse_ref[0, 0])
        delta_scr[...] = _column(delta_ref[0, 0])

    def body(masked):
        k = k_ref[0, 0]
        s = _dot(q_ref[0, 0], k, _NT) * scale                  # (bq, bk)
        if masked:
            s = _causal(s, iq, ik, bq, bk)
        p = jnp.exp(s - lse_scr[...])
        dp = _dot(do_ref[0, 0], v_ref[0, 0], _NT)              # (bq, bk)
        ds = p * (dp - delta_scr[...])
        acc_scr[...] += _dot(ds, k, _NN)

    _masked_or_not(iq, ik, bq, bk, body)

    @pl.when(ik == _last_k_block(iq, bq, bk))
    def _finalize():
        dq_ref[0, 0] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, ki_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, bq, bk,
                n_q_blocks, scale):
    t = pl.program_id(2)
    iq, ik = qi_ref[t], ki_ref[t]

    @pl.when(iq == _first_q_block(ik, bq, bk))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q, do = q_ref[0, 0], do_ref[0, 0]
        st = _dot(k_ref[0, 0], q, _NT) * scale                 # (bk, bq)
        if masked:
            st = _causal(st, iq, ik, bq, bk, transposed=True)
        pt = jnp.exp(st - lse_ref[0, 0])
        dv_scr[...] += _dot(pt, do, _NN)
        dpt = _dot(v_ref[0, 0], do, _NT)                       # (bk, bq)
        dst = pt * (dpt - delta_ref[0, 0])
        dk_scr[...] += _dot(dst, q, _NN)

    _masked_or_not(iq, ik, bq, bk, body)

    @pl.when(iq == n_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _blocks(S, block_q, block_k):
    bq, bk = min(block_q, S), min(block_k, S)
    if S % bq or S % bk:
        raise ValueError(f"sequence {S} is not a multiple of the blocks "
                         f"({bq}, {bk})")
    return bq, bk


def _call(kern, name, tables, B, H, in_specs, out_specs, out_shape, scratch,
          interpret, *args):
    return pl.pallas_call(
        kern,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, tables[0].shape[0]),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*tables, *args)


def _q_map(b, h, t, qi, ki):
    return b, h, qi[t], 0


def _k_map(b, h, t, qi, ki):
    return b, h, ki[t], 0


def _row_map(b, h, t, qi, ki):
    return b, h, 0, qi[t]


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def causal_flash_fwd(q, k, v, *, block_q: int = BLOCK, block_k: int = BLOCK,
                     interpret: bool = False):
    """q, k: (B, H, S, dk); v: (B, H, S, dv) -> (o (B, H, S, dv),
    lse (B, H, 1, S) float32)."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    bq, bk = _blocks(S, block_q, block_k)
    return _call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk, scale=dk ** -0.5),
        "causal_flash_fwd", _tiles(S, bq, bk), B, H,
        [pl.BlockSpec((1, 1, bq, dk), _q_map),
         pl.BlockSpec((1, 1, bk, dk), _k_map),
         pl.BlockSpec((1, 1, bk, dv), _k_map)],
        [pl.BlockSpec((1, 1, bq, dv), _q_map),
         pl.BlockSpec((1, 1, 1, bq), _row_map)],
        [jax.ShapeDtypeStruct((B, H, S, dv), q.dtype),
         jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        [pltpu.VMEM((bq, 1), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
         pltpu.VMEM((bq, dv), jnp.float32)],
        interpret, q, k, v)


def _grad_specs(bq, bk, dk, dv):
    """BlockSpecs of q, k, v, do, lse and delta."""
    return [pl.BlockSpec((1, 1, bq, dk), _q_map),
            pl.BlockSpec((1, 1, bk, dk), _k_map),
            pl.BlockSpec((1, 1, bk, dv), _k_map),
            pl.BlockSpec((1, 1, bq, dv), _q_map),
            pl.BlockSpec((1, 1, 1, bq), _row_map),
            pl.BlockSpec((1, 1, 1, bq), _row_map)]


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def causal_flash_dq(q, k, v, do, lse, delta, *, block_q: int = BLOCK,
                    block_k: int = BLOCK, interpret: bool = False):
    """dq (B, H, S, dk) of the causal attention, from its log-sum-exp and
    ``delta = rowsum(do . o)``, both (B, H, 1, S)."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    bq, bk = _blocks(S, block_q, block_k)
    return _call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, scale=dk ** -0.5),
        "causal_flash_dq", _tiles(S, bq, bk), B, H,
        _grad_specs(bq, bk, dk, dv),
        pl.BlockSpec((1, 1, bq, dk), _q_map),
        jax.ShapeDtypeStruct((B, H, S, dk), q.dtype),
        [pltpu.VMEM((bq, dk), jnp.float32), pltpu.VMEM((bq, 1), jnp.float32),
         pltpu.VMEM((bq, 1), jnp.float32)],
        interpret, q, k, v, do, lse, delta)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def causal_flash_dkv(q, k, v, do, lse, delta, *, block_q: int = BLOCK,
                     block_k: int = BLOCK, interpret: bool = False):
    """(dk (B, H, S, dk), dv (B, H, S, dv)) of the causal attention."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    bq, bk = _blocks(S, block_q, block_k)
    return _call(
        functools.partial(_dkv_kernel, bq=bq, bk=bk, n_q_blocks=S // bq,
                          scale=dk ** -0.5),
        "causal_flash_dkv", _tiles(S, bq, bk, k_major=True), B, H,
        _grad_specs(bq, bk, dk, dv),
        [pl.BlockSpec((1, 1, bk, dk), _k_map),
         pl.BlockSpec((1, 1, bk, dv), _k_map)],
        [jax.ShapeDtypeStruct((B, H, S, dk), k.dtype),
         jax.ShapeDtypeStruct((B, H, S, dv), v.dtype)],
        [pltpu.VMEM((bk, dk), jnp.float32), pltpu.VMEM((bk, dv), jnp.float32)],
        interpret, q, k, v, do, lse, delta)


def causal_flash_bwd(q, k, v, o, lse, do, *, block_q: int = BLOCK,
                     block_k: int = BLOCK, interpret: bool = False):
    """(dq, dk, dv) of ``causal_flash_fwd`` for the output cotangent
    ``do``, from its output ``o`` and log-sum-exp."""
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    kw = dict(block_q=block_q, block_k=block_k, interpret=interpret)
    dq = causal_flash_dq(q, k, v, do, lse, delta, **kw)
    dk, dv = causal_flash_dkv(q, k, v, do, lse, delta, **kw)
    return dq, dk, dv
