"""Pallas TPU kernels: fused Lanczos reorthogonalization (dots + axpy).

Full reorthogonalization of a candidate Lanczos vector w against the m
basis vectors collected so far is the memory-bound inner loop of the
landscape probe (DESIGN §10):

    d_i = <v_i, w>                 i = 0..m-1     (masked to the live prefix)
    w  <- w - sum_i d_i v_i

Written naively (one jnp dot + one axpy per basis vector) XLA streams the
(T, 128) parameter view from HBM 2m times.  The two kernels here stream the
stacked basis V (M, T, 128) and w exactly once each:

  * ``reorth_dots``  — all M dot products in a single pass over {V, w},
    accumulating per-lane partial sums across the sequential TPU grid.
  * ``reorth_axpy``  — the M-term rank-1 subtraction in a single pass
    (same shape of fusion as kernels/gossip_mix.py's neighbor loop).

Traffic: 2(M+1) passes -> 2 passes + 2 over V, i.e. ~(2M+2)P vs (2M+3)P…
the win is per-*vector* reuse: w is read once per kernel instead of M
times, and the dot/axpy loop never materializes M temporaries.  Masking
(only the first j < M vectors are live at Lanczos step j) is applied to the
dot results, so one compiled kernel serves every iteration.

Like the other kernels, interpret mode (CPU container) measures correctness
cost; on TPU they compile to Mosaic.  ``kernels/ref.py`` holds the jnp
oracle (``reorth_ref``), pinned bitwise-close in tests/test_landscape.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
BLOCK_ROWS = 256          # (256, 128) f32 block = 128 KiB / buffer in VMEM


def _dots_kernel(v_ref, w_ref, out_ref, *, n_vecs: int):
    """Accumulate per-lane partial dots over the sequential row grid.

    v_ref: (M, rows, LANE); w_ref: (rows, LANE); out_ref: (M, LANE).
    """
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...].astype(jnp.float32)
    for k in range(n_vecs):
        out_ref[k, :] += jnp.sum(v_ref[k].astype(jnp.float32) * w, axis=0)


def _axpy_kernel(w_ref, v_ref, d_ref, out_ref, *, n_vecs: int):
    """out = w - sum_k d_k v_k on one (rows, LANE) tile; d in (M,) SMEM-like."""
    acc = w_ref[...].astype(jnp.float32)
    for k in range(n_vecs):
        acc -= d_ref[k] * v_ref[k].astype(jnp.float32)
    out_ref[...] = acc.astype(out_ref.dtype)


def _pad_rows(x, rows):
    """Zero-pad the row axis (axis -2) to a multiple of ``rows`` — zero rows
    contribute nothing to a dot and are sliced off after an axpy."""
    T = x.shape[-2]
    pad = (-T) % rows
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[-2] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def reorth_dots(basis, w, *, interpret: bool = False,
                block_rows: int = BLOCK_ROWS):
    """All-M dot products <v_i, w> in one fused pass.

    basis: (M, T, 128) f32; w: (T, 128) f32.  Returns (M,) f32.
    """
    M, T, lane = basis.shape
    assert lane == LANE and w.shape == (T, LANE), (basis.shape, w.shape)
    rows = min(block_rows, T)
    basis, w = _pad_rows(basis, rows), _pad_rows(w, rows)
    T = w.shape[0]
    grid = (T // rows,)

    kern = functools.partial(_dots_kernel, n_vecs=M)
    lanes = pl.pallas_call(
        kern,
        name="reorth_dots",
        grid=grid,
        in_specs=[pl.BlockSpec((M, rows, LANE), lambda i: (0, i, 0)),
                  pl.BlockSpec((rows, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((M, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, LANE), jnp.float32),
        interpret=interpret,
    )(basis, w)
    return jnp.sum(lanes, axis=1)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def reorth_axpy(w, basis, dots, *, interpret: bool = False,
                block_rows: int = BLOCK_ROWS):
    """w - sum_i dots_i v_i in one fused pass.

    w: (T, 128); basis: (M, T, 128); dots: (M,) f32.  Returns (T, 128).
    """
    M, T, lane = basis.shape
    assert lane == LANE and w.shape == (T, LANE), (basis.shape, w.shape)
    rows = min(block_rows, T)
    basis, w = _pad_rows(basis, rows), _pad_rows(w, rows)
    Tp = w.shape[0]
    grid = (Tp // rows,)

    kern = functools.partial(_axpy_kernel, n_vecs=M)
    block = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        kern,
        name="reorth_axpy",
        grid=grid,
        in_specs=[block,
                  pl.BlockSpec((M, rows, LANE), lambda i: (0, i, 0)),
                  pl.BlockSpec((M,), lambda i: (0,))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((Tp, LANE), w.dtype),
        interpret=interpret,
    )(w, basis, dots)
    return out[:T]


def reorth_pass(basis, w, mask, *, interpret: bool = False,
                block_rows: int = BLOCK_ROWS):
    """One classical-Gram-Schmidt sweep: w <- w - sum_{i: mask_i} <v_i,w> v_i.

    ``mask`` ((M,) 0/1 f32) selects the live prefix of the basis so the same
    compiled kernels serve every Lanczos iteration.  Returns (w_new, dots).
    """
    dots = reorth_dots(basis, w, interpret=interpret,
                       block_rows=block_rows) * mask
    return reorth_axpy(w, basis, dots, interpret=interpret,
                       block_rows=block_rows), dots
