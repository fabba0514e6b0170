"""Flat-state parameter store: the (T, 128) layout as a *persistent* buffer.

The Pallas gossip kernels (DESIGN §7) and the Lanczos probe (§10) both live
on a lane-aligned (T, 128) f32 view of the parameter pytree.  Until PR 3 that
view was rebuilt per call — a full concatenate + dtype round-trip over every
leaf, i.e. one extra read+write of the whole model per step, which is more
HBM traffic than the fused kernel saves.

This module makes the flat view the *source of truth* instead:

  * ``FlatMeta`` captures the pytree structure ONCE (treedef, per-leaf
    shapes/dtypes, sizes, precomputed offsets, padded row count).  It is
    static, hashable metadata — safe to close over in a jitted step and
    cached per structure (``flat_meta``).
  * ``FlatMeta.flatten`` builds the (..., T, 128) f32 buffer (arbitrary
    leading axes, e.g. the learner axis n).  The trainer calls it exactly
    once, at init.
  * ``FlatMeta.unflatten`` reconstitutes per-leaf views with precomputed
    static slices — no concatenate, no offset rebuilding.  It carries a
    custom VJP that scatters the cotangent straight back into ONE flat
    buffer, so taking gradients *with respect to the flat buffer* keeps the
    whole train step free of parameter-sized concatenates (asserted by
    ``max_concat_elems`` in tests).

Padding: T is rounded up to a multiple of ``ROW_ALIGN`` (f32 sublane tile),
so the store is whole (8, 128) tiles; the kernels' blocks need not divide
T (a partial last block is masked).  The pad region is
written as zeros at flatten time and never escapes: unflatten drops it, and
gradients through unflatten are identically zero there.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["LANE", "ROW_ALIGN", "FlatMeta", "flat_meta", "max_concat_elems"]

LANE = 128
ROW_ALIGN = 8           # f32 sublane tile: the store is whole (8, 128) tiles


@dataclasses.dataclass(frozen=True)
class FlatMeta:
    """Static description of a pytree's flat (T, 128) layout."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]            # per-leaf np.dtypes, preserved on unflatten
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]           # precomputed once — never per call
    n_elem: int                        # real (unpadded) element count
    rows: int                          # T: padded row count, multiple of ROW_ALIGN

    @classmethod
    def for_tree(cls, tree) -> "FlatMeta":
        """Build metadata from a pytree (concrete or abstract leaves);
        same cached instance as ``flat_meta``."""
        return flat_meta(tree)

    # -- layout --------------------------------------------------------------
    @property
    def padded(self) -> int:
        return self.rows * LANE

    def leading(self, tree_or_flat, *, flat: bool) -> Tuple[int, ...]:
        if flat:
            return tuple(tree_or_flat.shape[:-2])
        leaves = jax.tree_util.tree_leaves(tree_or_flat,
                                           is_leaf=lambda x: x is None)
        for leaf, shape in zip(leaves, self.shapes):
            if leaf is None:          # align with metadata past None leaves
                continue
            nd = len(leaf.shape) - len(shape)
            return tuple(leaf.shape[:nd])
        return ()

    # -- conversions ---------------------------------------------------------
    def flatten(self, tree, dtype=jnp.float32) -> jnp.ndarray:
        """Pytree (leaves ``lead + shape``) -> (lead + (T, 128)) buffer.

        The ONE place a parameter-sized concatenate is allowed — called at
        trainer init (and in the thin ``flatten_for_kernel`` shim), never
        inside the hot step.  ``dtype`` defaults to the f32 compute layout;
        the flat gossip collectives pass the params' own wire dtype so a
        bf16 model is not shipped over the links at double width.
        """
        leaves = jax.tree_util.tree_leaves(tree)
        lead = self.leading(tree, flat=False)
        flats = [l.astype(dtype).reshape(lead + (-1,)) for l in leaves]
        pad = self.padded - self.n_elem
        if pad:
            flats.append(jnp.zeros(lead + (pad,), dtype))
        return jnp.concatenate(flats, axis=-1).reshape(
            lead + (self.rows, LANE))

    def wire_dtype(self):
        """The single dtype all leaves share, or f32 for mixed trees —
        what the flat gossip collectives put on the links."""
        uniq = set(self.dtypes)
        return self.dtypes[0] if len(uniq) == 1 else np.dtype(np.float32)

    def unflatten(self, flat) -> Any:
        """(lead + (T, 128)) buffer -> pytree of per-leaf views.

        Static slices at precomputed offsets; per-leaf dtypes restored from
        metadata.  No concatenate — cheap enough to sit inside the train
        step.  Differentiable with a custom VJP: the cotangent is scattered
        back into ONE flat buffer with in-place dynamic-update-slices
        (XLA's default transpose — a pad-and-add per leaf — costs several
        extra full passes over the model and was measurably slower)."""
        return _unflatten_diff(self, flat)

    def _unflatten_impl(self, flat) -> Any:
        lead = self.leading(flat, flat=True)
        v = flat.reshape(lead + (self.padded,))

        def view(off, sz, shape, dtype):
            part = v[..., off:off + sz]
            if shape and shape[-1] % LANE:
                # a leaf narrower than a lane row: without the barrier XLA
                # may hoist its reshape above the slice and lay the WHOLE
                # store out in the leaf's narrow, lane-padded tiling (2x
                # the store, measured at a (4, 64) leaf)
                part = jax.lax.optimization_barrier(part)
            return part.reshape(lead + shape).astype(dtype)
        leaves = [view(*m) for m in zip(self.offsets, self.sizes,
                                         self.shapes, self.dtypes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def scatter(self, tree) -> jnp.ndarray:
        """Pytree -> flat buffer via in-place slice updates (no concatenate).

        The transpose of ``unflatten`` (pad region identically zero); also
        handy wherever a tree of per-leaf values must land in the flat
        layout without a parameter-sized concatenate.  Skips None /
        float0 leaves (non-differentiable cotangents); None nodes are kept
        in the traversal (is_leaf) so offsets stay aligned with the
        metadata."""
        leaves = jax.tree_util.tree_leaves(tree, is_leaf=lambda x: x is None)
        lead = self.leading(tree, flat=False)
        v = jnp.zeros(lead + (self.padded,), jnp.float32)
        for leaf, off, sz in zip(leaves, self.offsets, self.sizes):
            if leaf is None or leaf.dtype == jax.dtypes.float0:
                continue
            v = v.at[..., off:off + sz].set(
                leaf.astype(jnp.float32).reshape(lead + (-1,)))
        return v.reshape(lead + (self.rows, LANE))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _unflatten_diff(meta: FlatMeta, flat):
    return meta._unflatten_impl(flat)


def _unflatten_fwd(meta, flat):
    return meta._unflatten_impl(flat), None


def _unflatten_bwd(meta, _, ct):
    return (meta.scatter(ct),)


_unflatten_diff.defvjp(_unflatten_fwd, _unflatten_bwd)


@lru_cache(maxsize=64)
def _meta_cached(treedef, shapes, dtypes) -> FlatMeta:
    sizes = tuple(int(np.prod(s, dtype=np.int64)) if s else 1 for s in shapes)
    offsets, off = [], 0
    for sz in sizes:
        offsets.append(off)
        off += sz
    rows = -(-off // LANE)
    rows += (-rows) % ROW_ALIGN
    return FlatMeta(treedef, shapes, dtypes, sizes, tuple(offsets), off, rows)


def flat_meta(tree) -> FlatMeta:
    """Cached FlatMeta for ``tree``'s structure (works on tracers too)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(np.dtype(l.dtype) for l in leaves)
    return _meta_cached(treedef, shapes, dtypes)


# ---------------------------------------------------------------------------
# jaxpr audit: prove the hot step carries no parameter-sized concatenate
# ---------------------------------------------------------------------------

def max_concat_elems(closed_jaxpr) -> int:
    """Largest ``concatenate`` output (in elements) anywhere in the jaxpr.

    The implementation moved to ``repro.analysis.jaxpr_audit`` when the
    ad-hoc check grew into the rule framework (DESIGN §16); this delegate
    keeps the original import path for the tier-1 guard test and the bench
    harness.  Imported lazily so core stays importable without analysis.
    """
    from repro.analysis.jaxpr_audit import max_concat_elems as _impl
    return _impl(closed_jaxpr)
