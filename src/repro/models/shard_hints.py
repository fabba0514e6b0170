"""Conditional sharding hints: apply lax.with_sharding_constraint only when
the current (abstract) mesh actually has the named axes — model code stays
runnable on a bare CPU (tests) and acquires the right activation shardings
under the production mesh (dry-run / real launch)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def current_mesh():
    """The abstract mesh of the surrounding ``jax.set_mesh`` context, or
    None outside one."""
    am = jax.sharding.get_abstract_mesh()
    return am if am.axis_names else None


def mesh_axes() -> tuple:
    return tuple(jax.sharding.get_abstract_mesh().axis_names)


def _filter(spec_entry, axes):
    if spec_entry is None:
        return None
    if isinstance(spec_entry, (tuple, list)):
        kept = tuple(a for a in spec_entry if a in axes)
        return kept if kept else None
    return spec_entry if spec_entry in axes else None


def hint(x, *spec):
    """with_sharding_constraint(x, P(*spec)) with unknown axes dropped.
    No-op when there is no surrounding mesh."""
    axes = mesh_axes()
    if not axes:
        return x
    filtered = [_filter(s, axes) for s in spec]
    if all(s is None for s in filtered):
        return x
    return jax.lax.with_sharding_constraint(x, P(*filtered))


import contextlib
import threading

_CTX = threading.local()


def batch_axes():
    """Mesh axes that shard the activation batch dim in the CURRENT context.
    Serving (pjit, batch is global): ('pod', 'data') — the default.
    DPSGD training (under vmap over learners with spmd_axis_name): () — the
    learner axis is handled by vmap itself and the per-learner batch is
    unsharded."""
    return getattr(_CTX, "batch_axes", DATA_AXES)


@contextlib.contextmanager
def activation_batch_axes(axes):
    prev = getattr(_CTX, "batch_axes", DATA_AXES)
    _CTX.batch_axes = tuple(axes)
    try:
        yield
    finally:
        _CTX.batch_axes = prev


@contextlib.contextmanager
def vmapped_learner_axes(axes):
    """Inside it, a ``vmap`` over learners is sharded over the mesh axes
    ``axes`` (the DPSGD steps' ``spmd_axis_name``): code that must run once
    per learner, unbatched, maps over them with ``shard_map``."""
    prev = getattr(_CTX, "learner_axes", ())
    _CTX.learner_axes = tuple(axes)
    try:
        yield
    finally:
        _CTX.learner_axes = prev


def learner_axes() -> tuple:
    """The mesh axes the current vmap over learners is sharded over, or ()
    (no mesh: the learners share one device)."""
    return getattr(_CTX, "learner_axes", ())


def map_learners(fn, args):
    """``fn`` once per learner over the leading (learner) axis of ``args``,
    unbatched: inside a vmap sharded over the learner mesh axes
    (``vmapped_learner_axes``) each device maps over its own learners in
    ``shard_map``; elsewhere (learners stacked on one device) a
    ``lax.map`` does."""
    def local(*a):
        if a[0].shape[0] == 1:
            return jax.tree_util.tree_map(lambda o: o[None],
                                          fn(*(x[0] for x in a)))
        return jax.lax.map(lambda t: fn(*t), a)
    axes = learner_axes()
    if not axes:
        return local(*args)
    spec = P(axes)
    return jax.shard_map(local, in_specs=(spec,) * len(args),
                         out_specs=spec, check_vma=False)(*args)


def per_learner(fn):
    """``fn`` under ``custom_vmap``: a vmap over learners runs it as one
    unbatched call per learner (``map_learners``).  For what takes no
    batch dimension or cannot be partitioned by the compiler: the TPU's
    ragged dot, a Mosaic kernel."""
    f = jax.custom_batching.custom_vmap(fn)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = map_learners(fn, args)
        return out, jax.tree_util.tree_map(lambda _: True, out)
    return f


def residual_hint(x):
    """Constrain a (B, S, d) residual-stream activation: batch over the
    context's batch axes, S and d replicated over `model` — forces XLA's
    SPMD propagation into the Megatron pattern (one (B,S,d) all-reduce per
    row-parallel matmul instead of two (B,S,ff) ones; see EXPERIMENTS H2)."""
    return hint(x, batch_axes(), *([None] * (x.ndim - 1)))


def has_axis(name: str) -> bool:
    return name in mesh_axes()


def axis_size(name: str) -> int:
    m = current_mesh()
    if m is None:
        return 1
    return dict(zip(m.axis_names, m.axis_sizes)).get(name, 1)


DATA_AXES = ("pod", "data")
