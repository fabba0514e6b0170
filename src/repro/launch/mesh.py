"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

The DPSGD *learner* axis is ('data',) single-pod / ('pod', 'data') multi-pod:
each learner is one model-parallel group of 16 chips — exactly the paper's
App. F "super-learner" recommendation (16 learners single-pod, 32 multi-pod).

Functions, not module constants: importing this module must never touch jax
device state (XLA_FLAGS must be set before first jax init in dryrun).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes.  Step builders place their operands
    through in/out shardings and leave the rest to XLA's propagation, so
    the models' activation hints (models/shard_hints.py) stay constraints
    under ``jax.set_mesh`` instead of Explicit-mode asserts."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def learner_axes(mesh) -> tuple:
    """Mesh axes that enumerate DPSGD learners."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_learners(mesh) -> int:
    n = 1
    for a in learner_axes(mesh):
        n *= mesh.shape[a]
    return n


def make_test_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))
