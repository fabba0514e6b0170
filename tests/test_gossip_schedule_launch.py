"""SPMD gossip-schedule conformance (DESIGN §12).

The launch path derives its collective-permute sequence from the SAME
compiled GossipSchedule tables the fused kernel consumes.  This suite pins,
in an 8-forced-host-device subprocess (own process so the device count does
not leak into the rest of the suite):

  * ppermute gossip == the gather-order reference (bitwise: identical
    accumulation order, f32) for every deterministic schedule, both the
    flat-buffer and per-leaf variants, across a full schedule period;
  * both == the einsum realization of ``schedule.step_matrix`` (allclose —
    the einsum contracts in a different summation order);
  * the compiled HLO issues one collective-permute per distinct live
    neighbor permutation per round (none for padding; slots that repeat a
    permutation, like a 2-row torus's up/down, share one).
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, re
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.targets import live_slots
from repro.core.dpsgd import (mix_einsum, mix_ppermute_schedule,
                              mix_ppermute_schedule_flat)
from repro.core.schedule import DETERMINISTIC_TOPOLOGIES, make_schedule
from repro.launch.mesh import make_mesh

n = 8
mesh = make_mesh((n,), ("learners",))
tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (n, 4, 2)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (n, 5))}
specs = jax.tree_util.tree_map(lambda _: P("learners"), tree)


def gather_ref(t, s, step):
    # same accumulation order as _schedule_round_mix: self term, then the
    # neighbor slots in table order, all in f32
    def mix_leaf(x):
        for j in range(s.rounds_per_step):
            r = (step * s.rounds_per_step + j) % s.period
            partners, coefs = s.partners[r], s.coefs[r]
            bshape = (n,) + (1,) * (x.ndim - 1)
            acc = jnp.asarray(coefs[:, 0]).reshape(bshape) * x.astype(
                jnp.float32)
            for k in range(s.K):
                if (partners[k] == np.arange(n)).all() \
                        and not coefs[:, 1 + k].any():
                    continue
                acc = acc + jnp.asarray(coefs[:, 1 + k]).reshape(bshape) \
                    * x[jnp.asarray(partners[k])].astype(jnp.float32)
            x = acc
        return x
    return jax.tree_util.tree_map(mix_leaf, t)


out = {}
for name in DETERMINISTIC_TOPOLOGIES:
    s = make_schedule(name, n)
    res = {"bitwise_flat": True, "bitwise_leaf": True,
           "max_err_vs_einsum": 0.0}
    variants = max(2, s.period if s.time_varying else 1)
    for step in range(variants + 1):        # cross the period boundary too
        st = jnp.int32(step)
        with jax.set_mesh(mesh):
            got_flat = jax.shard_map(
                lambda p: mix_ppermute_schedule_flat(p, ("learners",), st, s),
                mesh=mesh, in_specs=(specs,), out_specs=specs,
                check_vma=False)(tree)
            got_leaf = jax.shard_map(
                lambda p: mix_ppermute_schedule(p, ("learners",), st, s),
                mesh=mesh, in_specs=(specs,), out_specs=specs)(tree)
        ref = gather_ref(tree, s, step)
        ein = mix_einsum(tree, s.step_matrix(None, step))
        for k in tree:
            res["bitwise_flat"] &= bool(
                (np.asarray(got_flat[k]) == np.asarray(ref[k])).all())
            res["bitwise_leaf"] &= bool(
                (np.asarray(got_leaf[k]) == np.asarray(ref[k])).all())
            res["max_err_vs_einsum"] = max(
                res["max_err_vs_einsum"],
                float(np.max(np.abs(np.asarray(got_flat[k], np.float64)
                                    - np.asarray(ein[k], np.float64)))))
    # collective count: one permute per distinct live permutation per round
    with jax.set_mesh(mesh):
        lowered = jax.jit(lambda p: jax.shard_map(
            lambda q: mix_ppermute_schedule_flat(
                q, ("learners",), jnp.int32(0), s),
            mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False)(p)).lower(tree).compile()
    res["collective_permutes"] = len(re.findall(
        r"collective-permute(?:-start)?\(", lowered.as_text()))
    # a static step runs every period round; one_peer_exp runs exactly one
    # round per step but compiles all period branches (lax.switch): XLA
    # keeps one collective per branch, so the count stays == live perms
    res["expected_permutes"] = live_slots(s)
    res["K"] = s.K
    out[name] = res
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


TOPOLOGIES = ("full", "ring", "torus", "hierarchical", "exp", "one_peer_exp")


@pytest.mark.parametrize("name", TOPOLOGIES)
def test_ppermute_matches_gather_reference_bitwise(results, name):
    """Acceptance: the launch ppermute sequence realizes the schedule's
    mixing matrix — bitwise against the identically-ordered gather form,
    for the flat-buffer and per-leaf variants alike."""
    assert results[name]["bitwise_flat"], results[name]
    assert results[name]["bitwise_leaf"], results[name]


@pytest.mark.parametrize("name", TOPOLOGIES)
def test_ppermute_matches_einsum_matrix(results, name):
    """...and against the einsum step-matrix realization up to summation
    order (f32 reassociation only)."""
    assert results[name]["max_err_vs_einsum"] < 1e-6, results[name]


@pytest.mark.parametrize("name", TOPOLOGIES)
def test_collective_count_is_one_permute_per_neighbor_slot(results, name):
    """The flat variant issues exactly one collective-permute per live
    neighbor slot — padding slots cost nothing, slots with the same
    permutation share one, and leaf count does not multiply the
    collectives."""
    r = results[name]
    assert r["collective_permutes"] == r["expected_permutes"], r
