"""Small-mesh lowering tests (8 forced host devices, own subprocess so the
device count doesn't leak into the rest of the suite)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, re
from collections import Counter
import jax
from repro.configs import get_config
from repro.models.model import build_model
from repro.optim import sgd
from repro.launch import sharding as shd
from repro.launch.train import (jit_train_step, make_adpsgd_train_step,
                                make_dpsgd_train_step, make_ssgd_train_step,
                                make_decode_step, train_state_specs,
                                train_state_shardings)
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(4, 2)
cfg = get_config("transformer-100m").smoke_config()
api = build_model(cfg)
opt = sgd(0.1, momentum=0.9)
out = {}
for algo, backend in [("dpsgd", "einsum"), ("dpsgd", "ppermute"),
                      ("ssgd", "einsum"), ("adpsgd", "ppermute")]:
    specs = train_state_specs(api, opt, mesh, algo=algo)
    shds = train_state_shardings(specs, mesh, algo=algo)
    bspecs = api.train_batch_spec(8, 64)
    bshd = shd.batch_sharding(bspecs, mesh, stacked=False)
    if algo == "dpsgd":
        step = make_dpsgd_train_step(api, opt, mesh, gossip_backend=backend)
    elif algo == "adpsgd":
        step = make_adpsgd_train_step(api, opt, mesh, max_staleness=4,
                                      slow_learner=0, slow_factor=3)
    else:
        step = make_ssgd_train_step(api, opt, mesh)
    with jax.set_mesh(mesh):
        compiled = jit_train_step(
            step, in_shardings=shd.named_shardings((shds, bshd), mesh),
            out_shardings=shd.named_shardings((shds, None), mesh),
        ).lower(specs, bspecs).compile()
    colls = Counter(re.findall(
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(",
        compiled.as_text()))
    out[f"{algo}_{backend}"] = dict(colls)
    if algo == "adpsgd":
        # acceptance: the async path must actually TRAIN under pjit, not
        # just compile — run 4 real ticks and watch state/metrics evolve
        import numpy as np
        key = jax.random.PRNGKey(0)
        params = jax.vmap(lambda k: api.init(k))(
            jax.random.split(key, 4))
        state = type(specs)(
            params=params,
            opt_state=jax.vmap(opt.init)(params),
            step=jnp.zeros((), jnp.int32),
            rng=jax.random.PRNGKey(1),
            buffer=jax.tree_util.tree_map(jnp.copy, params),
            age=jnp.zeros((4,), jnp.int32))
        batch = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), bspecs)
        with jax.set_mesh(mesh):
            run = jit_train_step(step)
            ages = []
            for _ in range(4):
                state, metrics = run(state, batch)
                ages.append(int(jnp.max(state.age)))
        out["adpsgd_exec"] = {
            "loss_finite": bool(jnp.isfinite(metrics["loss"])),
            "step": int(state.step),
            "max_age_seen": max(ages)}

# elastic membership on the launch path (DESIGN 15): gated hypercube
# gossip + membership operands, driven by the SAME FaultPlan harness as
# the vmap trainer — crash, straggle, drop a round, quarantine-rejoin
import numpy as np
from repro.core import FaultPlan, Membership
from repro.core.faults import apply_plan
from repro.launch.train import membership_operands

mem = Membership(4)
plan = FaultPlan(FaultPlan.crash_rejoin(1, 2, 6).events
                 + FaultPlan.straggler(0, 3).events)
estep = make_adpsgd_train_step(api, opt, mesh, max_staleness=4,
                               elastic=True)
key = jax.random.PRNGKey(2)
params = jax.vmap(lambda k: api.init(k))(jax.random.split(key, 4))
especs = train_state_specs(api, opt, mesh, algo="adpsgd", elastic=True)
state = type(especs)(
    params=params,
    opt_state=jax.vmap(opt.init)(params),
    step=jnp.zeros((), jnp.int32),
    rng=jax.random.PRNGKey(3),
    buffer=jax.tree_util.tree_map(jnp.copy, params),
    age=jnp.zeros((4,), jnp.int32),
    **membership_operands(mem))
bspecs = api.train_batch_spec(8, 64)
batch = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), bspecs)
leaf0 = lambda p: jax.tree_util.tree_leaves(p)[0]
dead_row, frozen, n_act, losses_fin = None, False, [], []
with jax.set_mesh(mesh):
    erun = jit_train_step(estep)
    for i in range(8):
        drop = apply_plan(mem, plan, i)
        state = state._replace(**membership_operands(mem, drop_round=drop))
        state, metrics = erun(state, batch)
        n_act.append(int(metrics["n_active"]))
        losses_fin.append(bool(jnp.isfinite(metrics["loss"])))
        if i == 2:
            dead_row = np.asarray(leaf0(state.params)[1])
        if i == 5:
            frozen = bool(
                (np.asarray(leaf0(state.params)[1]) == dead_row).all())
try:
    cache = int(erun._cache_size())
except Exception:
    cache = 1
out["elastic_exec"] = {"losses_finite": all(losses_fin),
                       "n_active": n_act, "dead_row_frozen": frozen,
                       "cache_size": cache, "step": int(state.step)}

# decode lowering
params_specs = jax.eval_shape(api.init, jax.random.PRNGKey(0))
params_shd = shd.params_sharding(params_specs, mesh, stacked=False)
cache_specs = jax.eval_shape(lambda: api.init_cache(None, 8, 64))
cache_shd = shd.cache_sharding(cache_specs, mesh)
tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
tok_shd = shd.batch_sharding(tok, mesh, stacked=False)
with jax.set_mesh(mesh):
    c = jax.jit(make_decode_step(api),
                in_shardings=shd.named_shardings(
                    (params_shd, cache_shd, tok_shd, P()), mesh),
                out_shardings=shd.named_shardings((None, cache_shd), mesh)).lower(
        params_specs, cache_specs, tok, jax.ShapeDtypeStruct((), jnp.int32)
    ).compile()
out["decode_ok"] = True
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def launch_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_all_paths_lower(launch_results):
    assert launch_results["decode_ok"]
    assert "dpsgd_einsum" in launch_results
    assert "ssgd_einsum" in launch_results


def test_ppermute_backend_uses_collective_permute(launch_results):
    pp = launch_results["dpsgd_ppermute"]
    assert pp.get("collective-permute", 0) > 0
    # the optimized backend must move strictly fewer all-gathers than einsum
    eins = launch_results["dpsgd_einsum"]
    assert pp.get("all-gather", 0) < eins.get("all-gather", 0)


def test_adpsgd_lowers_with_collective_permute(launch_results):
    """The async path's only cross-learner traffic is the ONE pairwise
    buffer exchange — a collective-permute, never a learner all-gather."""
    ad = launch_results["adpsgd_ppermute"]
    assert ad.get("collective-permute", 0) > 0
    eins = launch_results["dpsgd_einsum"]
    assert ad.get("all-gather", 0) < eins.get("all-gather", 0)


def test_adpsgd_trains_under_pjit(launch_results):
    """4 executed ticks with a 3x straggler: finite loss, advancing step,
    staleness actually observed on the sharded age vector."""
    ex = launch_results["adpsgd_exec"]
    assert ex["loss_finite"]
    assert ex["step"] == 4
    assert 0 < ex["max_age_seen"] <= 4


def test_ssgd_has_gradient_allreduce(launch_results):
    assert launch_results["ssgd_einsum"].get("all-reduce", 0) > 0


def test_elastic_membership_on_launch_path(launch_results):
    """Crash/straggle/drop/rejoin via FaultPlan on the pjit path: losses
    stay finite, the live count tracks the plan, the crashed learner's
    rows are bitwise-frozen while dead, and every membership change is a
    same-shape operand swap (ONE compiled step for the whole run)."""
    ex = launch_results["elastic_exec"]
    assert ex["losses_finite"]
    assert ex["step"] == 8
    # crash at tick 2 (visible from tick 2's metrics on), rejoin at 6
    assert ex["n_active"] == [4, 4, 3, 3, 3, 3, 4, 4]
    assert ex["dead_row_frozen"]
    # at most 2 compiles: one cold, one when the first step's outputs come
    # back committed to their shardings — the crash (tick 2), drop-round
    # toggles and rejoin (tick 6) operand swaps must add ZERO retraces
    assert ex["cache_size"] <= 2
