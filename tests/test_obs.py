"""The train steps' phase scopes (repro.obs).

* Every compiled train step names its phases in the HLO ``op_name``
  metadata: the research trainer's flat and pytree steps here, the launch
  path's steps on four virtual devices in a child process (which alone can
  ask for them).  The trainer's matrix products are the forward/backward's
  (the CPU's SPMD compile drops the metadata of the launch step's dots; the
  TPU compile of that step keeps it: tests/test_tpu_compile.py).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from hlo_text import instructions, phases as hlo_phases

from repro import obs
from repro.core import AlgoConfig, MultiLearnerTrainer
from repro.models import fcnet
from repro.optim import sgd

N = 4
PARAMS = fcnet.init_params(jax.random.PRNGKey(0), in_dim=32, hidden=16)
BATCH = {"image": jnp.ones((N, 8, 32)), "label": jnp.zeros((N, 8), jnp.int32)}


@pytest.mark.parametrize("op_name,phase", [
    ("jit(_train_step)/fwd_bwd/transpose(jvp())/dot_general", "fwd_bwd"),
    ("jit(train_step)/gossip/shard_map/ppermute", "gossip"),
    ("jit(_train_step)/stats/reduce_sum", "stats"),
    ("jit(f)/update/stats/add", "update"),           # the outer scope wins
    ("jit(_train_step)/jit(_threefry_split)/add", None),
    ("jit(f)/fwd_bwd_extra/add", None),              # whole components only
    ("", None),
])
def test_phase_of(op_name, phase):
    assert obs.phase_of(op_name) == phase


def test_phase_names_scopes_and_rejects_unknown():
    with pytest.raises(ValueError):
        obs.phase("optimizer")

    @obs.phase("update")
    def inner(x):
        return x * 2.0

    def f(x):
        with obs.phase("fwd_bwd"):
            y = jnp.sin(x)
        return inner(inner(y))          # the decorated function re-enters
    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "fwd_bwd/sin" in text and "update/mul" in text


CASES = {
    # (algo, engine, topology): the phases the compiled step must name
    ("dpsgd", "flat", "random_pair"): {"fwd_bwd", "gossip", "stats"},
    ("dpsgd", "pytree", "random_pair"): {"fwd_bwd", "gossip", "update",
                                         "stats"},
    ("adpsgd", "flat", "random_pair"): {"fwd_bwd", "gossip", "stats"},
    ("ssgd", "flat", "random_pair"): {"fwd_bwd", "gossip", "update",
                                      "stats"},
}


@pytest.mark.parametrize("algo,engine,topology", list(CASES),
                         ids=["-".join(c) for c in CASES])
def test_trainer_step_names_its_phases(algo, engine, topology):
    """Tiny DPSGD (and AD-PSGD, SSGD) steps: the compiled HLO's op_names
    map to the expected phases, and every matrix product (the model's
    layers; the pair gossip is a gather) maps to ``fwd_bwd``."""
    kw = dict(max_staleness=2) if algo == "adpsgd" else {}
    tr = MultiLearnerTrainer(
        fcnet.loss_fn, sgd(0.1, momentum=0.9),
        AlgoConfig(algo=algo, topology=topology, n_learners=N, **kw),
        engine=engine)
    assert tr.is_flat == (engine == "flat")
    state = tr.init(jax.random.PRNGKey(1), PARAMS)
    text = tr.train_step.lower(state, BATCH).compile().as_text()
    phases = hlo_phases(text)
    assert set(phases.values()) == CASES[(algo, engine, topology)]
    dots = [n for n, op in instructions(text) if op in ("dot", "convolution")]
    assert dots
    assert {phases.get(n) for n in dots} == {"fwd_bwd"}


LAUNCH = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import json
import jax
from hlo_text import instructions, phases as hlo_phases
from repro.configs import get_config
from repro.launch import sharding as shd
from repro.launch.mesh import make_mesh
from repro.launch.train import (jit_train_step, make_adpsgd_train_step,
                                make_dpsgd_train_step, make_ssgd_train_step,
                                train_state_shardings, train_state_specs)
from repro.models.model import build_model
from repro.optim import sgd

mesh = make_mesh((4, 1), ("data", "model"))
api = build_model(get_config("transformer-100m").smoke_config())
opt = sgd(0.1, momentum=0.9)
steps = {
    "dpsgd": make_dpsgd_train_step(api, opt, mesh, topology="ring",
                                   gossip_backend="ppermute"),
    "adpsgd": make_adpsgd_train_step(api, opt, mesh, max_staleness=2),
    "ssgd": make_ssgd_train_step(api, opt, mesh),
}
out = {}
for algo, step in steps.items():
    specs = train_state_specs(api, opt, mesh, algo=algo)
    shds = train_state_shardings(specs, mesh, algo=algo)
    bspecs = api.train_batch_spec(8, 32)
    bshd = shd.batch_sharding(bspecs, mesh, stacked=False)
    with jax.set_mesh(mesh):
        text = jit_train_step(
            step, in_shardings=shd.named_shardings((shds, bshd), mesh),
            out_shardings=shd.named_shardings((shds, None), mesh),
        ).lower(specs, bspecs).compile().as_text()
    phases = hlo_phases(text)
    ops = instructions(text)
    out[algo] = {
        "phases": sorted(set(phases.values())),
        "permutes": sorted({str(phases.get(n)) for n, op in ops
                            if op.startswith("collective-permute")}),
        "n_permutes": sum(op.startswith("collective-permute")
                          for _, op in ops),
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def launch_phases():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", LAUNCH,
                        os.path.dirname(os.path.abspath(__file__))], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_launch_dpsgd_ring_permutes_are_gossip(launch_phases):
    """The launch DPSGD step, ring gossip by collective-permute on four
    devices: the permutes are ``gossip`` and all four phases appear."""
    r = launch_phases["dpsgd"]
    assert r["n_permutes"] >= 2
    assert r["permutes"] == ["gossip"]
    assert r["phases"] == sorted(obs.PHASES)


def test_launch_adpsgd_and_ssgd_name_their_phases(launch_phases):
    a, s = launch_phases["adpsgd"], launch_phases["ssgd"]
    assert a["n_permutes"] >= 1 and a["permutes"] == ["gossip"]
    assert a["phases"] == sorted(obs.PHASES)
    assert set(s["phases"]) == {"fwd_bwd", "update"}
    assert s["n_permutes"] == 0



@pytest.mark.parametrize("op_name,span", [
    ("jit(train_step)/fwd_bwd/vmap(jvp())/while/body/closed_call/mla/dot_general", "mla"),
    ("jit(train_step)/fwd_bwd/vmap(transpose(jvp(moe_experts)))/dot", "moe_experts"),
    ("jit(train_step)/fwd_bwd/transpose(jvp(moe_route))/scatter-add", "moe_route"),
    ("jit(train_step)/fwd_bwd/mla/moe_route/sort", "moe_route"),   # innermost
    ("jit(train_step)/fwd_bwd/mla_extra/add", None),
    ("jit(train_step)/gossip/shard_map/ppermute", None),
])
def test_span_of(op_name, span):
    assert obs.span_of(op_name) == span
    with pytest.raises(ValueError):
        obs.span("attention")


MLA_MOE = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import dataclasses, json
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.launch import sharding as shd
from repro.launch.mesh import make_mesh
from repro.launch.train import (jit_train_step, make_dpsgd_train_step,
                                train_state_shardings, train_state_specs)
from repro.models.model import build_model
from repro.obs import phase_of, span_of
from repro.optim import sgd

cfg = dataclasses.replace(
    get_config("moonlight-16b-a3b"), n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16,
    v_head_dim=16, n_experts=16, experts_held=(0, 8), experts_per_tok=4,
    d_ff=32, shared_d_ff=32, dense_d_ff=96, vocab=512, attn_chunk=8,
    param_dtype="float32", compute_dtype="float32")
mesh = make_mesh((4, 1), ("data", "model"))
api = build_model(cfg)
opt = sgd(0.1, momentum=0.9)
step = make_dpsgd_train_step(api, opt, mesh, topology="ring",
                             gossip_backend="ppermute")
specs = train_state_specs(api, opt, mesh, algo="dpsgd")
shds = shd.named_shardings(train_state_shardings(specs, mesh, algo="dpsgd"),
                           mesh)
bshd = shd.named_shardings(shd.batch_sharding(api.train_batch_spec(8, 16),
                                              mesh, stacked=False), mesh)
with jax.set_mesh(mesh):
    fn = jit_train_step(step, in_shardings=(shds, bshd),
                        out_shardings=(shds, None))
    p = jax.vmap(api.init)(jax.random.split(jax.random.PRNGKey(0), 4))
    state = type(specs)(params=p, opt_state=jax.vmap(opt.init)(p),
                        step=jnp.zeros((), jnp.int32),
                        rng=jax.random.PRNGKey(1))
    state = jax.device_put(state, shds)
    tok = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 512)
    batch = jax.device_put({"tokens": tok, "labels": tok,
                            "mask": jnp.ones((8, 16))}, bshd)
    text = fn.lower(state, batch).compile().as_text()
    _, metrics = fn(state, batch)
import re
spans = {}
for op_name in re.findall(r'op_name="([^"]*)"', text):
    s = span_of(op_name)
    if s:
        spans.setdefault(s, set()).add(str(phase_of(op_name)))
print(json.dumps({"spans": {k: sorted(v) for k, v in spans.items()},
                  "metrics": {k: float(v) for k, v in metrics.items()}}))
"""


@pytest.fixture(scope="module")
def mla_moe_launch():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", MLA_MOE,
                        os.path.dirname(os.path.abspath(__file__))], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_launch_mla_moe_spans_sit_inside_fwd_bwd(mla_moe_launch):
    """The launch DPSGD step of an MLA/MoE model on four devices: its ops
    name the three model spans, inside the ``fwd_bwd`` phase and no other
    (a few ops XLA hoists or recomputes keep the span but lose the outer
    path, and so carry no phase)."""
    spans = mla_moe_launch["spans"]
    assert set(spans) == set(obs.SPANS)
    for phases in spans.values():
        assert "fwd_bwd" in phases, spans
        assert set(phases) <= {"fwd_bwd", "None"}, spans


def test_launch_dpsgd_reports_the_moe_counter(mla_moe_launch):
    """The step's metrics carry the mean over learners of the held experts'
    assignments: 2 MoE layers x 32 tokens x 4 experts, 8 of 16 held."""
    m = mla_moe_launch["metrics"]
    assert set(m) == {"loss", "moe_held_assignments"}
    assert 0 < m["moe_held_assignments"] < 2 * 32 * 4
