"""shard_map MoE backend == einsum-dispatch oracle on a small forced-device
mesh (subprocess: needs its own XLA device count)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.models.moe import init_moe_params, moe_forward
from repro.models.moe_shardmap import moe_forward_shardmap

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(2, 4)
E, k, d, f = 8, 2, 16, 32
B, S = 4, 8
key = jax.random.PRNGKey(0)
params = init_moe_params(key, d, f, E, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d))

# big capacity so neither backend drops -> outputs must match exactly
with jax.set_mesh(mesh):
    y_sm = jax.jit(lambda p, xx: moe_forward_shardmap(
        p, xx, n_experts=E, top_k=k, capacity_factor=64.0))(params, x)
y_ref = moe_forward(params, x, n_experts=E, top_k=k, capacity_factor=64.0)
err = float(jnp.abs(y_sm - y_ref).max())

# gradient path
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(lambda p, xx: jnp.sum(moe_forward_shardmap(
        p, xx, n_experts=E, top_k=k, capacity_factor=64.0) ** 2)))(params, x)
gnorm = float(sum(jnp.abs(l).sum() for l in jax.tree_util.tree_leaves(g)))
print(json.dumps({"err": err, "gnorm_finite": bool(np.isfinite(gnorm)),
                  "g_router": float(jnp.abs(g["router"]).sum())}))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_matches_einsum_oracle(result):
    assert result["err"] < 1e-4, result


def test_grads_flow(result):
    assert result["gnorm_finite"]
    assert result["g_router"] > 0
