"""Main-path Pallas kernels compile for a described TPU v5e chip.

Interpret mode and the jnp oracles cannot see Mosaic's block-shape rules
(last two block dims divisible by (8, 128) or equal to the array's), so
each kernel of the training and serving hot paths is compiled here at
transformer-100m shapes against a described ``v5e:2x2`` topology — no chip
is attached; nothing runs.  Each compiled call must carry its kernel's
``name=``, the op name a profiler trace shows.  The topology is described inside a fixture,
never at import or collection: only one process at a time may load the
TPU compiler library, and the workers of a parallel test run all import
this file.  The persistent compile cache is off around these compiles (an
entry written for a described device cannot be read back without one).
"""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.flatstate import flat_meta  # noqa: E402
from repro.kernels.causal_flash import (causal_flash_dkv,  # noqa: E402
                                        causal_flash_dq, causal_flash_fwd)
from repro.kernels.decode_attention import \
    paged_decode_attention_fwd  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.kernels.gossip_mix import (LANE, flat_block_rows,  # noqa
                                     gossip_mix_update,
                                     gossip_mix_update_flat)
from repro.kernels.reorth import reorth_axpy, reorth_dots  # noqa: E402
from repro.models.model import build_model  # noqa: E402

N_LEARNERS = 4


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture(scope="module")
def cfg():
    return get_config("transformer-100m")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name):
    """The kernel compiled to a Mosaic call that carries its stable name
    (the trace's op name, which the benchmark's traffic files match)."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{name}." in text, name


@pytest.mark.parametrize("K,publish", [(1, False), (2, False), (1, True)],
                         ids=["K1", "K2", "publish"])
def test_gossip_mix_update_flat_compiles(one_chip, cfg, K, publish):
    """The flat engine's fused gossip+SGD step at the learner store of
    transformer-100m (n=4, T=1,056,920 rows of 128): pair matching, ring,
    and the AD-PSGD publish mode.  T has no 8-aligned divisor between 40
    and T, so the blocks leave a partial last one; they are large, and
    their double buffers fit the scoped VMEM the compile allows."""
    T = flat_meta(jax.eval_shape(build_model(cfg).init,
                                 jax.random.PRNGKey(0))).rows
    assert T == 1_056_920
    rows = flat_block_rows(T, K, has_momentum=True, publish=publish)
    assert rows >= 1024 and rows % 8 == 0 and T % rows, rows
    n = N_LEARNERS
    big = _spec((n, T, LANE), jnp.float32, one_chip)
    partners = _spec((K, n), jnp.int32, one_chip)
    coefs = _spec((n, K + (5 if publish else 3)), jnp.float32, one_chip)
    buffer = big if publish else None
    compiled = jax.jit(
        lambda w, g, mu, p, c, *buf: gossip_mix_update_flat(
            w, w, g, mu, p, c, lr=0.1, beta=0.9,
            buffer=buf[0] if buf else None)
    ).lower(big, big, big, partners, coefs,
            *((buffer,) if publish else ())).compile()
    _assert_kernel(compiled, "gossip_mix_update_flat")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_decode_attention_compiles(one_chip, cfg, dtype):
    """Serving decode over the head-major paged pool at transformer-100m's
    heads (KV=12, hd=64): 4 slots, 16-token pages, 8 pages per slot."""
    S, page, max_pages = 4, 16, 8
    KV, hd, H = cfg.n_kv_heads, cfg.head_dim_, cfg.n_heads
    assert (KV, hd) == (12, 64)
    P = 1 + S * max_pages
    q = _spec((S, H, hd), dtype, one_chip)
    pool = _spec((P, KV, page, hd), dtype, one_chip)
    table = _spec((S, max_pages), jnp.int32, one_chip)
    lengths = _spec((S,), jnp.int32, one_chip)
    compiled = jax.jit(paged_decode_attention_fwd).lower(
        q, pool, pool, table, lengths).compile()
    _assert_kernel(compiled, "paged_decode_attention_fwd")


def test_flash_attention_fwd_compiles(one_chip, cfg):
    """Training/prefill attention at seq 512 with transformer-100m heads."""
    B, S = 2, 512
    q = _spec((B, cfg.n_heads, S, cfg.head_dim_), jnp.float32, one_chip)
    kv = _spec((B, cfg.n_kv_heads, S, cfg.head_dim_), jnp.float32, one_chip)
    compiled = jax.jit(flash_attention_fwd).lower(q, kv, kv).compile()
    _assert_kernel(compiled, "flash_attention_fwd")


def _mosaic_calls(text):
    """(instruction name, op_name) of each Mosaic call in compiled HLO."""
    import re
    return [(re.search(r"%(\S+) = ", line).group(1),
             re.search(r'op_name="([^"]*)"', line).group(1))
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("kernel", ["causal_flash_fwd", "causal_flash_dq",
                                    "causal_flash_dkv"])
def test_causal_flash_compiles_at_moonlight_shapes(one_chip, kernel):
    """Latent attention's kernels at Moonlight-16B-A3B's training shapes:
    batch 2, 16 heads, seq 8,192, q/k 192 wide, v 128, float32."""
    B, H, S = 2, 16, 8192
    qk = _spec((B, H, S, 192), jnp.float32, one_chip)
    v = _spec((B, H, S, 128), jnp.float32, one_chip)
    row = _spec((B, H, 1, S), jnp.float32, one_chip)
    fn, args = {"causal_flash_fwd": (causal_flash_fwd, (qk, qk, v)),
                "causal_flash_dq": (causal_flash_dq, (qk, qk, v, v, row, row)),
                "causal_flash_dkv": (causal_flash_dkv,
                                     (qk, qk, v, v, row, row))}[kernel]
    _assert_kernel(jax.jit(fn).lower(*args).compile(), kernel)


def test_mla_grad_runs_the_kernels_inside_its_span(one_chip, monkeypatch):
    """``jax.grad`` of ``mla_forward`` as the TPU compiles it (small
    widths, seq 256): the core is the forward kernel and both backward
    kernels, and each call's op_name maps to the ``mla`` span, so that
    ``span_ms.mla`` keeps counting the attention."""
    import dataclasses

    from repro.kernels import ops
    from repro.models.mla import init_mla_params, mla_forward
    from repro.obs import span_of
    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), d_model=64,
                              n_heads=4, kv_lora_rank=32,
                              qk_nope_head_dim=16, qk_rope_head_dim=16,
                              v_head_dim=16)
    S = 256
    p = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init_mla_params(jax.random.PRNGKey(0), cfg,
                                               jnp.float32)))
    x = _spec((2, S, cfg.d_model), jnp.float32, one_chip)

    def loss(p, x):
        return jnp.sum(mla_forward(p, x, cfg, jnp.arange(S)) ** 2)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile(
    ).as_text()
    calls = _mosaic_calls(text)
    assert sorted(n.split(".")[0] for n, _ in calls) == [
        "causal_flash_dkv", "causal_flash_dq", "causal_flash_fwd"]
    assert {span_of(op) for _, op in calls} == {"mla"}, calls


def test_gossip_mix_update_compiles(one_chip):
    """The single-learner gossip + momentum kernel, pair gossip (K=1)."""
    T = 4096
    one = _spec((T, LANE), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda w, nbr, g, mu, c: gossip_mix_update(w, nbr, g, mu, c, lr=0.1)
    ).lower(one, _spec((1, T, LANE), jnp.float32, one_chip), one, one,
            _spec((2,), jnp.float32, one_chip)).compile()
    _assert_kernel(compiled, "gossip_mix_update")


@pytest.mark.parametrize("kernel", ["reorth_dots", "reorth_axpy"])
def test_reorth_compiles(one_chip, kernel):
    """The landscape probe's Lanczos reorthogonalization passes over an
    8-vector basis."""
    M, T = 8, 4096
    basis = _spec((M, T, LANE), jnp.float32, one_chip)
    w = _spec((T, LANE), jnp.float32, one_chip)
    if kernel == "reorth_dots":
        lowered = jax.jit(reorth_dots).lower(basis, w)
    else:
        lowered = jax.jit(reorth_axpy).lower(
            w, basis, _spec((M,), jnp.float32, one_chip))
    _assert_kernel(lowered.compile(), kernel)


def test_launch_ring_step_phases_on_four_chips(v5e_2x2):
    """The launch DPSGD step with ring gossip by collective-permute,
    compiled for the four described chips (ordered as a 2x2 host's ring)
    at test size: every matrix product (a ``convolution`` on the TPU) is
    ``fwd_bwd``, the permutes are ``gossip``, and all four phases appear."""
    from hlo_text import instructions
    from hlo_text import phases as hlo_phases

    from repro import obs
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (jit_train_step, make_dpsgd_train_step,
                                    train_state_shardings, train_state_specs)
    from repro.optim import sgd
    d = v5e_2x2
    mesh = make_mesh((4, 1), ("data", "model"),
                     devices=[d[0], d[1], d[3], d[2]])
    api = build_model(get_config("transformer-100m").smoke_config())
    opt = sgd(0.1, momentum=0.9)
    step = make_dpsgd_train_step(api, opt, mesh, topology="ring",
                                 gossip_backend="ppermute")
    specs = train_state_specs(api, opt, mesh, algo="dpsgd")
    shds = shd.named_shardings(
        train_state_shardings(specs, mesh, algo="dpsgd"), mesh)
    bspecs = api.train_batch_spec(8, 32)
    bshd = shd.named_shardings(
        shd.batch_sharding(bspecs, mesh, stacked=False), mesh)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: _spec(a.shape, a.dtype, s), tree, shardings)
    with jax.set_mesh(mesh):
        text = jit_train_step(
            step, in_shardings=(shds, bshd), out_shardings=(shds, None)
        ).lower(placed(specs, shds), placed(bspecs, bshd)).compile(
        ).as_text()
    phases = hlo_phases(text)
    ops = instructions(text)
    convs = {phases.get(n) for n, op in ops if op == "convolution"}
    permutes = {phases.get(n) for n, op in ops
                if op.startswith("collective-permute")}
    assert convs == {"fwd_bwd"} and permutes == {"gossip"}
    assert set(phases.values()) == set(obs.PHASES)


def test_moonlight_share_launch_step_fits_a_v5e(v5e_2x2, monkeypatch):
    """One chip's share of Moonlight-16B-A3B (the benchmark cell's: the
    dense layer and 4 MoE layers of 8 held experts, a 20,480-row vocabulary
    slice, float32) in the launch DPSGD step with ring gossip, one learner
    per described chip, at local batch 2 x 8,192, as the TPU runs it: it
    compiles within a v5e's 16 GB, the held experts run as the TPU's
    grouped matmul, latent attention as the causal flash kernels inside
    the ``mla`` span, and the model spans are in the compiled step."""
    import re

    from bench.harness import cell, mla_moe
    from repro.kernels import ops
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (jit_train_step, make_dpsgd_train_step,
                                    train_state_shardings, train_state_specs)
    from repro.obs import SPANS, span_of
    from repro.optim import sgd
    monkeypatch.setattr(ops, "on_cpu", lambda: False)
    d = v5e_2x2
    mesh = make_mesh((4, 1), ("data", "model"),
                     devices=[d[0], d[1], d[3], d[2]])
    api = mla_moe.build(cell.load_json(
        cell.BENCH / "configs" / "moonlight-16b-a3b.json"))
    opt = sgd(0.5, momentum=0.9)
    step = make_dpsgd_train_step(api, opt, mesh, topology="ring",
                                 gossip_backend="ppermute")
    specs = train_state_specs(api, opt, mesh, algo="dpsgd")
    shds = shd.named_shardings(
        train_state_shardings(specs, mesh, algo="dpsgd"), mesh)
    bspecs = api.train_batch_spec(4 * 2, 8192)
    bshd = shd.named_shardings(
        shd.batch_sharding(bspecs, mesh, stacked=False), mesh)

    def placed(tree, shardings):
        return jax.tree_util.tree_map(
            lambda a, s: _spec(a.shape, a.dtype, s), tree, shardings)
    with jax.set_mesh(mesh):
        compiled = jit_train_step(
            step, in_shardings=(shds, bshd), out_shardings=(shds, None)
        ).lower(placed(specs, shds), placed(bspecs, bshd)).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < 16e9
    text = compiled.as_text()
    assert "ragged-dot" in text
    found = {span_of(n) for n in re.findall(r'op_name="([^"]*)"', text)}
    assert set(SPANS) <= found
    calls = [(n, op) for n, op in _mosaic_calls(text)
             if n.startswith("causal_flash")]
    assert {n.split(".")[0] for n, _ in calls} == {
        "causal_flash_fwd", "causal_flash_dq", "causal_flash_dkv"}
    assert {span_of(op) for _, op in calls} == {"mla"}, calls
