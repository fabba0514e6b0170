"""The DeepSeek-V3-class cell (``train.dpsgd.moonlight.ring4``) at a size
the CPU holds (``data/tiny-mla-moe.json``: d 64, 4 heads, kv rank 32,
rope/nope/v 16, 16 routed experts of which 8 are held, top-4, one shared
expert, vocabulary 512, the dense layer and 2 MoE layers):

* the program's loss and gradients against the plain reference
  (``reference_mla_moe``) on the benchmark's seeded weights;
* the parameter and operation counts against hand counts;
* the cell's whole run on four virtual CPU devices, in a child process
  (which alone can ask for four host devices): ``correct`` for the sound
  program, not for any fault planted in it, and the control apart;
* the readers of the new per-layer metrics on a run with nothing to read.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import cell as C
from bench.harness import flops_mla_moe as F
from bench.harness import mla_moe as MM
from bench.harness import reference_mla_moe as RM

TINY = C.load_json(C.BENCH / "tests" / "data" / "tiny-mla-moe.json")
FULL = C.load_json(C.BENCH / "configs" / "moonlight-16b-a3b.json")
SEED = (1 << 40) + 3


def test_program_matches_reference_loss_and_gradients():
    """Both in float32 on the CPU, where a matmul is exact float32: the
    program's blocked online-softmax attention, sorted grouped matmuls and
    scatter-add sum in other orders than the reference's full softmax and
    dense per-expert products, which moves the loss and every gradient
    leaf by about 1e-6 relative; 1e-5 leaves ten times that."""
    s = MM.share_of(TINY)
    api = MM.build(TINY)
    params = MM.make_params(api, SEED)
    t = jax.random.randint(jax.random.PRNGKey(7), (2, 33), 0, s.vocab)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:],
             "mask": jnp.ones((2, 32))}
    lp, gp = jax.value_and_grad(api.loss_fn)(params, batch)
    lr, gr = jax.value_and_grad(lambda q: RM.loss(q, batch, s))(params)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    flat_p = jax.tree_util.tree_leaves_with_path(gp)
    flat_r = jax.tree_util.tree_leaves(gr)
    for (path, a), b in zip(flat_p, flat_r):
        ref = float(jnp.linalg.norm(b))
        gap = float(jnp.linalg.norm(a - b))
        if jax.tree_util.keystr(path).endswith("['select_bias']"):
            assert ref == 0.0 and gap == 0.0     # a buffer: no gradient
        else:
            assert gap <= 1e-5 * ref, (jax.tree_util.keystr(path), gap, ref)


def test_expert_shares_add_up_to_the_uncut_reference_layer():
    """Model-configs guide section 4: the tiny layer's 16 experts split over
    two chips of 8; what each chip's program computes, with the shared
    expert (computed on both) counted once, adds up to the reference's
    layer that holds all 16.  Float32 on the CPU: 1e-5 as above."""
    import dataclasses

    from repro.models.moe import init_moe_params, moe_forward
    s = MM.share_of(TINY)
    whole = dataclasses.replace(s, held_first=0, held=s.router_experts)
    E, d = s.router_experts, s.d_model
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (E,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, d))
    kw = dict(n_experts=E, top_k=s.top_k, scoring="sigmoid",
              routed_scaling=s.routed_scaling, norm_topk=s.norm_topk)

    def layer(held):
        p = init_moe_params(jax.random.PRNGKey(3), d, s.expert_ff, E,
                            jnp.float32, held=held, shared_d_ff=s.shared_ff,
                            select_bias=True)
        return dict(p, select_bias=bias)
    half = E // 2
    a = moe_forward(layer((0, half)), x, held=(0, half), **kw)
    b = moe_forward(layer((half, half)), x, held=(half, half), **kw)
    p = layer(None)
    shared = RM._swiglu(x.reshape(-1, d), p["shared"]).reshape(x.shape)
    ref = RM.moe(x, p, whole)
    np.testing.assert_allclose(np.asarray(a + b - shared), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_parameter_count_is_the_program_layout():
    """``n_params`` by the published equations against the leaves the
    program builds: at test size, and at the cell's share (568.5M)."""
    api = MM.build(TINY)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    assert MM.n_params(MM.share_of(TINY)) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes))
    full = MM.build(FULL)
    shapes = jax.eval_shape(full.init, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert MM.n_params(MM.share_of(FULL)) == n == 568_484_608


def test_forward_flops_per_token_by_hand():
    s = MM.share_of(TINY)
    # per layer, MLA projections in multiply-adds: wq 64x4x32, wkv_a
    # 64x(32+16), wkv_b 32x4x(16+16), wo 4x16x64
    mla = 8192 + 3072 + 4096 + 4096
    # seq 3: the positions attend to 1, 2, 3 keys, 2 on average; per key
    # 4 heads x (32 q.k + 16 p.v) multiply-adds
    core = 2 * 4 * (32 + 16)
    dense = 3 * 64 * 128                 # the leading layer's SwiGLU
    moe = 64 * 16 + 3 * 64 * 32          # router over 16, shared expert
    expert = 3 * 64 * 32                 # one held (token, expert) pair
    head = 64 * 512
    want = 2 * (3 * (mla + core) + dense + 2 * moe + 1.5 * expert + head)
    assert F.forward_flops_per_token(s, 3, 1.5) == pytest.approx(want)
    assert F.train_flops_per_token(s, 3, 1.5) == pytest.approx(3 * want)
    assert F.expert_train_flops(s, 10) == pytest.approx(3 * 10 * 2 * expert)


def test_new_readers_find_nothing_without_a_trace_or_counter():
    cell = C.load_cell("train.dpsgd.moonlight.ring4")
    run = C.Run(cell=cell, window_s=1.0,
                counters={"tokens": 10, "tokens_per_step": 10, "seq": 5,
                          "learners": 1, "chips": 1,
                          "mla_moe": MM.share_of(FULL)},
                extra={"peak": {"flops": 197e12}})
    for name in ("span_ms.mla", "span_ms.moe_route", "span_ms.moe_experts",
                 "moe_experts_roofline", "mfu.train.mla_moe"):
        assert C.reader(name)(run) is None, name
    run.counters["held_assignments"] = 12.0
    assert C.reader("mfu.train.mla_moe")(run) > 0


def test_compiler_ragged_dot_kernels_count_as_moe_experts():
    """XLA's TPU compiler names its grouped-matmul kernels by themselves
    (``op_name="ragged-dot-none"``, in a v5e compile of the cell's step),
    dropping the scopes; the reader gives them the only span that has
    ragged dots."""
    from bench.harness import spans
    assert spans.span_of("ragged-dot-none") == "moe_experts"
    assert spans.span_of("ragged-dot-metadata") == "moe_experts"
    assert spans.span_of(
        "jit(train_step)/fwd_bwd/vmap(jvp(mla))/dot_general") == "mla"
    assert spans.span_of("jit(train_step)/gossip/ppermute") is None


def test_program_without_latent_attention_fails_at_once(monkeypatch):
    """The parent of the change that brought MLA has no ``attn_kind``: the
    cell's driver refuses it before any device work."""
    from repro.configs import base
    monkeypatch.delitem(base.ModelConfig.__dataclass_fields__, "attn_kind")
    with pytest.raises(RuntimeError, match="no latent attention"):
        MM.model_config(TINY)


SCRIPT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench.harness import cell as C, runner, train_mla_moe as D
import repro.launch.mesh as lm
make_mesh = lm.make_mesh


def ring_order(shape, names, devices):
    # a 2x2 host's ring does not run in device-id order
    d = list(devices)
    return make_mesh(shape, names, devices=[d[0], d[1], d[3], d[2]])
lm.make_mesh = ring_order
cell = C.load_cell("train.dpsgd.moonlight.ring4")
cell.config = C.load_json(C.BENCH / "tests" / "data" / "tiny-mla-moe.json")
cell.traffic.update(seq=16, batches=6)
d = jax.devices()[:4]
seed = (1 << 40) + 777
case = sys.argv[2]
if case == "control":
    from bench.harness import train
    out = {"program": D.calibration(cell, seed, d),
           "control": D.calibration(cell, seed, d, dtype="bfloat16")}
    out.update({k + "_correct": all(c.ok for c in train.checks(cell, v))
                for k, v in list(out.items())})
else:
    from repro.launch import train as lt
    if case == "state_unchanged":
        real = lt.make_dpsgd_train_step

        def make(*a, **kw):
            fn = real(*a, **kw)
            return lambda st, b: (st, fn(st, b)[1])
        lt.make_dpsgd_train_step = make
    elif case == "half_batch":
        from repro.models import model
        ce = model.cross_entropy

        def half(logits, labels, mask=None, **kw):
            h = logits.shape[0] // 2
            return ce(logits[:h], labels[:h],
                      None if mask is None else mask[:h], **kw)
        model.cross_entropy = half
    elif case == "no_exchange":
        lt.mix_ppermute_schedule_flat = lambda p, *a: p
    r = runner.run_cell(cell, seed=seed, seconds=1.0, trace=False,
                        devices=d, t_start=time.perf_counter(),
                        trace_dir=C.ROOT / ".bench_cache" / "test")
    out = {"correct": r.correct, "attempted": r.attempted,
           "held": r.counters["held_assignments"],
           "checks": {c.name: [c.value, c.limit] for c in r.checks}}
print(json.dumps(out))
"""


def child(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(C.ROOT), case],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_moonlight_sound_run_is_correct():
    got = child("sound")
    assert got["correct"] and got["attempted"] > 0, got
    # 2 MoE layers x 32 tokens x top-4 over 16 experts, 8 of them held
    assert 0 < got["held"] < 2 * 32 * 4, got


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_moonlight_fault_is_not_correct(fault):
    got = child(fault)
    assert not got["correct"], got


def test_moonlight_control_separates_from_the_program():
    """Through the cell's committed limits (``train.checks``): the sound
    program is correct and its bfloat16 path is not."""
    got = child("control")
    prog, ctl = got["program"], got["control"]
    assert got["program_correct"] and not got["control_correct"], got
    assert max(ctl[k] / max(prog[k], 1e-12) for k in prog) >= 10, got
    assert np.isfinite(list(ctl.values())).all()
