#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU, at transformer-100m's widths.

    python3 chip_smoke.py              # one chip: DPSGD training + serving
    python3 chip_smoke.py --chips 4    # four chips: launch-path gossip only

Phases, all in this one process (a chip belongs to one process at a time):

* train  -- ``examples/train_100m.py``'s trainer: DPSGD, 4 learners stacked
  on the chip, the flat engine with the fused Pallas gossip+SGD kernel,
  seq 512.  A few steps; the loss must be finite and the kernel must match
  its jnp oracle on a slice of the live learner store.
* serve  -- ``examples/serve_batched.py``'s ServeEngine: 8 requests of mixed
  prompt length over 4 slots, 32 new tokens each, through the Pallas paged
  decode kernel.  Every request must finish, and its greedy tokens must
  agree with the full-sequence forward pass (teacher forcing).
* gossip4 (``--chips 4``) -- ``launch/train.py``'s DPSGD step on a
  (data=4, model=1) mesh, ring gossip by collective-permute against the
  einsum reference from one init and one batch stream, plus AD-PSGD ticks.

Without a TPU the script exits with status 2 and prints no result.  Any
failed phase raises, so the exit status is non-zero.  The last line of
standard output is the JSON result, and nothing else is printed after it.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "transformer-100m"


def log(msg: str) -> None:
    print(msg, flush=True)


def _example(name: str):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "examples" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# train: the paper's one-chip form, n learners stacked on one device
# ---------------------------------------------------------------------------

def train_phase(preset: str = "full", *, learners: int = 4,
                local_batch: int = 2, seq: int = 512, steps: int = 5) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops

    cfg, api, loader, trainer, state = _example("train_100m").build(
        preset, learners=learners, local_batch=local_batch, seq=seq)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(api.init, jax.random.PRNGKey(0))))
    assert trainer.is_flat, "DPSGD did not resolve to the flat engine"
    log(f"train: {cfg.name} params={n_params} learners={learners} "
        f"local_batch={local_batch} seq={seq} flat_rows={state.params.shape}")

    batch = loader.batch(0)
    t0 = time.perf_counter()
    step = trainer.train_step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    kernel = _has_kernel(step)
    log(f"train: compile_s={compile_s:.3f} tpu_custom_call={kernel}")

    losses, times = [], []
    for i in range(steps):
        batch = loader.batch(i)
        jax.block_until_ready(batch)
        t0 = time.perf_counter()
        state, m = step(state, batch)
        jax.block_until_ready(state.params)
        times.append(time.perf_counter() - t0)
        losses.append(float(m.loss))
    warm = times[1:] or times
    ms = 1e3 * sum(warm) / len(warm)
    log(f"train: losses={losses}")
    log(f"train: step_ms_after_warmup={ms:.3f} first_step_ms="
        f"{1e3 * times[0]:.3f}")
    assert all(np.isfinite(losses)), losses

    # the fused kernel against its jnp oracle on a slice of the live store
    rows = min(8192, state.params.shape[1])
    w = state.params[:, :rows]
    key = jax.random.PRNGKey(7)
    g = 1e-2 * jax.random.normal(key, w.shape, jnp.float32)
    mu = 1e-2 * jax.random.normal(jax.random.fold_in(key, 1), w.shape,
                                  jnp.float32)
    n = w.shape[0]
    partners = jnp.asarray([[i ^ 1 for i in range(n)]], jnp.int32)
    coefs = jnp.tile(jnp.asarray([[0.5, 0.5, 1.0, 1.0]], jnp.float32), (n, 1))
    got = kops.flat_gossip_update(w, w, g, mu, partners, coefs, lr=0.1,
                                  beta=0.9, backend="pallas")
    want = kops.flat_gossip_update(w, w, g, mu, partners, coefs, lr=0.1,
                                   beta=0.9, backend="ref")
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
    log(f"train: gossip kernel vs oracle max_abs_err={err:.3e} "
        f"on ({n}, {rows}, 128)")
    assert err <= 1e-6, err
    return dict(kernel=kernel, compile_s=compile_s, step_ms=ms,
                losses=losses)


# ---------------------------------------------------------------------------
# serve: continuous batching over the paged KV cache
# ---------------------------------------------------------------------------

def serve_phase(preset: str = "full", *, slots: int = 4, requests: int = 8,
                new_tokens: int = 32, page: int = 16, buf: int = 64) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops

    sb = _example("serve_batched")
    eng = sb.build_engine(ARCH, preset, slots=slots, page=page, buf=buf)
    api, cfg = eng.api, eng.api.cfg
    reqs = sb.submit_mixed(eng, requests, new_tokens)
    log(f"serve: {cfg.name} slots={slots} page={page} buf={eng.max_len} "
        f"prompt_lens={[len(r.prompt) for r in reqs]}")

    S = eng.n_slots
    step_args = (eng.params, eng.cache, jnp.zeros((S, 1), jnp.int32),
                 jnp.zeros((S,), jnp.int32), jnp.asarray(eng.page_table),
                 jnp.zeros((S,), bool))
    t0 = time.perf_counter()
    kernel = _has_kernel(jax.jit(api.paged_decode_step).lower(
        *step_args).compile())
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.warmup()
    log(f"serve: compile_s={compile_s:.3f} tpu_custom_call={kernel} "
        f"warmup_s={time.perf_counter() - t0:.3f}")

    t0 = time.perf_counter()
    eng.run()
    run_s = time.perf_counter() - t0
    finished = sum(r.done for r in reqs)
    log(f"serve: finished={finished}/{len(reqs)} "
        f"tokens={eng.generated_total} steps={eng.real_steps} "
        f"step_ms={1e3 * run_s / max(eng.real_steps, 1):.3f}")
    assert finished == len(reqs), finished
    assert all(len(r.generated) == new_tokens for r in reqs)
    assert eng.generated_total == requests * new_tokens

    # teacher forcing: the full forward pass over prompt + answer must pick
    # the tokens the engine generated one step at a time
    L = eng.max_len
    toks = np.zeros((len(reqs), L), np.int32)
    for i, r in enumerate(reqs):
        seq = r.prompt + r.generated
        toks[i, :len(seq)] = seq
    logits = jax.jit(api.apply)(eng.params, {"tokens": jnp.asarray(toks)})
    pred = np.asarray(jnp.argmax(logits[..., :cfg.vocab], axis=-1))
    agree = total = 0
    for i, r in enumerate(reqs):
        p = len(r.prompt)
        agree += int((pred[i, p - 1:p - 1 + new_tokens]
                      == np.asarray(r.generated)).sum())
        total += new_tokens
    log(f"serve: greedy tokens agreeing with the forward pass: "
        f"{agree}/{total}")
    assert agree >= 0.9 * total, (agree, total)

    # the paged kernel against its jnp oracle at the model's head widths
    KV, hd, H = cfg.n_kv_heads, cfg.head_dim_, cfg.n_heads
    max_pages, n_pool = eng.max_pages, 1 + slots * eng.max_pages
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (slots, H, hd))
    kp = jax.random.normal(ks[1], (n_pool, KV, page, hd))
    vp = jax.random.normal(ks[2], (n_pool, KV, page, hd))
    table = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n_pool)).reshape(slots, max_pages), jnp.int32)
    lengths = jnp.asarray(np.linspace(1, L, slots).astype(np.int32))
    got = kops.paged_decode_attention(q, kp, vp, table, lengths,
                                      backend="pallas")
    with jax.default_matmul_precision("highest"):
        want = kops.paged_decode_attention(q, kp, vp, table, lengths,
                                           backend="ref")
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    log(f"serve: paged kernel vs oracle max_abs_err={err:.3e} "
        f"(max |out| {scale:.3e})")
    assert err <= 1e-2 * scale, (err, scale)
    return dict(kernel=kernel, compile_s=compile_s, finished=finished)


# ---------------------------------------------------------------------------
# gossip4: the launch path, learners = chips, gossip = collective-permute
# ---------------------------------------------------------------------------

def gossip4_phase(preset: str = "full", *, local_batch: int = 2,
                  seq: int = 512, steps: int = 3, ticks: int = 4) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.analysis.targets import live_slots
    from repro.configs import get_config
    from repro.core.schedule import make_schedule
    from repro.data import SyntheticTokenStream
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (jit_train_step, make_adpsgd_train_step,
                                    make_dpsgd_train_step, train_state_specs,
                                    train_state_shardings)
    from repro.models.model import build_model
    from repro.optim import sgd

    devices = jax.devices()
    L = len(devices)
    mesh = make_mesh((L, 1), ("data", "model"))
    cfg = get_config(ARCH)
    if preset == "smoke":
        cfg = cfg.smoke_config()
    api = build_model(cfg)
    opt = sgd(0.1, momentum=0.9)
    GB = L * local_batch
    ds = SyntheticTokenStream(vocab=cfg.vocab)
    bspecs = api.train_batch_spec(GB, seq)
    bshd = shd.named_shardings(
        shd.batch_sharding(bspecs, mesh, stacked=False), mesh)
    sample = jax.jit(lambda k: ds.sample(k, GB, seq), out_shardings=bshd)
    batches = [sample(jax.random.PRNGKey(100 + i))
               for i in range(max(steps, ticks))]

    def init_state(algo):
        specs = train_state_specs(api, opt, mesh, algo=algo)
        shds = shd.named_shardings(
            train_state_shardings(specs, mesh, algo=algo), mesh)

        def make(key):          # distinct init per learner, made in place
            params = jax.vmap(api.init)(jax.random.split(key, L))
            extra = {}
            if algo == "adpsgd":
                extra = dict(buffer=jax.tree_util.tree_map(jnp.copy, params),
                             age=jnp.zeros((L,), jnp.int32))
            return type(specs)(params=params,
                               opt_state=jax.vmap(opt.init)(params),
                               step=jnp.zeros((), jnp.int32),
                               rng=jax.random.PRNGKey(1), **extra)
        return jax.jit(make, out_shardings=shds)(jax.random.PRNGKey(0)), shds

    def placement(params):
        """Each learner's rows on its own chip, and nothing sizable held
        by device 0 alone."""
        leaves = jax.tree_util.tree_leaves(params)
        for leaf in leaves:
            owner = {}
            for sh in leaf.addressable_shards:
                assert sh.data.shape[0] == 1, sh.data.shape
                owner[sh.index[0].start] = sh.device.id
            assert sorted(owner) == list(range(L)), owner
            assert len(set(owner.values())) == L, owner
        # a shard's ``.data`` is a one-device view of the sharded buffer,
        # not a copy: only arrays whose buffer no sharded array owns count
        live = jax.live_arrays()
        views = {sh.data.unsafe_buffer_pointer() for a in live
                 if len(a.sharding.device_set) > 1
                 for sh in a.addressable_shards}
        solo = [a.shape for a in jax.live_arrays()
                if a.sharding.device_set == {devices[0]}
                and a.nbytes > 1 << 20
                and a.unsafe_buffer_pointer() not in views]
        assert not solo, f"arrays on device 0 alone: {solo}"
        used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        log(f"gossip4: bytes_in_use per chip={used}")
        if None not in used:     # no chip holds half a learner more
            learner = sum(x.nbytes for x in leaves) // L
            assert max(used) - min(used) < learner // 2, (used, learner)

    state0, shds = init_state("dpsgd")
    placement(state0.params)
    finals, losses = {}, {}
    for backend in ("ppermute", "einsum"):
        fn = make_dpsgd_train_step(api, opt, mesh, topology="ring",
                                   gossip_backend=backend)
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            step = jit_train_step(
                fn, in_shardings=(shds, bshd),
                out_shardings=(shds, None)).lower(state0, batches[0]).compile()
        compile_s = time.perf_counter() - t0
        hlo = step.as_text()
        n_perm = len(re.findall(r"collective-permute(?:-start)?\(", hlo))
        state = jax.tree_util.tree_map(jnp.copy, state0)
        ls, times = [], []
        for i in range(steps):
            t0 = time.perf_counter()
            state, m = step(state, batches[i])
            jax.block_until_ready(state.params)
            times.append(time.perf_counter() - t0)
            ls.append(float(m["loss"]))
        warm = times[1:] or times
        log(f"gossip4: dpsgd[{backend}] compile_s={compile_s:.3f} "
            f"collective_permutes={n_perm} losses={ls} step_ms_after_warmup="
            f"{1e3 * sum(warm) / len(warm):.3f}")
        if backend == "ppermute":
            want = live_slots(make_schedule("ring", L))
            assert n_perm == want, (n_perm, want)
            placement(state.params)
        finals[backend], losses[backend] = state.params, ls
        assert all(np.isfinite(ls)), ls

    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(finals["ppermute"]),
        jax.tree_util.tree_leaves(finals["einsum"])))
    scale = max(float(jnp.max(jnp.abs(a)))
                for a in jax.tree_util.tree_leaves(finals["einsum"]))
    log(f"gossip4: ppermute vs einsum max_param_diff={diff:.3e} "
        f"(max |param| {scale:.3e}) after {steps} steps")
    assert diff <= 1e-3 * scale, (diff, scale)

    state, ashds = init_state("adpsgd")
    fn = make_adpsgd_train_step(api, opt, mesh, max_staleness=2,
                                slow_learner=0, slow_factor=3)
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        step = jit_train_step(
            fn, in_shardings=(ashds, bshd),
            out_shardings=(ashds, None)).lower(state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    n_perm = len(re.findall(r"collective-permute(?:-start)?\(",
                            step.as_text()))
    ls, ages = [], []
    for i in range(ticks):
        state, m = step(state, batches[i])
        ls.append(float(m["loss"]))
        ages.append(int(m["staleness_max"]))
    log(f"gossip4: adpsgd compile_s={compile_s:.3f} "
        f"collective_permutes={n_perm} losses={ls} staleness_max={ages}")
    assert all(np.isfinite(ls)), ls
    placement(state.params)
    return dict(diff=diff, losses=losses)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip launch-path gossip")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    log(f"device_kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} compile_cache={enable_compile_cache()}")

    if args.chips == 4:
        gossip4_phase()
    else:
        tr = train_phase()
        assert tr["kernel"], "no Pallas kernel in the compiled train step"
        sv = serve_phase()
        assert sv["kernel"], "no Pallas kernel in the compiled decode step"
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
