"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against 512 placeholder host devices, and extract the roofline
terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST run before any other import (jax locks device count on first init).

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs import ASSIGNED, SHAPES, get_config
from ..models.model import build_model
from ..models.transformer import n_periods as layer_scan_periods
from ..optim import sgd
from . import analytic, sharding as shd
from .mesh import make_production_mesh, n_learners
from .roofline import memory_summary, roofline_from_compiled
from .train import (jit_train_step, make_decode_step, make_dpsgd_train_step,
                    make_prefill_step, make_ssgd_train_step,
                    train_state_shardings, train_state_specs)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# (arch, shape) pairs that are skipped by design — see DESIGN.md §5
SKIPS = {
    ("seamless-m4t-large-v2", "long_500k"):
        "enc-dec speech model: 500k-token decode has no meaningful analogue",
}


def _decode_buf_len(cfg, seq_len: int) -> int:
    # long-context serving always uses the sliding-window variant (rotating
    # buffer of `window`); shorter decode keeps the full context.
    if seq_len > 65536:
        return min(seq_len, cfg.window)
    return seq_len


def build_lowered(arch: str, shape: str, *, multi_pod: bool, algo: str,
                  backend: str, extra: dict | None = None):
    cfg = get_config(arch)
    if extra:
        import dataclasses
        cfg = dataclasses.replace(cfg, **extra)
    seq_len, global_batch, kind = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    api = build_model(cfg)

    if kind == "train":
        opt = sgd(lr=0.1, momentum=0.9)
        state_specs = train_state_specs(api, opt, mesh, algo=algo)
        state_shd = train_state_shardings(state_specs, mesh, algo=algo)
        batch_specs = api.train_batch_spec(global_batch, seq_len)
        batch_shd = shd.batch_sharding(batch_specs, mesh, stacked=False)
        if algo == "dpsgd":
            step = make_dpsgd_train_step(api, opt, mesh,
                                         gossip_backend=backend)
        else:
            step = make_ssgd_train_step(api, opt, mesh)
        with jax.set_mesh(mesh):
            lowered = jit_train_step(
                step,
                in_shardings=shd.named_shardings((state_shd, batch_shd), mesh),
                out_shardings=shd.named_shardings((state_shd, None), mesh),
            ).lower(state_specs, batch_specs)
        n_tokens = global_batch * seq_len
        model_flops = 6.0 * cfg.n_active_params() * n_tokens
        return lowered, mesh, model_flops

    if kind == "prefill":
        params_specs = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        params_shd = shd.params_sharding(params_specs, mesh, stacked=False)
        batch_specs = api.train_batch_spec(global_batch, seq_len)
        batch_shd = shd.batch_sharding(batch_specs, mesh, stacked=False)
        step = make_prefill_step(api)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=shd.named_shardings((params_shd, batch_shd), mesh),
            ).lower(params_specs, batch_specs)
        model_flops = 2.0 * cfg.n_active_params() * global_batch * seq_len
        return lowered, mesh, model_flops

    # decode
    params_specs = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    params_shd = shd.params_sharding(params_specs, mesh, stacked=False)
    buf_len = _decode_buf_len(cfg, seq_len)
    if cfg.family == "audio":
        enc_len = 4096  # fixed stub audio memory
        frames_spec = jax.ShapeDtypeStruct(
            (global_batch, enc_len, cfg.d_model), jnp.bfloat16
            if cfg.param_dtype == "bfloat16" else jnp.float32)
        cache_specs = jax.eval_shape(
            lambda p, f: api.init_cache(p, f, buf_len), params_specs,
            frames_spec)
    else:
        cache_specs = jax.eval_shape(
            lambda: api.init_cache(None, global_batch, buf_len))
    cache_shd = shd.cache_sharding(cache_specs, mesh)
    tok_spec = jax.ShapeDtypeStruct((global_batch, 1), jnp.int32)
    tok_shd = shd.batch_sharding(tok_spec, mesh, stacked=False)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_decode_step(api)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            step,
            in_shardings=shd.named_shardings(
                (params_shd, cache_shd, tok_shd, P()), mesh),
            out_shardings=shd.named_shardings((None, cache_shd), mesh),
        ).lower(params_specs, cache_specs, tok_spec, pos_spec)
    model_flops = 2.0 * cfg.n_active_params() * global_batch
    return lowered, mesh, model_flops


def run_one(arch: str, shape: str, *, multi_pod: bool, algo: str = "dpsgd",
            backend: str = "einsum", outdir: str = RESULTS_DIR,
            tag: str = "", extra: dict | None = None) -> dict:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    name = f"{arch}__{shape}__{mesh_name}__{algo}__{backend}"
    if tag:
        name += f"__{tag}"
    if (arch, shape) in SKIPS:
        rec = {"name": name, "status": "skipped",
               "reason": SKIPS[(arch, shape)]}
        _write(outdir, name, rec)
        print(json.dumps(rec))
        return rec

    t0 = time.time()
    try:
        lowered, mesh, model_flops = build_lowered(
            arch, shape, multi_pod=multi_pod, algo=algo, backend=backend,
            extra=extra)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        n_chips = 512 if multi_pod else 256
        cfg = get_config(arch)
        if extra:
            import dataclasses
            cfg = dataclasses.replace(cfg, **extra)
        seq_len, global_batch, kind = SHAPES[shape]
        L = n_learners(mesh)
        trip = cfg.n_layers if cfg.family == "audio" \
            else layer_scan_periods(cfg)
        if kind == "train":
            a_flops = analytic.train_flops_per_chip(cfg, global_batch,
                                                    seq_len, n_chips)
            a_bytes = analytic.train_bytes_per_chip(
                cfg, global_batch, seq_len, n_chips, L)
        elif kind == "prefill":
            a_flops = analytic.prefill_flops_per_chip(cfg, global_batch,
                                                      seq_len, n_chips)
            a_bytes = analytic.prefill_bytes_per_chip(cfg, global_batch,
                                                      seq_len, n_chips)
        else:
            capped = seq_len > 65536
            a_flops = analytic.decode_flops_per_chip(
                cfg, global_batch, seq_len, n_chips, window_capped=capped)
            a_bytes = analytic.decode_bytes_per_chip(
                cfg, global_batch, seq_len, n_chips, window_capped=capped)
        rl = roofline_from_compiled(compiled, body_trip_count=trip,
                                    analytic_flops=a_flops,
                                    analytic_bytes=a_bytes)
        mem = memory_summary(compiled)
        summ = rl.summary()
        rec = {
            "name": name, "status": "ok", "arch": arch, "shape": shape,
            "mesh": mesh_name, "algo": algo, "backend": backend,
            "n_chips": n_chips,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "roofline": summ,
            "memory": mem,
            "model_flops_total": model_flops,
            "model_flops_per_chip": model_flops / n_chips,
            "useful_flops_ratio": (model_flops / n_chips) / max(summ["flops"],
                                                                1.0),
            "collectives_top": sorted(rl.collectives,
                                      key=lambda c: -c["link_bytes"])[:10],
        }
    except Exception as e:  # noqa: BLE001 — a dry-run failure IS the signal
        rec = {"name": name, "status": "error", "arch": arch, "shape": shape,
               "mesh": mesh_name, "algo": algo, "backend": backend,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    _write(outdir, name, rec)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("collectives_top", "traceback")}, indent=1))
    return rec


def _write(outdir, name, rec):
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--algo", default="dpsgd", choices=["dpsgd", "ssgd"])
    ap.add_argument("--backend", default="einsum",
                    choices=["einsum", "ppermute"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--outdir", default=RESULTS_DIR)
    args = ap.parse_args()

    combos = []
    if args.all:
        for arch in ASSIGNED:
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape
        combos = [(args.arch, args.shape)]

    for arch, shape in combos:
        run_one(arch, shape, multi_pod=(args.mesh == "multi"),
                algo=args.algo, backend=args.backend, outdir=args.outdir,
                tag=args.tag)


if __name__ == "__main__":
    main()
