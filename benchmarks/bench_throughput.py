"""Engine regression harness (App. F + DESIGN §11).

Measures the REAL research-trainer hot path per algorithm, old vs new:

  * pytree — the reference engine: stacked pytrees, unfused tree_map
    updates, one host dispatch per step (how the repo trained before PR 3);
  * flat   — the flat-state engine: persistent (n, T, 128) store, batched
    fused gossip kernel, ``run_steps`` lax.scan driver with state donation.

Emits ``results/bench/BENCH_PR3.json`` with us/step and tokens/s per
(algo, engine) plus the traced-step concatenate audit, and the usual CSV
table.  ``make bench-check`` gates on it via benchmarks.check_regression:
the flat engine must not regress past the pytree path beyond the measured
CPU parity-noise band on this smoke config, the fused kernel must actually
dispatch, and the traced step must stay free of parameter-sized
concatenates.  The derived production collective volume per gossip backend
(roofline model, App. F) is carried along in the JSON for context.

``measure_cell`` is the single-engine unit benchmarks.matrix reuses as
its ``throughput`` workload plugin; the emitted BENCH_PR3.json is the v1
payload the schema's legacy adapter keeps aligned with matrix cells
(DESIGN §13).  ``--smoke`` shortens the paired run.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import AlgoConfig, MultiLearnerTrainer
from repro.core.flatstate import max_concat_elems
from repro.data import ShardedLoader, TemplateImages
from repro.launch.analytic import gossip_link_bytes_per_chip
from repro.models import fcnet
from repro.optim import sgd

from .common import parse_smoke, write_table
from .schema import results_dir

# smoke config: the paper's FC net / learner count at CPU scale.
# CHUNK x CHUNKS steps per engine, interleaved chunkwise (below).
N, LOCAL_BATCH, LR, CHUNK, CHUNKS = 5, 400, 0.1, 6, 16
STEPS = CHUNK * CHUNKS
ALGOS = ("ssgd", "dpsgd", "adpsgd")
ALGO_KW = {"adpsgd": dict(max_staleness=4, slow_learner=0, slow_factor=3)}


def _make(algo: str, engine: str) -> MultiLearnerTrainer:
    return MultiLearnerTrainer(
        fcnet.loss_fn, sgd(LR, momentum=0.9),
        AlgoConfig(algo=algo, topology="random_pair", n_learners=N,
                   **ALGO_KW.get(algo, {})),
        engine=engine)


def _workload_inputs(chunk: int):
    loader = ShardedLoader(TemplateImages(), n_learners=N,
                           local_batch=LOCAL_BATCH, seed=0)
    params = fcnet.init_params(jax.random.PRNGKey(0), in_dim=784, hidden=50)
    batches = [loader.batch(i) for i in range(chunk)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches)
    return params, batches, stacked


def measure_cell(algo: str, engine: str, *, chunk: int = CHUNK,
                 chunks: int = 4):
    """Single-engine measurement for one matrix cell (benchmarks.matrix).

    Same drivers as the paired harness below — per-step loop for the
    pytree engine, the ``run_steps`` scan for flat — so matrix cells stay
    comparable with the legacy BENCH_PR3.json cells the trajectory aligns
    them against.  Returns (metrics, extra) in the schema-v2 cell shape.
    """
    params, batches, stacked = _workload_inputs(chunk)
    tr = _make(algo, engine)
    st = tr.init(jax.random.PRNGKey(0), params)
    flat = tr._flat

    def run_chunk(st):
        if flat:
            st, _ = tr.run_steps(st, stacked, k=chunk)
        else:
            for b in batches:
                st, _ = tr.train_step(st, b)
        return st

    st = run_chunk(st)                                 # compile + warm
    jax.block_until_ready(st.params)
    t0 = time.perf_counter()
    for _ in range(chunks):
        st = run_chunk(st)
    jax.block_until_ready(st.params)
    s = (time.perf_counter() - t0) / (chunk * chunks)
    metrics = {"us_per_step": s * 1e6,
               "tokens_per_s": N * LOCAL_BATCH / s}
    extra = {"source": "bench_throughput",
             "fused_kernel": tr._fused is not None, "flat_engine": flat}
    return metrics, extra


def _measure(algo: str, params, batches, stacked, chunks=CHUNKS):
    """Finely paired engine timing, robust to machine-load drift.

    Both engines train continuously (donated states, real drivers: per-step
    loop for pytree — the pre-PR3 hot path — and the run_steps scan for
    flat), alternating every CHUNK steps so the two accumulate wall time
    under near-identical machine load; run-level pairing (hundreds of ms
    apart) measurably does NOT cancel load swings on shared hosts.  One
    warm-up chunk per engine (compile) is excluded."""
    tr_tree = _make(algo, "pytree")
    tr_flat = _make(algo, "flat")
    st_tree = tr_tree.init(jax.random.PRNGKey(0), params)
    st_flat = tr_flat.init(jax.random.PRNGKey(0), params)
    for b in batches:                                  # compile + warm
        st_tree, _ = tr_tree.train_step(st_tree, b)
    st_flat, _ = tr_flat.run_steps(st_flat, stacked, k=CHUNK)
    t_tree = t_flat = 0.0
    for _ in range(chunks):
        t0 = time.perf_counter()
        for b in batches:
            st_tree, _ = tr_tree.train_step(st_tree, b)
        jax.block_until_ready(st_tree.params)
        t_tree += time.perf_counter() - t0
        t0 = time.perf_counter()
        st_flat, _ = tr_flat.run_steps(st_flat, stacked, k=CHUNK)
        jax.block_until_ready(st_flat.params)
        t_flat += time.perf_counter() - t0
    steps = CHUNK * chunks
    return tr_flat, t_tree / steps, t_flat / steps, t_flat / t_tree


def main(argv=None):
    smoke = parse_smoke(argv)
    chunks = 4 if smoke else CHUNKS
    params, batches, stacked = _workload_inputs(CHUNK)
    tokens_per_step = N * LOCAL_BATCH       # 1 sample == 1 token (FC proxy)

    rows, report = [], {}
    for algo in ALGOS:
        tr_flat, s_tree, s_flat, ratio = _measure(algo, params, batches,
                                                  stacked, chunks)
        # audit: the traced flat step must not concatenate anything
        # parameter-sized (the per-step re-flatten this PR removed)
        st = tr_flat.init(jax.random.PRNGKey(0), params)
        concat = max_concat_elems(jax.make_jaxpr(tr_flat._train_step)(
            st, batches[0]))
        report[algo] = {
            "pytree_us_per_step": s_tree * 1e6,
            "flat_us_per_step": s_flat * 1e6,
            "flat_speedup": 1.0 / ratio,
            "flat_over_pytree_ratio": ratio,
            "tokens_per_s_pytree": tokens_per_step / s_tree,
            "tokens_per_s_flat": tokens_per_step / s_flat,
            "flat_step_max_concat_elems": concat,
            "fused_kernel": tr_flat._fused is not None,
            "default_engine_flat": MultiLearnerTrainer(
                fcnet.loss_fn, sgd(LR),
                AlgoConfig(algo=algo, topology="random_pair",
                           n_learners=N, **ALGO_KW.get(algo, {})))._flat,
        }
        rows.append([algo, s_tree * 1e6, s_flat * 1e6, 1.0 / ratio,
                     tokens_per_step / s_flat])

    cfg = get_config("yi-34b")
    volume = {
        "yi34b_gossip_einsum_GB":
            gossip_link_bytes_per_chip(cfg, 256, 16, "einsum") / 1e9,
        "yi34b_gossip_ppermute_GB":
            gossip_link_bytes_per_chip(cfg, 256, 16, "ppermute") / 1e9,
    }
    payload = {
        "config": {"n_learners": N, "local_batch": LOCAL_BATCH, "lr": LR,
                   "steps": CHUNK * chunks, "chunk": CHUNK,
                   "model": "fcnet-784-50-50-10",
                   "n_elem": int(tr_flat._meta.n_elem)},
        "algos": report,
        "gossip_volume": volume,
    }
    os.makedirs(results_dir(), exist_ok=True)
    with open(os.path.join(results_dir(), "BENCH_PR3.json"), "w") as f:
        json.dump(payload, f, indent=2)

    write_table("bench_throughput",
                ["algo", "pytree_us_per_step", "flat_us_per_step",
                 "flat_speedup", "flat_tokens_per_s"], rows)
    d = report["dpsgd"]
    derived = ("flat/pytree speedup: "
               + " ".join(f"{a}={report[a]['flat_speedup']:.2f}x"
                          for a in ALGOS)
               + f"; dpsgd flat {d['tokens_per_s_flat']:.0f} tok/s, "
               f"step concat={d['flat_step_max_concat_elems']} elems "
               "(BENCH_PR3.json gated by check_regression)")
    print(f"bench_throughput,{d['flat_us_per_step']:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
