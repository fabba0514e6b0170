"""Serving benchmark: continuous batching vs static batching under an
open-loop Poisson arrival stream (ISSUE 7, DESIGN §14).

One cell = (engine mode, arrival rate).  The driver replays the SAME
deterministic arrival schedule (mixed-length prompts, mixed decode budgets,
exponential inter-arrival gaps in engine-step space) against a
:class:`repro.serve.ServeEngine` in ``continuous`` or ``static`` admission
mode and measures what a serving operator would: aggregate tokens/s,
us per model step, and request-completion latency percentiles (p50/p99).
Open-loop means arrivals do NOT wait for capacity — a saturated engine
grows its queue and the latency tail shows it, which is exactly the regime
where continuous batching's slot recycling wins over the static baseline's
head-of-line blocking.

``main`` additionally demonstrates the consensus-view bridge: a live flat
DPSGD trainer (n=4 learners, ring) keeps training the same tiny LM while a
snapshot of its consensus mean serves requests; the summary reports the
snapshot's staleness (steps behind, sigma_w then vs now) and the
logit-level divergence of the served snapshot against the current mean.

CLI (wired into ``make bench-smoke`` / the matrix ``serving`` workload):
    python -m benchmarks.serving [--smoke]
"""
from __future__ import annotations

import sys
import time

import numpy as np

# tiny dense LM sized so a CPU smoke run finishes in seconds; the serving
# metrics compare ENGINES, not models, so small is fine (and the cell key
# pins the model name so cross-PR trajectories stay aligned).
TINY = dict(name="tiny-lm", family="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=128,
            attn_chunk=16)

N_SLOTS = 4
PAGE_SIZE = 4
MAX_LEN = 16

_MODEL_CACHE: dict = {}


def _tiny_model():
    if "api" not in _MODEL_CACHE:
        import jax
        from repro.configs.base import ModelConfig
        from repro.models.model import build_model
        cfg = ModelConfig(**TINY)
        api = build_model(cfg)
        _MODEL_CACHE["api"] = api
        _MODEL_CACHE["params"] = api.init(jax.random.PRNGKey(0))
    return _MODEL_CACHE["api"], _MODEL_CACHE["params"]


def _arrival_schedule(rate: float, n_requests: int, seed: int = 0):
    """Deterministic open-loop schedule: (arrival_step, prompt, max_new)."""
    rng = np.random.default_rng(seed)
    t, sched = 0.0, []
    for _ in range(n_requests):
        t += rng.exponential(1.0 / rate)
        prompt = rng.integers(1, TINY["vocab"], rng.integers(1, 9)).tolist()
        max_new = int(rng.integers(2, min(8, MAX_LEN - len(prompt)) + 1))
        sched.append((t, prompt, max_new))
    return sched


def measure_cell(mode: str, rate: float, *, smoke: bool = False,
                 seed: int = 0) -> dict:
    """Run one (admission mode, arrival rate) serving cell -> metrics."""
    from repro.serve import ServeEngine

    api, params = _tiny_model()
    n_requests = 12 if smoke else 48
    sched = _arrival_schedule(rate, n_requests, seed)

    eng = ServeEngine(api, params, n_slots=N_SLOTS, page_size=PAGE_SIZE,
                      max_len=MAX_LEN, admission=mode)
    eng.warmup()

    pending = list(sched)
    inflight, t_submit, t_finish = [], {}, {}
    t0 = time.perf_counter()
    while pending or eng.has_work:
        while pending and pending[0][0] <= eng.step_count:
            _, prompt, max_new = pending.pop(0)
            r = eng.submit(prompt, max_new)
            t_submit[r.rid] = time.perf_counter()
            inflight.append(r)
        if eng.has_work:
            eng.step()
            now = time.perf_counter()
            for r in inflight:
                if r.done and r.rid not in t_finish:
                    t_finish[r.rid] = now
            inflight = [r for r in inflight if not r.done]
        else:
            eng.idle_tick()   # fast-forward to the next arrival
    wall = time.perf_counter() - t0

    lat_ms = np.array([(t_finish[rid] - t_submit[rid]) * 1e3
                       for rid in t_finish])
    assert len(lat_ms) == n_requests, "driver lost requests"
    return {
        "us_per_step": wall * 1e6 / max(eng.real_steps, 1),
        "tokens_per_s": eng.generated_total / wall,
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "n_requests": float(n_requests),
        "total_tokens": float(eng.generated_total),
        "real_steps": float(eng.real_steps),
        "stall_events": float(eng.stall_events),
    }


def bridge_demo(smoke: bool = False) -> dict:
    """Serve consensus snapshots of a LIVE flat DPSGD trainer; report
    staleness and served-output divergence (the ISSUE 7 bridge contract)."""
    import jax
    import jax.numpy as jnp
    from repro.core import AlgoConfig, MultiLearnerTrainer
    from repro.models.model import make_synthetic_batch
    from repro.optim import sgd
    from repro.serve import ConsensusBridge, ServeEngine, served_divergence

    api, params = _tiny_model()
    n = 4
    tr = MultiLearnerTrainer(
        api.loss_fn, sgd(0.05),
        AlgoConfig(algo="dpsgd", topology="ring", n_learners=n),
        engine="flat")
    key = jax.random.PRNGKey(0)
    st = tr.init(key, params)

    def batch(i):
        b = make_synthetic_batch(api.cfg, jax.random.PRNGKey(i), n * 2, 16)
        return jax.tree_util.tree_map(
            lambda x: x.reshape((n, 2) + x.shape[1:]), b)

    warm, extra = (2, 3) if smoke else (5, 10)
    for i in range(warm):
        st, _ = tr.train_step(st, batch(i))

    bridge = ConsensusBridge(tr)
    snap = bridge.snapshot(st)
    eng = ServeEngine(api, snap.params, n_slots=N_SLOTS,
                      page_size=PAGE_SIZE, max_len=MAX_LEN)
    served = []
    for _, prompt, max_new in _arrival_schedule(1.0, 3, seed=7):
        served.append(eng.submit(prompt, max_new))
    # training keeps moving WHILE the snapshot serves: interleave
    for i in range(extra):
        st, _ = tr.train_step(st, batch(warm + i))
        if eng.has_work:
            eng.step()
    eng.run()
    assert all(r.done for r in served)

    stale = bridge.staleness(st, snap)
    live = bridge.snapshot(st)
    probe = jnp.asarray(
        np.random.default_rng(3).integers(1, api.cfg.vocab, (2, 8)))
    div = served_divergence(api, snap.params, live.params, probe)
    eng.set_params(live.params)   # hot swap: same shapes, no retrace
    return {**stale, **div,
            "served_tokens": sum(len(r.generated) for r in served)}


def main(argv=None) -> int:
    from .common import fmt, parse_smoke, write_table

    smoke = parse_smoke(argv)
    t0 = time.perf_counter()
    rows, cells = [], {}
    for mode in ("continuous", "static"):
        for rate in (0.25, 1.0):
            m = measure_cell(mode, rate, smoke=smoke)
            cells[(mode, rate)] = m
            rows.append([mode, rate, fmt(m["us_per_step"]),
                         fmt(m["tokens_per_s"]), fmt(m["p50_ms"]),
                         fmt(m["p99_ms"]), int(m["total_tokens"]),
                         int(m["real_steps"]), int(m["stall_events"])])
    write_table("bench_serving",
                ["mode", "rate", "us_per_step", "tokens_per_s", "p50_ms",
                 "p99_ms", "total_tokens", "real_steps", "stall_events"],
                rows)

    # the tentpole claim: under the heavy mixed-length stream, continuous
    # batching's slot recycling beats static admission on aggregate tokens/s
    cont, stat = cells[("continuous", 1.0)], cells[("static", 1.0)]
    speedup = cont["tokens_per_s"] / stat["tokens_per_s"]
    assert speedup > 1.0, (
        f"continuous {cont['tokens_per_s']:.1f} tok/s did not beat "
        f"static {stat['tokens_per_s']:.1f} tok/s at rate=1.0")

    bd = bridge_demo(smoke)
    us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
    derived = (f"continuous/static tok/s x{speedup:.2f} at rate=1.0; "
               f"bridge steps_behind={bd['steps_behind']} "
               f"top1_agree={bd['top1_agreement']:.2f} "
               f"sigma_w {bd['consensus_dist_snapshot']:.3g}->"
               f"{bd['consensus_dist_now']:.3g}")
    print(f"bench_serving,{us:.0f},{derived}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
