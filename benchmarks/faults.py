"""Fault-injection smoke benchmark: the fleet survives crashes mid-run.

Drives the elastic-membership stack (DESIGN §15) end to end on the real
trainer: a seeded :class:`~repro.core.FaultPlan` crashes a learner mid-run,
rejoins it later (consensus-clone ``admit``), and the
:class:`~repro.core.Supervisor` applies every event as a same-shape operand
swap — the compiled step is never invalidated on the randomized-matching
path.  Measured per cell:

  * **us/step** in three windows — healthy fleet, degraded (post-crash),
    and post-rejoin (the "post-resize throughput" of the acceptance gate)
  * **recovery_steps** — how many steps after the crash the training loss
    takes to return to its pre-crash level (the recovery-time measurement)
  * **final loss** and the minimum live-member count seen

``measure_cell`` is the matrix plugin (workload ``elastic`` in
`benchmarks.matrix`); ``main`` is the standalone smoke benchmark wired
into ``make bench-smoke`` (contract row ``bench_faults,us,derived``).
"""
from __future__ import annotations

import math
import time

from .common import final_loss, parse_smoke, write_table

N, LR, LOCAL_BATCH = 5, 0.5, 200
ALGOS = ("dpsgd", "adpsgd")


def _plan(fault: str, steps: int, n: int):
    """The per-cell fault script.  ``crash_rejoin`` is the acceptance
    scenario (die at 1/3, consensus-rejoin at 2/3, straggler throughout);
    ``chaos`` is the seeded random schedule."""
    from repro.core import FaultPlan
    if fault == "crash_rejoin":
        plan = FaultPlan.crash_rejoin(1, steps // 3, 2 * steps // 3)
        return FaultPlan(plan.events + FaultPlan.straggler(0, 2).events)
    if fault == "chaos":
        return FaultPlan.random(0, steps, n, min_active=2)
    raise ValueError(f"unknown fault scenario {fault!r}")


def run_faulted(algo: str, fault: str, *, steps: int, n: int = N,
                engine: str = "flat", seed: int = 0):
    """Train fcnet under a Supervisor + FaultPlan; returns the windowed
    timings, the loss trace, the live-count trace and the fault report."""
    import jax

    from repro.core import (AlgoConfig, Membership, MultiLearnerTrainer,
                            Supervisor)
    from repro.data import ShardedLoader, TemplateImages
    from repro.models import fcnet
    from repro.optim import sgd

    loader = ShardedLoader(TemplateImages(), n_learners=n,
                           local_batch=LOCAL_BATCH, seed=seed)
    key = jax.random.PRNGKey(seed)
    params = fcnet.init_params(key, in_dim=784, hidden=50)
    kw = {"max_staleness": 4} if algo == "adpsgd" else {}
    tr = MultiLearnerTrainer(
        fcnet.loss_fn, sgd(LR),
        AlgoConfig(algo=algo, topology="random_pair", n_learners=n,
                   noise_std=0.01, **kw),
        engine=engine)
    st = tr.init(key, params)
    sup = Supervisor(tr, Membership(n), _plan(fault, steps, n))
    st = tr.set_membership(st, sup.membership)

    st = sup.tick(st, 0)
    st, m = tr.train_step(st, loader.batch(0))   # warm-up/compile
    jax.block_until_ready(m.loss)
    losses, times, n_act = [], [], []
    for i in range(1, steps):
        st = sup.tick(st, i)
        t0 = time.perf_counter()
        st, m = tr.train_step(st, loader.batch(i))
        loss = float(m.loss)                     # blocks on the step
        times.append(time.perf_counter() - t0)
        losses.append(loss)
        n_act.append(int(m.n_active))
    return {"losses": losses, "times": times, "n_active": n_act,
            "report": sup.report, "state": st, "trainer": tr}


def _window_us(times, lo, hi):
    w = times[lo:hi]
    return 1e6 * sum(w) / len(w) if w else float("nan")


def recovery_steps(losses, crash_step: int) -> int:
    """Steps after the crash until the loss trace returns to its pre-crash
    level (min over the healthy window); -1 if it never does."""
    pre = [x for x in losses[:crash_step] if math.isfinite(x)]
    if not pre:
        return -1
    floor = min(pre)
    for j in range(crash_step, len(losses)):
        if math.isfinite(losses[j]) and losses[j] <= floor:
            return j - crash_step
    return -1


def measure_cell(algo: str, fault: str, *, engine: str = "flat",
                 smoke: bool = False):
    """Matrix plugin for the ``elastic`` workload: metrics + extra."""
    steps = 36 if smoke else 150
    r = run_faulted(algo, fault, steps=steps, engine=engine)
    rep = r["report"]
    crash = rep.crashes[0][0] if rep.crashes else steps // 3
    rejoin = rep.rejoins[-1][0] if rep.rejoins else crash
    # loss/time indices are step-1 (step 0 is the excluded warm-up)
    metrics = {
        "us_per_step": _window_us(r["times"], 0, None),
        "us_per_step_resized": _window_us(r["times"], rejoin, None),
        "recovery_steps": float(recovery_steps(r["losses"],
                                               max(crash - 1, 0))),
        "final_loss": final_loss(r["losses"]),
        "n_active_min": float(min(r["n_active"])),
    }
    extra = {"fault": fault, "steps": steps,
             "crashes": len(rep.crashes), "rejoins": len(rep.rejoins),
             "evictions": len(rep.evictions), "retries": len(rep.retries),
             "dropped_rounds": rep.dropped_rounds,
             "interventions": rep.interventions}
    return metrics, extra


def main(argv=None):
    smoke = parse_smoke(argv)
    t0 = time.perf_counter()
    rows, derived_bits = [], {}
    for algo in ALGOS:
        m, x = measure_cell(algo, "crash_rejoin", smoke=smoke)
        rows.append([algo, "crash_rejoin", m["us_per_step"],
                     m["us_per_step_resized"], m["recovery_steps"],
                     m["final_loss"], m["n_active_min"],
                     x["interventions"]])
        derived_bits[algo] = m
    write_table("bench_faults",
                ["algo", "fault", "us_per_step", "us_per_step_resized",
                 "recovery_steps", "final_loss", "n_active_min",
                 "interventions"], rows)
    us = (time.perf_counter() - t0) * 1e6
    d, a = derived_bits["dpsgd"], derived_bits["adpsgd"]
    derived = (f"crash+rejoin survived: dpsgd loss={d['final_loss']:.3f} "
               f"recovery={d['recovery_steps']:.0f} steps; adpsgd "
               f"loss={a['final_loss']:.3f} recovery={a['recovery_steps']:.0f}"
               f" steps (fleet floor n={d['n_active_min']:.0f})")
    print(f"bench_faults,{us:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
