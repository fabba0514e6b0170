"""Paper Fig. 3 + App. F: straggler immunity, measured on the real code path.

Trains sync pairwise DPSGD vs async AD-PSGD with an injected straggler
(learner 0 takes ``slow_factor`` ticks per local step, injected through
``FaultPlan.straggler`` — the same seeded fault path the elastic-membership
harness replays, DESIGN §15) through the actual MultiLearnerTrainer and
reports, per algorithm:

  * measured us/step of the jitted train step (the real compute cost)
  * effective wall-clock per tick under the straggler: synchronous gossip
    barriers on the slowest learner every tick (x slow_factor), AD-PSGD
    proceeds against the straggler's stale published buffer (x 1)
  * final training loss and the max buffer staleness actually observed —
    the convergence price of asynchrony (bounded by max_staleness)

The barrier inflation is the one modeled quantity: SPMD hardware is
bulk-synchronous, so true overlap cannot be timed in-process (DESIGN.md §2);
everything else — the training dynamics, the staleness, the losses, the
step cost — is measured, not simulated.  App. F's roofline communication
model lives on in benchmarks/roofline_report.py.
"""
from __future__ import annotations

import time

from repro.core import FaultPlan

from .common import final_loss, parse_smoke, train_fc, write_table

SLOW_FACTORS = (1, 2, 5)
N, LR, STEPS, TAU = 8, 0.5, 120, 4


def main(argv=None):
    smoke = parse_smoke(argv)
    steps = 24 if smoke else STEPS
    slow_factors = SLOW_FACTORS[-1:] if smoke else SLOW_FACTORS
    t0 = time.perf_counter()
    rows = []
    derived_bits = {}
    # the sync run does not depend on the straggle factor (only its barrier
    # inflation does) — train it once, reuse across the sweep
    sync = train_fc("dpsgd", LR, n=N, steps=steps)
    for slow in slow_factors:
        adp = train_fc("adpsgd", LR, n=N, steps=steps,
                       algo_kwargs=dict(max_staleness=TAU),
                       fault_plan=FaultPlan.straggler(0, slow))
        for name, run, tick_scale in (("dpsgd_sync", sync, slow),
                                      ("adpsgd", adp, 1)):
            us = run["us_per_step"]
            rows.append([name, slow, us, us * tick_scale,
                         final_loss(run["losses"]), run["staleness_max"]])
        if slow == slow_factors[-1]:
            derived_bits = {
                "sync_ms": sync["us_per_step"] * slow / 1e3,
                "async_ms": adp["us_per_step"] / 1e3,
                "async_loss": final_loss(adp["losses"]),
                "sync_loss": final_loss(sync["losses"]),
            }
    write_table("fig3_straggler",
                ["algo", "straggle_x", "us_per_step_measured",
                 "us_per_tick_with_straggler", "final_loss",
                 "staleness_max_seen"], rows)
    us = (time.perf_counter() - t0) * 1e6
    derived = (f"5x-straggler tick ms: sync={derived_bits['sync_ms']:.1f} "
               f"async={derived_bits['async_ms']:.1f}; final loss "
               f"sync={derived_bits['sync_loss']:.3f} "
               f"async={derived_bits['async_loss']:.3f} "
               "(paper Fig3: DPSGD immune)")
    print(f"fig3_straggler,{us:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
