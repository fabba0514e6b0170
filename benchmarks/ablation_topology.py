"""Beyond-paper ablation (App. F territory): the full GossipSchedule sweep
at the critical lr — every compiled topology (static: full/ring/torus/
hierarchical/exp; time-varying: one-peer exponential, random matchings with
multi-round mixing) plus solo, each dispatching the fused flat engine
(DESIGN §12).

Two stories in one table:

  * the paper's noise trade-off: solo never consensus-averages, full
    averaging kills the landscape-dependent noise (back to SSGD behaviour),
    the sparse schedules hit the sweet spot;
  * the schedule analyzer: per-schedule measured consensus contraction vs
    the product-of-(1-λ₂) bound (`measured_gap >= gap_bound`; time-varying
    schedules beat their per-step bound by a wide margin — that headroom is
    why one-peer exponential is usable at one collective per step).

CSV columns (benchmarks/README.md contract):
  topology, K, period, rounds_per_step, fused, gap_bound, measured_gap,
  final_loss, consensus_dist
Smoke mode (``--smoke``, used by `make bench-check`) shortens training but
keeps every schedule and every column.
"""
from __future__ import annotations

import sys

import numpy as np

from repro.core import learner_var
from repro.core.schedule import make_schedule, spectral_gap_profile

from .common import final_loss, train_fc, write_table

LR = 0.5
TOPOLOGIES = ("full", "ring", "torus", "random_pair", "solo",
              "hierarchical", "exp", "one_peer_exp", "random_matching")
N = 8


def run_topology(name: str, *, steps: int = 130, n: int = N) -> dict:
    """One GossipSchedule cell: train dpsgd on ``name`` + profile the
    schedule.  Shared by this script's sweep and benchmarks.matrix's
    ``topology`` workload plugin."""
    kw = {"gossip_rounds": 2} if name == "random_matching" else {}
    r = train_fc("dpsgd", LR, n=n, steps=steps, topology=name,
                 algo_kwargs=kw)
    tr = r["trainer"]
    sched = make_schedule(name, n, rounds=kw.get("gossip_rounds", 1))
    prof = spectral_gap_profile(sched, window=16)
    consensus = float(np.sqrt(float(
        learner_var(tr.params_tree(r["state"])))))
    return {
        "topology": name,
        "K": sched.K if sched else 0,
        "period": sched.period if sched else 0,
        "rounds_per_step": sched.rounds_per_step if sched else 0,
        "fused": int(tr._fused is not None),
        "gap_bound": round(prof["gap_bound"], 6),
        "measured_gap": round(prof["measured_gap"], 6),
        "final_loss": final_loss(r["losses"]),
        "consensus_dist": consensus,
        "us_per_step": r["us_per_step"],
    }


COLUMNS = ("topology", "K", "period", "rounds_per_step", "fused",
           "gap_bound", "measured_gap", "final_loss", "consensus_dist")


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    steps = 40 if smoke else 130
    rows = []
    us = 0.0
    for name in TOPOLOGIES:
        r = run_topology(name, steps=steps)
        us += r["us_per_step"]
        rows.append([r[c] for c in COLUMNS])
    write_table("ablation_topology", list(COLUMNS), rows)
    d = {r[0]: r for r in rows}
    # every scheduled topology must have run the fused kernel; the analyzer
    # must never report contraction faster than measured
    assert all(r[4] == 1 for r in rows if r[0] != "solo"), rows
    assert all(r[6] >= r[5] - 1e-9 for r in rows), rows
    derived = (f"full={d['full'][7]:.3f} ring={d['ring'][7]:.3f} "
               f"pair={d['random_pair'][7]:.3f} solo={d['solo'][7]:.3f} "
               "(partial averaging beats full & none); one_peer_exp "
               f"measured_gap={d['one_peer_exp'][6]:.2f} vs per-step bound "
               f"{d['one_peer_exp'][5]:.2f} at 1 collective/step; all "
               "schedules fused")
    print(f"ablation_topology,{us / max(len(rows), 1):.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
