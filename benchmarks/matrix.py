"""Declarative benchmark matrix: one sweep spec, one BENCH_PR<N>.json
(DESIGN §13, ROADMAP item 5).

The spec declares the sweep over (model x algo x topology x n x precision
x engine) per workload; ``expand`` turns it into runnable cells (cartesian
product minus excludes, deterministic order), and a per-workload plugin
registry maps each cell onto one of the existing runners:

  * ``throughput``  -> `bench_throughput.measure_cell` (per-engine us/step
    and tokens/s — the same drivers and cell axes as the legacy
    BENCH_PR3.json, so the trajectory aligns across the schema break)
  * ``topology``    -> `ablation_topology.run_topology` (GossipSchedule
    sweep: contraction bound + loss per schedule)
  * ``large_batch`` -> `table1_large_batch.run_cell` (AdaScale-style
    batch/LR scaling axis — the paper's Table 1 regime)
  * ``elastic``     -> `faults.measure_cell` (crash / consensus-rejoin /
    seeded chaos under the membership Supervisor: recovery time and
    post-resize throughput, ISSUE 8)
  * ``serving``     -> `serving.measure_cell` (continuous vs static
    batching under open-loop Poisson arrivals: tokens/s + p50/p99 latency
    on the paged decode path, ISSUE 7)

Each PR's run emits ``results/bench/BENCH_PR<N>.json`` in the
schema-versioned format of `benchmarks.schema`; `benchmarks.trajectory`
aligns those across PRs and `benchmarks.check_regression` gates them.

CLI (wired into ``make bench-smoke`` / ``bench-check``):
    python -m benchmarks.matrix [--smoke] [--pr N]

``--smoke`` trims the axis lists (SPEC.smoke below) and shortens training;
cell KEYS are unchanged, so smoke and full runs align on their shared
cells.  Spec expansion and the registry are importable without jax (the
runners import the training stack lazily) so tests can exercise them
standalone.
"""
from __future__ import annotations

import dataclasses
import itertools
import sys
import time

from . import schema

CURRENT_PR = 8   # bump per PR: the emitted artifact is BENCH_PR<N>.json


@dataclasses.dataclass(frozen=True)
class MatrixSpec:
    """Axes are {axis: (values...)}; per-workload axes override ``base``.

    ``exclude`` entries are partial axis dicts — a cell is dropped when
    every listed key matches.  ``smoke`` holds per-workload axis overrides
    for the trimmed CI run (never new axis NAMES: smoke subsets values).
    """
    base: dict
    workloads: dict
    exclude: tuple = ()
    smoke: dict = dataclasses.field(default_factory=dict)


SPEC = MatrixSpec(
    base={"model": ("fcnet",), "precision": ("f32",), "n": (5,)},
    workloads={
        "throughput": {
            "algo": ("ssgd", "dpsgd", "adpsgd", "ssgd_star"),
            "engine": ("flat", "pytree"),
            "topology": ("random_pair",),
        },
        "topology": {
            "algo": ("dpsgd",),
            "engine": ("flat",),
            "n": (8,),
            "topology": ("full", "ring", "torus", "random_pair", "solo",
                         "hierarchical", "exp", "one_peer_exp",
                         "random_matching"),
        },
        "large_batch": {
            "algo": ("ssgd", "dpsgd", "ssgd_autolr"),
            "engine": ("auto",),
            "topology": ("random_pair",),
            "batch_scale": (1, 2, 4),
        },
        # elastic sweeps the fault scenario under the membership harness:
        # crash+consensus-rejoin and the seeded chaos schedule (DESIGN §15)
        "elastic": {
            "algo": ("dpsgd", "adpsgd"),
            "engine": ("flat",),
            "topology": ("random_pair",),
            "fault": ("crash_rejoin", "chaos"),
        },
        # serving sweeps the ADMISSION engine, not the trainer engine; the
        # greedy/solo axes are degenerate but keep the cell key canonical
        "serving": {
            "model": ("tiny-lm",),
            "algo": ("greedy",),
            "topology": ("solo",),
            "n": (4,),                      # decode slots
            "engine": ("continuous", "static"),
            "rate": (0.25, 1.0),            # requests per engine step
        },
    },
    # ssgd_star draws per-leaf weight noise — the flat engine refuses it
    # (trainer raises); it is measured on the pytree reference only.
    exclude=({"algo": "ssgd_star", "engine": "flat"},),
    smoke={
        "throughput": {"algo": ("ssgd", "dpsgd", "adpsgd")},
        "topology": {"topology": ("full", "ring", "random_pair", "solo")},
        # ssgd_autolr's probe compile dominates smoke wall-clock: full only
        "large_batch": {"algo": ("ssgd", "dpsgd"), "batch_scale": (1, 4)},
        # one scripted scenario per algo keeps smoke wall-clock bounded;
        # the chaos schedule runs in the full sweep
        "elastic": {"fault": ("crash_rejoin",)},
    },
)


def expand(spec: MatrixSpec, smoke: bool = False) -> list[dict]:
    """Spec -> ordered list of cell axes dicts (workload key included)."""
    cells = []
    for wl, wl_axes in spec.workloads.items():
        axes_def = {**spec.base, **wl_axes}
        if smoke:
            for k, vals in spec.smoke.get(wl, {}).items():
                assert k in axes_def, (wl, k)
                axes_def[k] = vals
        names = list(axes_def)
        for combo in itertools.product(*(axes_def[k] for k in names)):
            axes = {"workload": wl, **dict(zip(names, combo))}
            if any(all(axes.get(k) == v for k, v in ex.items())
                   for ex in spec.exclude):
                continue
            cells.append(axes)
    return cells


# -- per-workload plugin registry --------------------------------------------

REGISTRY: dict = {}


def workload(name: str):
    def deco(fn):
        REGISTRY[name] = fn
        return fn
    return deco


@workload("throughput")
def _run_throughput(axes: dict, smoke: bool):
    # keep chunk == bench_throughput.CHUNK: the flat engine's run_steps
    # scan amortizes a fixed per-call cost over the chunk, so a smaller
    # smoke chunk would skew flat cells vs the legacy BENCH_PR3 history
    from .bench_throughput import measure_cell
    return measure_cell(axes["algo"], axes["engine"],
                        chunks=2 if smoke else 8)


@workload("topology")
def _run_topology(axes: dict, smoke: bool):
    from .ablation_topology import run_topology
    r = run_topology(axes["topology"], steps=20 if smoke else 130)
    metrics = {k: float(r[k]) for k in
               ("us_per_step", "final_loss", "consensus_dist",
                "gap_bound", "measured_gap")}
    extra = {k: r[k] for k in ("K", "period", "rounds_per_step", "fused")}
    return metrics, extra


@workload("large_batch")
def _run_large_batch(axes: dict, smoke: bool):
    from .table1_large_batch import run_cell
    r = run_cell(axes["algo"], axes["batch_scale"],
                 steps=12 if smoke else 120)
    metrics = {k: float(r[k]) for k in
               ("us_per_step", "final_loss", "autolr_scale")}
    return metrics, {"nB": r["nB"], "lr": r["lr"]}


@workload("elastic")
def _run_elastic(axes: dict, smoke: bool):
    # recovery-time + post-resize throughput under the seeded fault
    # harness (DESIGN §15): the acceptance metrics for the elastic fleet
    from .faults import measure_cell
    return measure_cell(axes["algo"], axes["fault"],
                        engine=axes["engine"], smoke=smoke)


@workload("serving")
def _run_serving(axes: dict, smoke: bool):
    from .serving import MAX_LEN, PAGE_SIZE, measure_cell
    m = measure_cell(axes["engine"], axes["rate"], smoke=smoke)
    extra = {"page_size": PAGE_SIZE, "max_len": MAX_LEN,
             "n_requests": int(m.pop("n_requests"))}
    return m, extra


# -- execution ----------------------------------------------------------------

def run_matrix(spec: MatrixSpec = SPEC, *, smoke: bool = False,
               pr: int = CURRENT_PR):
    """Run every cell; returns (payload, errors).  Failed cells are
    reported and dropped from the payload rather than killing the run."""
    import jax
    payload = schema.new_payload(pr, {
        "smoke": smoke, "backend": jax.default_backend(),
        "device_count": jax.device_count()})
    errors = []
    cells = expand(spec, smoke=smoke)
    for i, axes in enumerate(cells):
        label = schema.cell_key(axes)
        t0 = time.perf_counter()
        try:
            metrics, extra = REGISTRY[axes["workload"]](axes, smoke)
        except Exception as e:  # noqa: BLE001 — cell isolation is the point
            errors.append(f"{label}: {type(e).__name__}: {e}")
            print(f"  cell {i + 1}/{len(cells)} FAILED {label}: {e}",
                  file=sys.stderr)
            continue
        key, cell = schema.make_cell(axes, metrics, extra=extra)
        payload["cells"][key] = cell
        print(f"  cell {i + 1}/{len(cells)} {label} "
              f"us/step={metrics['us_per_step']:.0f} "
              f"({time.perf_counter() - t0:.1f}s)")
    return payload, errors


def main(argv=None) -> int:
    import json
    import os

    from .common import parse_smoke, write_table

    argv = sys.argv[1:] if argv is None else list(argv)
    smoke = parse_smoke(argv)
    pr = int(argv[argv.index("--pr") + 1]) if "--pr" in argv else CURRENT_PR

    t0 = time.perf_counter()
    payload, errors = run_matrix(SPEC, smoke=smoke, pr=pr)
    bad = schema.validate(payload)
    assert not bad, bad   # the emitter must honor its own schema

    out_dir = schema.results_dir()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_PR{pr}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)

    rows = [[key, c["axes"]["workload"], c["axes"]["algo"],
             c["axes"]["topology"], c["axes"]["n"], c["axes"]["engine"],
             c["metrics"]["us_per_step"],
             c["metrics"].get("tokens_per_s", ""),
             c["metrics"].get("final_loss", "")]
            for key, c in payload["cells"].items()]
    write_table("bench_matrix",
                ["cell", "workload", "algo", "topology", "n", "engine",
                 "us_per_step", "tokens_per_s", "final_loss"], rows)

    n = len(payload["cells"])
    us = (time.perf_counter() - t0) * 1e6 / max(n, 1)
    by_wl = {}
    for c in payload["cells"].values():
        by_wl[c["axes"]["workload"]] = by_wl.get(c["axes"]["workload"], 0) + 1
    derived = (f"{n} cells ({'smoke' if smoke else 'full'}: "
               + " ".join(f"{k}={v}" for k, v in sorted(by_wl.items()))
               + f") -> {os.path.basename(path)} schema v"
               f"{schema.SCHEMA_VERSION}"
               + (f"; {len(errors)} FAILED" if errors else ""))
    print(f"bench_matrix,{us:.0f},{derived}")
    for e in errors:
        print(f"MATRIX CELL FAILED: {e}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
