#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

The cell (configuration x traffic mix) comes from ``BENCHMARK.json``.  The
run builds its weights and inputs from ``--seed``, warms up every shape it
uses (set-up), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON line last on
standard output: the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1`` (the tail of the window then runs
under the JAX profiler).

Without a TPU, or with fewer chips than the cell asks for, it exits with
status 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                           # noqa: E402
import json                                               # noqa: E402
import os                                                 # noqa: E402
import sys                                                # noqa: E402
from pathlib import Path                                  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".bench_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import cell as C
    cell = C.load_cell(args.workload)

    # the compile cache and every temporary file stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); nothing "
              "was run", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:cell.chips]

    from bench.harness import runner
    run = runner.run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), devices=devices,
                          t_start=T_START,
                          trace_dir=CACHE / "trace" / cell.name)
    line = runner.result_line(run, trace=bool(args.trace), devices=devices)
    for c in run.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
