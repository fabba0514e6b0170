#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from (chip only).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--fault-seeds 7,8,9]

One process, so the compiled programs are shared between seeds.  For each
seed it prints one JSON line with the numbers the cell compares:

* ``program``: the program as the configuration states it (sound runs;
  their largest reading is the lower reading of each limit);
* ``control``: the nearest lower precision, the program's own bfloat16
  path;
* ``fault``: faults planted where the reference stands in for the
  program: half of each batch left out, the gossip exchange left out.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_cache" / "jax"))
    from bench.harness import cell as C
    cell = C.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the cell's chips", file=sys.stderr)
        return 3
    devices = devices[:cell.chips]

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    from bench.harness import train
    for kind, dtype, ss in (("program", None, args.seeds),
                            ("control", "bfloat16", args.control_seeds)):
        for s in ss:
            t = time.perf_counter()
            emit(kind=kind, seed=s, **train.calibration(
                cell, s, devices, dtype=dtype),
                seconds=time.perf_counter() - t)
    for s in args.fault_seeds:
        for fault in ("half_batch", "no_exchange"):
            emit(kind="fault", fault=fault, seed=s,
                 **train.calibration(cell, s, devices, fault=fault))
    return 0


if __name__ == "__main__":
    sys.exit(main())
