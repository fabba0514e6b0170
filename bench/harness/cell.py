"""What a run is: its cell, as ``BENCHMARK.json`` and the data files name it,
and the record a run leaves for the metric readers.

Everything that belongs to one configuration, traffic mix or metric is
found by name:

    bench/configs/<config>.json     the configuration (file named in
                                    BENCHMARK.json)
    bench/traffic/<traffic>.json    the traffic mix; its ``driver`` names
                                    the module of bench/harness that runs it
    bench/limits/<workload>.json    the limits of the numbers compared
    bench/metrics/<metric>.py       the reader of one metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    spec: dict                   # the whole BENCHMARK.json

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def metrics(self, trace: bool) -> list:
        """The metric entries this cell reports in a run of this kind."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[kind]
                if self.name in m.get("workloads", [self.name])]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = load_json(root / "BENCHMARK.json")
    wl = [w for w in spec["workloads"] if w["name"] == workload]
    if not wl:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    cfg_entry = [c for c in spec["configs"] if c["name"] == wl["config"]][0]
    return Cell(workload=wl, config=load_json(root / cfg_entry["file"]),
                traffic=load_json(root / "bench" / "traffic"
                                  / f"{wl['traffic']}.json"),
                limits=load_json(root / "bench" / "limits"
                                 / f"{workload}.json"),
                spec=spec)


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What one run leaves for the metric readers."""
    cell: Cell
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    counters: dict = dataclasses.field(default_factory=dict)
    samples: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    trace: Any = None            # trace.Reduction of the traced segment
    device: dict = dataclasses.field(default_factory=dict)
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def reader(metric: str, root: Path = ROOT) -> Callable[[Run],
                                                      Optional[float]]:
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``: the
    metric's value, or None where the run left it nothing to read."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
