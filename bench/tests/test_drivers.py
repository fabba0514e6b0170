"""Every cell's traffic names a driver module of ``bench/harness`` that
``runner.run_cell`` can import, with ``run`` and ``calibration`` of
``train.py``'s signatures: a new cell with a new driver is new files."""
import importlib
import inspect

from bench.harness import cell as C
from bench.harness import train


def test_every_traffic_names_a_driver():
    spec = C.load_json(C.ROOT / "BENCHMARK.json")
    drivers = set()
    for w in spec["workloads"]:
        cell = C.load_cell(w["name"])
        mod = importlib.import_module(
            f"bench.harness.{cell.traffic['driver']}")
        for fn in ("run", "calibration"):
            assert (inspect.signature(getattr(mod, fn)).parameters.keys()
                    == inspect.signature(getattr(train, fn)).parameters
                    .keys()), (w["name"], fn)
        drivers.add(cell.traffic["driver"])
    assert {"train", "train_ssgd", "train_mla_moe"} <= drivers
