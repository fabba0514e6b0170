"""One run of one cell: the driver its traffic names, the profiler around the
tail of a traced window, and the result line built by the metric readers."""
from __future__ import annotations

import importlib
import shutil
from pathlib import Path

from . import cell as C


class Profiler:
    """The JAX profiler over one traced segment, reduced when it stops."""

    def __init__(self, directory: Path, devices, traffic: dict):
        self.dir = Path(directory)
        self.devices = devices
        self.kernels = traffic.get("kernels", {})
        self.step_module = traffic.get("step_module", "")
        self._span = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self._span = jax.profiler.TraceAnnotation("bench.traced")
        self._span.__enter__()

    def stop(self):
        import jax
        from . import trace
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        path = next(self.dir.rglob("*.xplane.pb"))
        return trace.reduce(path, n_devices=len(self.devices),
                            kernels=self.kernels,
                            step_module=self.step_module)


def run_cell(cell: C.Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_start: float, trace_dir: Path, **kw) -> C.Run:
    driver = importlib.import_module(
        f"{__package__}.{cell.traffic['driver']}")
    profiler = Profiler(trace_dir, devices, cell.traffic) if trace else None
    return driver.run(cell, seed, seconds, trace, devices, t_start,
                      profiler=profiler, **kw)


def result_line(run: C.Run, *, trace: bool, devices) -> dict:
    from . import peaks
    d0 = devices[0]
    run.extra["peak"] = peaks.peak(d0.device_kind)
    metrics = {}
    for m in run.cell.metrics(trace):
        value = C.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(run.device.get("memory_peak_bytes",
                                                      0))}
    line = {"correct": run.correct, "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in run.checks}
    return line
