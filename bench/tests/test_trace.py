"""The trace reduction: busy/idle union, per-kernel time, exposed
collective time and gap attribution, on hand-made events and on a small
trace recorded on four TPU v5e chips (``data/ring4_v5e.xplane.pb``)."""
from pathlib import Path

import pytest

from bench.harness import trace as T

DATA = Path(__file__).parent / "data"


def events():
    # window 0..100 ns; device 0: ops 10-30 (kernel), 20-40, 60-70
    # (collective, 65-70 hidden by 65-80); device 1: 0-50
    host = [("bench.traced", 0, 100), ("bench.step", 0, 50),
            ("bench.wait", 40, 100), ("bench.sample", 45, 58)]
    ops = {0: [("fusion_kernelA", 10, 30), ("fusion.2", 20, 40),
               ("collective-permute-done", 60, 70), ("add", 65, 80),
               ("while.3", 55, 85), ("late", 95, 130)],
           1: [("fusion.9", 0, 50)]}
    modules = {0: [("jit_step(1)", 10, 40), ("jit_step(2)", 60, 80),
                   ("jit_other", 50, 55)]}
    return T.Events(host=host, ops=ops, modules=modules)


def test_union_and_subtract():
    u = T.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert T.length(u) == 7
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]
    assert T.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]


def test_reduction_by_hand():
    r = T.reduce_events(events(), n_devices=2,
                        kernels={"a": "kernelA", "none": "nothing"},
                        step_module="jit_step")
    assert r.window_s == pytest.approx(100e-9)
    # device 0 busy: 10-40, 55-85, 95-100 = 65; device 1: 50 -> mean 57.5
    assert r.busy_s == pytest.approx(57.5e-9)
    assert r.kernel_s["a"] == pytest.approx(10e-9)   # 20 ns / 2 devices
    assert r.kernel_calls == {"a": 1, "none": 0}
    assert r.kernel_s["none"] == 0
    # collective 60-70 on device 0, 65-70 covered by "add" (the while
    # loop around them hides nothing): 5 exposed / 2
    assert r.collective_s == pytest.approx(5e-9)
    assert r.collective_exposed_s == pytest.approx(2.5e-9)
    assert r.steps == 2
    # device 0 gaps: 0-10 (step), 40-55 (mid 47.5: sample is innermost),
    # 85-95 (wait)
    gaps = dict(r.idle_gaps)
    assert gaps["bench.step"] == pytest.approx(10e-9)
    assert gaps["bench.sample"] == pytest.approx(15e-9)
    assert gaps["bench.wait"] == pytest.approx(10e-9)
    b = r.breakdown()
    assert len(b["device_ops"]) <= 10 and b["idle_gaps"][0][0] == \
        "bench.sample"


def test_window_is_required():
    ev = events()
    ev.host = [h for h in ev.host if h[0] != "bench.traced"]
    with pytest.raises(ValueError):
        T.reduce_events(ev, n_devices=1, kernels={})


CHIP_TRACE = DATA / "ring4_v5e.xplane.pb"


def test_recorded_chip_trace():
    """0.16 s of a trace of the four-chip training step recorded on a TPU
    v5e 2x2 host, cut down to the lines the reduction reads (event stats
    and the long form of op names dropped): every chip was busy for part
    of the window and ran the ring's collectives in every step, and the
    first chip's idle gaps fall inside the window and under the harness's
    host spans."""
    r = T.reduce(CHIP_TRACE, n_devices=4, kernels={},
                 step_module="train_step")
    assert 0 < r.busy_s < r.window_s
    assert r.steps >= 2
    assert 0 < r.collective_s < r.busy_s
    assert 0 <= r.collective_exposed_s <= r.collective_s
    assert 0 < sum(s for _, s in r.idle_gaps) < r.window_s
    assert any(n.startswith("bench.") for n, _ in r.idle_gaps)
