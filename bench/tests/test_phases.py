"""Device time per train-step phase (``bench/harness/phases.py``): the sums
over hand-made events, the op -> phase map decoded from a real profiler
trace of a scoped step on the CPU, and the readers on a traced run."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

from bench.harness import cell as C
from bench.harness import phases as PH
from bench.harness import trace as T


def events():
    # window 0..100 ns.  Device 0: a step 0..70 holding a loop op around
    # two scoped ops and an op with no phase, then a step 80..120 that the
    # window cuts.  Device 1: a step -10..10 that the window cuts, then a
    # step 10..50 and its two ops, and an op of another program at 60..70.
    host = [("bench.traced", 0, 100), ("bench.step", 0, 50)]
    ops = {0: [("while.3", 0, 60), ("fusion.1", 0, 30), ("kern.2", 30, 60),
               ("copy.7", 60, 70), ("fusion.1", 85, 120)],
           1: [("fusion.1", -10, 10), ("fusion.1", 10, 40),
               ("add.4", 40, 50), ("feed.9", 60, 70)]}
    modules = {0: [("jit_step(1)", 0, 70), ("jit_step(1)", 80, 120)],
               1: [("jit_step(1)", -10, 10), ("jit_step(1)", 10, 50),
                   ("jit_feed(2)", 60, 70)]}
    return T.Events(host=host, ops=ops, modules=modules)


PHASES = {"fusion.1": "fwd_bwd", "kern.2": "gossip", "add.4": "stats",
          "while.3": "fwd_bwd"}


def test_whole_steps_leave_out_the_cut_ones():
    ev = events()
    assert PH.whole_steps(ev, "jit_step", 2) == {0: [(0, 70)], 1: [(10, 50)]}
    assert PH.whole_steps(ev, "jit_step", 1) == {0: [(0, 70)]}
    # the trace reduction counts the step modules that start in the window
    r = T.reduce_events(ev, n_devices=2, kernels={}, step_module="jit_step")
    assert r.steps == 2


def test_phase_seconds_by_hand():
    ev = events()
    steps = PH.whole_steps(ev, "jit_step", 2)
    s = PH.phase_seconds(ev, PHASES, steps)
    # two whole steps: fwd_bwd 30 (device 0) + 30 (device 1); the op of the
    # cut steps and the other program's op are left out
    assert s["fwd_bwd"] == pytest.approx(30e-9)
    assert s["gossip"] == pytest.approx(15e-9)
    assert s["stats"] == pytest.approx(5e-9)
    assert s["other"] == pytest.approx(5e-9)        # copy.7: no phase
    # the loop op spans the ops it runs and is left out, as in the per-op
    # sums of the trace reduction: the phases add up to the summed op time
    # of the whole steps (70 + 40 ns) over their number
    assert sum(s.values()) == pytest.approx(110e-9 / 2)


def test_an_op_past_its_step_is_clipped_to_it():
    ev = events()
    ev.ops[0][3] = ("copy.7", 60, 75)
    s = PH.phase_seconds(ev, PHASES, {0: [(0, 70)]})
    assert s["other"] == pytest.approx(10e-9)


def test_phase_seconds_needs_the_window_and_a_whole_step():
    ev = events()
    with pytest.raises(ValueError):
        PH.phase_seconds(ev, PHASES, {0: [], 1: []})
    ev.host = [h for h in ev.host if h[0] != "bench.traced"]
    with pytest.raises(ValueError):
        PH.whole_steps(ev, "jit_step", 1)


def test_wire_format_reader():
    # field 1 varint 150, field 2 string "hi", field 3 fixed64, field 4
    # nested message {1: 7}
    buf = (bytes([0x08, 0x96, 0x01, 0x12, 2]) + b"hi" + bytes([0x19])
           + bytes(8) + bytes([0x22, 2, 0x08, 7]))
    got = list(PH.fields(buf))
    assert got[0] == (1, 150) and bytes(got[1][1]) == b"hi"
    assert got[2][0] == 4 and list(PH.fields(got[2][1])) == [(1, 7)]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A profiler trace of a jitted step with two phases and an unscoped
    op, and the step's compiled HLO text."""
    import jax
    import jax.numpy as jnp
    from repro import obs

    def _phases_probe_step(w, x):
        with obs.phase("fwd_bwd"):
            g = jax.grad(lambda w: jnp.sum(jnp.tanh(x @ w)))(w)
        with obs.phase("update"):
            w = w - 0.1 * g
        return w, jnp.sum(g)

    step = jax.jit(_phases_probe_step)
    w, x = jnp.ones((64, 64)), jnp.ones((16, 64))
    step(w, x)[0].block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    jax.block_until_ready(step(w, x))
    jax.profiler.stop_trace()
    text = step.lower(w, x).compile().as_text()
    return next(Path(d).rglob("*.xplane.pb")), text


def test_op_names_from_a_recorded_trace(cpu_trace):
    """The HLO the profiler stored in the trace gives every instruction the
    phase the compiled text gives it."""
    from repro import obs
    path, text = cpu_trace
    mods = PH.op_names(path, step_module="_phases_probe_step")
    assert len(mods) == 1 and "_phases_probe_step" in next(iter(mods))
    assert PH.op_names(path, ran=set(mods)) == mods
    got = PH.phase_map(mods, obs.phase_of)
    want = {}                  # the phase of each instruction in the text
    for m in re.finditer(r'^\s*(?:ROOT )?%?([^\s=]+) = .*metadata=\{[^}]*'
                         r'op_name="([^"]*)"', text, re.M):
        if obs.phase_of(m.group(2)):
            want[m.group(1)] = obs.phase_of(m.group(2))
    assert got == want
    assert set(got.values()) == {"fwd_bwd", "update"}


def test_readers_on_a_traced_run(cpu_trace, monkeypatch, tmp_path):
    """``phase_ms.*`` and ``mfu.fwd_bwd`` read the run's trace file; the
    phases sum to the summed op time; nothing is read without phases."""
    from repro import obs
    path, _ = cpu_trace
    step = "_phases_probe_step"
    phases = PH.phase_map(PH.op_names(path, step_module=step), obs.phase_of)
    fused = sorted(n for n, p in phases.items() if p == "fwd_bwd")[:2]
    upd = next(n for n, p in phases.items() if p == "update")
    # two steps of 400 us inside the window: each a scoped fwd_bwd op, an
    # update op and an op with no phase; a third step the window cuts
    ev = T.Events(host=[("bench.traced", 0, 1_000_000)],
                  ops={0: [(fused[0], 0, 250_000), (upd, 250_000, 350_000),
                           ("no-such-op.1", 350_000, 400_000),
                           (fused[0], 400_000, 650_000),
                           (upd, 650_000, 750_000),
                           ("no-such-op.1", 750_000, 800_000),
                           (fused[0], 800_000, 1_050_000)]},
                  modules={0: [(f"jit_{step}(1)", 0, 400_000),
                               (f"jit_{step}(1)", 400_000, 800_000),
                               (f"jit_{step}(1)", 800_000, 1_200_000)]})
    cell = C.load_cell("train.dpsgd.t100m.1chip")
    cell.traffic["step_module"] = step
    tdir = tmp_path / "trace"
    (tdir / cell.name).mkdir(parents=True)
    shutil.copy(path, tdir / cell.name / "x.xplane.pb")
    monkeypatch.setattr(PH, "TRACE_DIR", tdir)
    monkeypatch.setattr(T, "load", lambda p: ev)
    run = C.Run(cell=cell)
    run.trace = T.reduce_events(ev, n_devices=1, kernels={},
                                step_module=step)
    assert run.trace.steps == 3
    from bench.harness import model as M
    run.counters.update(chips=1, tokens_per_step=4096, seq=512,
                        shard=M.shard_of(cell.config))
    run.extra["peak"] = {"flops": 197e12}
    ms = {p: C.reader(f"phase_ms.{p}")(run)
          for p in ("fwd_bwd", "gossip", "update", "stats", "other")}
    assert ms == pytest.approx({"fwd_bwd": 0.25, "gossip": 0.0,
                                "update": 0.1, "stats": 0.0, "other": 0.05})
    # the phases sum to a whole step's op time
    assert sum(ms.values()) == pytest.approx(0.4, rel=1e-3)
    mfu = C.reader("mfu.fwd_bwd")(run)
    from bench.harness.flops import train_flops_per_token
    want = (100 * 4096 * train_flops_per_token(run.counters["shard"], 512)
            / 0.25e-3 / 197e12)
    assert mfu == pytest.approx(want)

    monkeypatch.setattr(PH, "TRACE_DIR", tmp_path / "none")
    bare = C.Run(cell=cell)
    bare.trace = run.trace
    assert C.reader("phase_ms.fwd_bwd")(bare) is None
    # a program from before the scopes has no repro.obs: nothing is read
    monkeypatch.setattr(PH, "TRACE_DIR", tdir)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    old = C.Run(cell=cell)
    old.trace = run.trace
    assert C.reader("phase_ms.other")(old) is None
    assert C.reader("mfu.fwd_bwd")(old) is None


def test_benchmark_lists_the_phase_metrics():
    spec = json.loads((C.ROOT / "BENCHMARK.json").read_text())
    per = {m["name"]: m for m in spec["per_layer"]}
    for p in ("fwd_bwd", "gossip", "update", "stats", "other"):
        assert (C.BENCH / "metrics" / f"phase_ms.{p}.py").exists()
        assert per[f"phase_ms.{p}"]["source"] == "device_trace"
    assert "train.dpsgd.t100m.1chip" not in per["phase_ms.update"][
        "workloads"]
    assert "train.dpsgd.t100m.ring4" not in per["phase_ms.stats"][
        "workloads"]


def test_whole_steps_of_a_recorded_ring4_trace():
    """A cut of a four-chip ring4 trace: two whole steps on each chip, and
    their ops add up to the step programs' spans less a little idle."""
    ev = T.load(Path(__file__).parent / "data" / "ring4_v5e.xplane.pb")
    steps = PH.whole_steps(ev, "train_step", 4)
    assert {d: len(s) for d, s in steps.items()} == {0: 2, 1: 2, 2: 2, 3: 2}
    per_step = sum(PH.phase_seconds(ev, {}, steps).values())
    span = sum(b - a for s in steps.values() for a, b in s) / 8 * 1e-9
    assert 0.995 * span < per_step <= span
