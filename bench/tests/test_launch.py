"""The four-chip cell's whole run (one learner per chip, ring gossip by
collective-permute) on four virtual CPU devices, ordered in the mesh as a
2x2 host's ring orders them, past the harness's look for a chip, with the cell's own traffic and limits and the lengths shrunk:
``correct`` comes out true for the sound program and false for every fault
the cell can have, and the control (the program's bfloat16 path) reads
apart from the program.  Each case runs in a child process, which alone
can ask for four host devices."""
import json
import os
import subprocess
import sys

import pytest

from bench.harness import cell as C

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench.harness import cell as C, runner, train
import repro.launch.mesh as lm
make_mesh = lm.make_mesh


def ring_order(shape, names, devices):
    # a 2x2 host's ring does not run in device-id order
    d = list(devices)
    return make_mesh(shape, names, devices=[d[0], d[1], d[3], d[2]])
lm.make_mesh = ring_order
cell = C.load_cell("train.dpsgd.t100m.ring4")
cell.config = C.load_json(C.BENCH / "tests" / "data" / "tiny-dense.json")
cell.traffic.update(seq=16, batches=6, local_batch=2)
d = jax.devices()[:4]
seed = (1 << 40) + 777
case = sys.argv[2]
if case == "control":
    out = {"program": train.calibration(cell, seed, d),
           "control": train.calibration(cell, seed, d, dtype="bfloat16")}
else:
    from repro.launch import train as lt
    if case == "state_unchanged":
        real = lt.make_dpsgd_train_step

        def make(*a, **kw):
            fn = real(*a, **kw)
            return lambda st, b: (st, fn(st, b)[1])
        lt.make_dpsgd_train_step = make
    elif case == "half_batch":
        from repro.models import model
        ce = model.cross_entropy

        def half(logits, labels, mask=None, **kw):
            h = logits.shape[0] // 2
            return ce(logits[:h], labels[:h],
                      None if mask is None else mask[:h], **kw)
        model.cross_entropy = half
    elif case == "no_exchange":
        lt.mix_ppermute_schedule_flat = lambda p, *a: p
    r = runner.run_cell(cell, seed=seed, seconds=1.0, trace=False,
                        devices=d, t_start=time.perf_counter(),
                        trace_dir=C.ROOT / ".bench_cache" / "test")
    out = {"correct": r.correct, "attempted": r.attempted,
           "checks": {c.name: [c.value, c.limit] for c in r.checks}}
print(json.dumps(out))
"""


def child(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(C.ROOT), case],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_ring4_sound_run_is_correct():
    got = child("sound")
    assert got["correct"] and got["attempted"] > 0, got


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_ring4_fault_is_not_correct(fault):
    got = child(fault)
    assert not got["correct"], got


def test_ring4_control_separates_from_the_program():
    got = child("control")
    prog, ctl = got["program"], got["control"]
    assert max(ctl[k] / max(prog[k], 1e-12) for k in prog) >= 10, got
