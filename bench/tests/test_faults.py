"""A whole run past the harness's look for a chip, at a size the CPU holds,
with the timed path broken underneath: ``correct`` has to come out false
for every fault the cell can have, and true for the sound program.

The configuration is the cells' dense block at test widths
(``data/tiny-dense.json``); traffic and limits are the cells' own, with the
lengths shrunk."""
import time

import jax
import pytest

from bench.harness import cell as C
from bench.harness import runner

TINY = C.load_json(C.BENCH / "tests" / "data" / "tiny-dense.json")
SEED = (1 << 40) + 12345


def tiny(workload):
    cell = C.load_cell(workload)
    cell.config = dict(TINY)
    cell.traffic.update(seq=16, batches=6)
    return cell


def run(cell, tmp_path, devices=None):
    devices = devices or jax.devices()[:1]
    return runner.run_cell(cell, seed=SEED, seconds=1.0, trace=False,
                           devices=devices, t_start=time.perf_counter(),
                           trace_dir=tmp_path)


# -- training on one chip ----------------------------------------------------

TRAIN = "train.dpsgd.t100m.1chip"


def plant_state_unchanged(mp):
    from repro.core.trainer import MultiLearnerTrainer
    real = MultiLearnerTrainer._train_step
    mp.setattr(MultiLearnerTrainer, "_train_step",
               lambda self, st, b: (st, real(self, st, b)[1]))


def plant_half_batch(mp):
    from repro.models import model
    real = model.cross_entropy

    def half(logits, labels, mask=None, **kw):
        h = logits.shape[0] // 2
        return real(logits[:h], labels[:h],
                    None if mask is None else mask[:h], **kw)
    mp.setattr(model, "cross_entropy", half)


def plant_no_exchange(mp):
    from repro.kernels import ops
    real = ops.flat_gossip_update

    def alone(w, remote, grads, mu, partners, coefs, **kw):
        own = jax.numpy.broadcast_to(jax.numpy.arange(w.shape[0]),
                                     partners.shape).astype(partners.dtype)
        return real(w, remote, grads, mu, own, coefs, **kw)
    mp.setattr(ops, "flat_gossip_update", alone)


def test_train_sound_run_is_correct(tmp_path):
    r = run(tiny(TRAIN), tmp_path)
    assert r.correct, [(c.name, c.value, c.limit) for c in r.checks]
    assert r.attempted > 0


@pytest.mark.parametrize("plant", [plant_state_unchanged, plant_half_batch,
                                   plant_no_exchange])
def test_train_fault_is_not_correct(plant, tmp_path, monkeypatch):
    plant(monkeypatch)
    r = run(tiny(TRAIN), tmp_path)
    assert not r.correct, [(c.name, c.value, c.limit) for c in r.checks]


# -- the control at test size ----------------------------------------------------

def test_train_control_separates_from_the_program():
    """The program's own bfloat16 path against float32, both against the
    float32 reference: the control reads at least ten times the program
    on one of the numbers compared."""
    from bench.harness import train
    cell = tiny(TRAIN)
    d = jax.devices()[:1]
    prog = train.calibration(cell, SEED, d)
    ctl = train.calibration(cell, SEED, d, dtype="bfloat16")
    assert max(ctl[k] / max(prog[k], 1e-12) for k in prog) >= 10

