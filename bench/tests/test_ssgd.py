"""The SSGD cell (``train.ssgd.t100m.1chip``) at test size on the CPU: a
whole run past the harness's look for a chip, ``correct`` for the sound
program and not for any fault planted in it, and the control apart."""
import time

import jax
import pytest

from bench.harness import cell as C
from bench.harness import runner
from bench.harness import train_ssgd as S

TINY = C.load_json(C.BENCH / "tests" / "data" / "tiny-dense.json")
SEED = (1 << 40) + 4321
CELL = "train.ssgd.t100m.1chip"


def tiny():
    cell = C.load_cell(CELL)
    cell.config = dict(TINY)
    cell.traffic.update(seq=16, batches=6)
    return cell


def run(tmp_path):
    return runner.run_cell(tiny(), seed=SEED, seconds=1.0, trace=False,
                           devices=jax.devices()[:1],
                           t_start=time.perf_counter(), trace_dir=tmp_path)


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
    """The learners' mean taken as the first learner's: every copy steps
    on learner 0's gradient alone."""
    from repro.core import trainer
    mp.setattr(trainer, "learner_mean", lambda tree: jax.tree_util.tree_map(
        lambda a: a[0], tree))


def test_ssgd_sound_run_is_correct(tmp_path):
    r = run(tmp_path)
    assert r.correct, [(c.name, c.value, c.limit) for c in r.checks]
    assert r.attempted > 0


@pytest.mark.parametrize("plant", [plant_state_unchanged, plant_half_batch,
                                   plant_no_exchange])
def test_ssgd_fault_is_not_correct(plant, tmp_path, monkeypatch):
    plant(monkeypatch)
    r = run(tmp_path)
    assert not r.correct, [(c.name, c.value, c.limit) for c in r.checks]


def test_ssgd_reference_faults_and_control_separate():
    """Through the cell's committed limits (``train.checks``): the sound
    program is correct; the reference's own faults and the program's
    bfloat16 path are not, and read at least ten times the sound program
    on one of the numbers compared."""
    from bench.harness import train
    cell = tiny()
    d = jax.devices()[:1]
    ok = lambda gaps: all(c.ok for c in train.checks(cell, gaps))
    prog = S.calibration(cell, SEED, d)
    assert ok(prog), prog
    for kw in ({"dtype": "bfloat16"}, {"fault": "half_batch"},
               {"fault": "no_exchange"}):
        got = S.calibration(cell, SEED, d, **kw)
        assert not ok(got), (kw, got)
        assert max(got[k] / max(prog[k], 1e-12) for k in prog) >= 10, kw
