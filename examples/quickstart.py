"""Quickstart: train the paper's FC net with DPSGD vs SSGD at a large
learning rate in the large-batch setting (the paper's headline experiment).

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core import AlgoConfig, MultiLearnerTrainer
from repro.data import ShardedLoader, TemplateImages
from repro.models import fcnet
from repro.optim import sgd

LR, N_LEARNERS, LOCAL_BATCH, STEPS = 0.5, 5, 400, 120


def train(algo: str, *, lr: float = LR, n_learners: int = N_LEARNERS,
          local_batch: int = LOCAL_BATCH, steps: int = STEPS,
          log_every: int = 20):
    loader = ShardedLoader(TemplateImages(), n_learners=n_learners,
                           local_batch=local_batch, seed=0)
    key = jax.random.PRNGKey(0)
    trainer = MultiLearnerTrainer(
        fcnet.loss_fn, sgd(lr),
        AlgoConfig(algo=algo, topology="random_pair", n_learners=n_learners))
    state = trainer.init(key, fcnet.init_params(key, in_dim=784, hidden=50))
    for step in range(steps):
        state, metrics = trainer.train_step(state, loader.batch(step))
        if step % log_every == 0:
            print(f"  [{algo}] step {step:4d} loss {float(metrics.loss):.4f} "
                  f"sigma_w^2 {float(metrics.sigma_w_sq):.2e}")
    return float(metrics.loss)


def main(*, steps: int = STEPS, local_batch: int = LOCAL_BATCH):
    print(f"large batch (nB={N_LEARNERS * local_batch}), lr={LR}")
    ssgd = train("ssgd", steps=steps, local_batch=local_batch)
    dpsgd = train("dpsgd", steps=steps, local_batch=local_batch)
    print(f"\nfinal loss: SSGD={ssgd:.4f}  DPSGD={dpsgd:.4f} "
          f"-> {'DPSGD converges where SSGD fails (paper Fig. 2a)' if dpsgd < ssgd else 'unexpected'}")
    return ssgd, dpsgd


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
