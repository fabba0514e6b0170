"""Paper Fig. 2 reproduction (MNIST stand-in, see DESIGN.md §9):
n=5 learners, 784-50-50-10 FC net, nB=2000, large lr.

Produces results/bench/paper_fig2_repro.csv with the loss / alpha_e /
sigma_w^2 / Delta_S / Delta2 trajectories for SSGD, SSGD*, DPSGD.

    PYTHONPATH=src python examples/paper_mnist_repro.py
"""
import csv
import os

import jax

from repro.core import AlgoConfig, MultiLearnerTrainer
from repro.data import ShardedLoader, TemplateImages
from repro.models import fcnet
from repro.optim import sgd

LR, STEPS = 0.5, 150
OUT = os.path.join(os.path.dirname(__file__), "..", "results", "bench",
                   "paper_fig2_repro.csv")


def run(algo):
    loader = ShardedLoader(TemplateImages(), n_learners=5, local_batch=400,
                           seed=0)
    key = jax.random.PRNGKey(0)
    tr = MultiLearnerTrainer(
        fcnet.loss_fn, sgd(LR),
        AlgoConfig(algo=algo, topology="random_pair", n_learners=5,
                   noise_std=0.01),
        alpha_for_diag=LR)
    st = tr.init(key, fcnet.init_params(key, in_dim=784, hidden=50))
    rows = []
    for i in range(STEPS):
        st, m = tr.train_step(st, loader.batch(i))
        if i % 10 == 0:
            d = tr.diagnostics(st, loader.batch(10_000 + i))
            acc_batch = loader.eval_batch(512)
            acc = float(jax.jit(fcnet.accuracy)(
                jax.tree_util.tree_map(lambda x: x.mean(0),
                                       tr.params_tree(st)),
                acc_batch))
            rows.append([algo, i, float(m.loss), float(d.alpha_e),
                         float(d.sigma_w_sq), float(d.delta_s),
                         float(d.delta_2), acc])
            print(f"[{algo}] step {i:4d} loss {float(m.loss):7.4f} "
                  f"alpha_e {float(d.alpha_e):6.3f} "
                  f"sigma_w2 {float(d.sigma_w_sq):8.2e} test_acc {acc:.3f}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    allrows = []
    for algo in ("ssgd", "ssgd_star", "dpsgd"):
        print(f"=== {algo} (lr={LR}, nB=2000) ===")
        allrows += run(algo)
    with open(OUT, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["algo", "step", "loss", "alpha_e", "sigma_w_sq",
                    "delta_s", "delta_2", "test_acc"])
        w.writerows(allrows)
    print(f"\nwrote {OUT}")
