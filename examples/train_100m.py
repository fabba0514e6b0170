"""End-to-end training driver (deliverable b): ~100M-parameter dense LM
trained with DPSGD on the synthetic token pipeline, with checkpointing and
heldout eval.  Full production-shape run:

    PYTHONPATH=src python examples/train_100m.py --steps 300 --seq 512

CPU-friendly demo (default): reduced seq/batch, same 100M architecture.
CI smoke: --preset smoke uses the reduced config.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.core import AlgoConfig, MultiLearnerTrainer
from repro.data import ShardedLoader, SyntheticTokenStream
from repro.models import build_model
from repro.optim import sgd, scale_by_schedule, warmup_linear_scale


def build(preset: str = "full", *, learners: int = 4, local_batch: int = 2,
          seq: int = 64, lr: float = 0.5, seed: int = 0):
    """The example's model, data and DPSGD trainer, from a seed.

    Returns (cfg, api, loader, trainer, state); ``preset='full'`` is
    transformer-100m at its own widths, ``'smoke'`` its reduced config.
    """
    cfg = get_config("transformer-100m")
    if preset == "smoke":
        cfg = cfg.smoke_config()
    api = build_model(cfg)
    ds = SyntheticTokenStream(vocab=cfg.vocab)
    loader = ShardedLoader(ds, n_learners=learners, local_batch=local_batch,
                           extra_args=(seq,))
    # paper recipe: warmup + linear scaling, DPSGD random-neighbor gossip
    opt = scale_by_schedule(sgd(lr, momentum=0.9),
                            warmup_linear_scale(10, 1.0))
    trainer = MultiLearnerTrainer(
        api.loss_fn, opt,
        AlgoConfig(algo="dpsgd", topology="random_pair",
                   n_learners=learners))
    key = jax.random.PRNGKey(seed)
    state = trainer.init(key, api.init(key))
    return cfg, api, loader, trainer, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--local-batch", type=int, default=2)
    ap.add_argument("--learners", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--preset", choices=["full", "smoke"], default="full")
    ap.add_argument("--ckpt-dir", default="results/ckpt_100m")
    ap.add_argument("--ckpt-every", type=int, default=20)
    args = ap.parse_args()

    cfg, api, loader, trainer, state = build(
        args.preset, learners=args.learners, local_batch=args.local_batch,
        seq=args.seq, lr=args.lr)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(api.init, jax.random.PRNGKey(0))))
    print(f"model: {cfg.name}  params={n_params / 1e6:.1f}M  "
          f"learners={args.learners}  nB={args.learners * args.local_batch}")

    def ckpt_tree(st):
        # checkpoint the pytree VIEW so checkpoints stay layout-stable
        # across trainer engines (the flat engine stores (n, T, 128))
        v = trainer.state_view(st)
        return {"params": v.params, "opt": v.opt_state}

    if latest_step(args.ckpt_dir) is not None:
        tree, step0 = restore_checkpoint(args.ckpt_dir, ckpt_tree(state))
        state = trainer.state_from_view(state._replace(
            params=tree["params"], opt_state=tree["opt"]))
        state = state._replace(step=jnp.int32(step0))
        print(f"resumed from step {step0}")

    t0 = time.time()
    for i in range(int(state.step), args.steps):
        state, m = trainer.train_step(state, loader.batch(i))
        if i % 5 == 0 or i == args.steps - 1:
            dt = (time.time() - t0) / max(i - int(state.step) + 1, 1)
            print(f"step {i:4d}  loss {float(m.loss):.4f}  "
                  f"sigma_w^2 {float(m.sigma_w_sq):.2e}  {dt:.1f}s/step")
        if args.ckpt_every and i and i % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i, ckpt_tree(state))
    heldout = float(trainer.eval_loss(state, loader.eval_batch(8)))
    print(f"heldout loss: {heldout:.4f}")
    save_checkpoint(args.ckpt_dir, args.steps, ckpt_tree(state))
    print(f"checkpoint saved to {args.ckpt_dir}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
