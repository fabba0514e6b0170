"""Paper Table 3/5 (ASR heldout loss, proxied at CPU scale).

The SWB tasks' defining stress (paper footnote 3) is the highly uneven
class distribution (32k zipfian classes).  Proxy: framewise classification
with 100 zipf(1.2)-distributed template classes, large batch (nB=2000),
lr scan.  Expected pattern (paper Table 5): parity at safe lr; at the
critical lr SSGD fails while DPSGD converges; at extreme lr both fail."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .common import final_loss, write_table


@dataclasses.dataclass(frozen=True)
class ZipfTemplates:
    n_classes: int = 100
    alpha: float = 1.2
    seed: int = 5

    def _templates(self):
        key = jax.random.PRNGKey(self.seed)
        return (jax.random.uniform(key, (self.n_classes, 784))
                > 0.8).astype(jnp.float32)

    def sample(self, key, b):
        k1, k2 = jax.random.split(key)
        ranks = jnp.arange(1, self.n_classes + 1, dtype=jnp.float32)
        lab = jax.random.categorical(
            k1, jnp.broadcast_to(-self.alpha * jnp.log(ranks),
                                 (b, self.n_classes)))
        x = jnp.clip(0.2 + 0.2 * jax.random.normal(k2, (b, 784))
                     + 0.8 * self._templates()[lab], 0, 1)
        return {"image": x, "label": lab.astype(jnp.int32)}


def main(argv=None):
    from repro.models import fcnet

    from .common import parse_smoke
    smoke = parse_smoke(argv)
    steps = 24 if smoke else 120
    ds = ZipfTemplates()
    rows = []
    us = 0.0
    for lr in (0.5,) if smoke else (0.25, 0.5, 1.0):
        for algo in ("ssgd", "dpsgd"):
            # 100-class head needs its own init: patch via custom optimizer? no:
            # train_fc uses fcnet.init_params(n_classes=10); do it inline here
            import jax as _jax
            from repro.core import AlgoConfig, MultiLearnerTrainer
            from repro.data import ShardedLoader
            from repro.optim import sgd
            loader = ShardedLoader(ds, n_learners=5, local_batch=400)
            key = _jax.random.PRNGKey(0)
            params = fcnet.init_params(key, in_dim=784, hidden=50,
                                       n_classes=100)
            tr = MultiLearnerTrainer(
                fcnet.loss_fn, sgd(lr),
                AlgoConfig(algo=algo, topology="random_pair", n_learners=5))
            st = tr.init(key, params)
            import time
            st, m = tr.train_step(st, loader.batch(0))
            t0 = time.perf_counter()
            losses = []
            for i in range(1, steps):
                st, m = tr.train_step(st, loader.batch(i))
                losses.append(float(m.loss))
            us = (time.perf_counter() - t0) / (steps - 1) * 1e6
            heldout = float(tr.eval_loss(st, loader.eval_batch(512)))
            rows.append([algo, lr, final_loss(losses), heldout])
    write_table("table5_asr_proxy", ["algo", "lr", "train_loss", "heldout"],
                rows)
    crit = {r[0]: r[3] for r in rows if r[1] == 0.5}
    derived = (f"critical-lr heldout ssgd={crit['ssgd']:.3f} "
               f"dpsgd={crit['dpsgd']:.3f} (paper T5: SSGD fails, DPSGD ok)")
    print(f"table5_asr_proxy,{us:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
