"""Batched serving example — now a thin CLI over the serve engine
(repro.serve.ServeEngine): continuous batching with the paged KV cache
instead of a hand-rolled loop on the rotating decode path.

Mixed-length prompts are submitted up front; the engine prefills them
token-at-a-time inside the same fused decode step (no separate prefill
trace), recycles slots as requests finish, and every generated token is
counted — including the first, which the old example dropped.  Timing
starts after ``warmup()`` (compile excluded) and each step host-syncs on
the logits, so the tok/s figure is honest wall-clock.

    PYTHONPATH=src python examples/serve_batched.py --arch gemma2-27b

``--preset full`` serves the architecture at its own widths (random
weights); the default ``smoke`` uses its reduced config.
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.serve import ServeEngine


def build_engine(arch: str = "transformer-100m", preset: str = "smoke", *,
                 slots: int = 4, page: int = 8, buf: int = 64, seed: int = 0):
    """A ServeEngine over ``arch`` with weights from ``seed``: its own
    widths with ``preset='full'``, its reduced config with ``'smoke'``."""
    cfg = get_config(arch)
    if preset == "smoke":
        cfg = cfg.smoke_config()
    api = build_model(cfg)
    if not api.has_paged:
        raise SystemExit(f"{cfg.name}: family {cfg.family} has no paged "
                         "decode path (text families only)")
    params = api.init(jax.random.PRNGKey(seed))
    return ServeEngine(api, params, n_slots=slots, page_size=page,
                       max_len=buf)


def submit_mixed(eng, n_req: int, new_tokens: int, seed: int = 0):
    """Queue ``n_req`` prompts of random length (1 .. buf - new_tokens)."""
    rng = np.random.default_rng(seed)
    vocab = eng.api.cfg.vocab
    max_prompt = max(1, eng.max_len - new_tokens)
    return [eng.submit(rng.integers(1, vocab,
                                    rng.integers(1, max_prompt + 1)).tolist(),
                       new_tokens)
            for _ in range(n_req)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="transformer-100m")
    ap.add_argument("--preset", choices=["full", "smoke"], default="smoke")
    ap.add_argument("--batch", type=int, default=4, help="decode slots")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--buf", type=int, default=64,
                    help="max tokens per request (prompt + generated)")
    ap.add_argument("--page", type=int, default=8, help="KV page size")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests to serve (default: 2x slots)")
    args = ap.parse_args()

    eng = build_engine(args.arch, args.preset, slots=args.batch,
                       page=args.page, buf=args.buf)
    cfg = eng.api.cfg
    n_req = args.requests or 2 * args.batch
    reqs = submit_mixed(eng, n_req, args.new_tokens)

    eng.warmup()                      # compile outside the timed region
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0

    total = eng.generated_total
    print(f"arch={cfg.name} slots={args.batch} page={args.page} "
          f"buf={args.buf} requests={n_req}")
    print(f"{dt * 1e3 / eng.real_steps:.1f} ms/step  "
          f"({total / dt:.1f} tok/s aggregate, {total} tokens, "
          f"{eng.real_steps} steps)")
    print("sequences:")
    for r in reqs[:4]:
        print("  ", r.generated[:16], "...")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
