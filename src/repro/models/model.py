"""build_model(cfg) — one uniform API over every architecture family.

API (all pure functions):
  init(key)                          -> params        (single learner, no stack)
  loss_fn(params, batch)             -> scalar        (one learner's minibatch)
  loss_and_stats(params, batch)      -> (scalar, stats) stats: counters the
                                        forward keeps; {"moe_held_assignments"}
                                        (held experts' (token, expert) pairs,
                                        summed over MoE layers) or {}
  apply(params, batch)               -> logits        (train/prefill forward)
  init_cache(params, batch, buf_len) -> decode cache
  decode_step(params, cache, tokens, pos) -> (logits, cache)
  train_batch_spec(global_batch, seq)     -> ShapeDtypeStruct pytree
  decode_batch_spec(global_batch, seq)    -> (cache_spec builder inputs)

Families:
  text (dense|moe|ssm|hybrid): batch = {tokens, labels, mask}
  vlm:   batch += patch_embeds (B, P, d) stub vision embeddings; text length
         is seq - P so the *total* token count matches the assigned shape.
  audio: enc-dec; batch = {frames (B, S/2, d), tokens/labels/mask (B, S/2)} —
         S/2 + S/2 = S total positions per the assigned shape.
"""
from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import encdec, transformer
from .layers import cross_entropy, dtype_of


class ModelAPI(NamedTuple):
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable
    apply: Callable
    init_cache: Callable
    decode_step: Callable
    train_batch_spec: Callable
    has_decode: bool
    # paged serving decode (ISSUE 7): per-slot positions + shared page
    # pools; None/False for families without it (audio enc-dec, M-RoPE vlm)
    init_paged_cache: Any = None
    paged_decode_step: Any = None
    reset_slot: Any = None
    has_paged: bool = False
    loss_and_stats: Any = None


def _mrope_positions(cfg: ModelConfig, P: int, S_text: int):
    """(3, P + S_text) (t, h, w) ids: image patches on an HxW grid at t=0,
    text tokens strictly after (qwen2-vl scheme)."""
    g = max(1, int(math.sqrt(P)))
    t_img = jnp.zeros((P,), jnp.int32)
    h_img = (jnp.arange(P) // g).astype(jnp.int32)
    w_img = (jnp.arange(P) % g).astype(jnp.int32)
    base = jnp.maximum(jnp.maximum(h_img.max(), w_img.max()), 0) + 1
    t_txt = base + jnp.arange(S_text, dtype=jnp.int32)
    pos = jnp.stack([
        jnp.concatenate([t_img, t_txt]),
        jnp.concatenate([h_img, t_txt]),
        jnp.concatenate([w_img, t_txt]),
    ])
    return pos


def build_model(cfg: ModelConfig) -> ModelAPI:
    act_dt = dtype_of(cfg.compute_dtype)

    # ------------------------------------------------------------- text LM --
    if cfg.family in ("dense", "moe", "ssm", "hybrid"):
        def init(key):
            return transformer.init_params(key, cfg)

        def apply(params, batch):
            return transformer.apply(params, cfg, batch["tokens"])

        def loss_and_stats(params, batch):
            logits, stats = transformer.apply(params, cfg, batch["tokens"],
                                              return_stats=True)
            return cross_entropy(logits, batch["labels"], batch.get("mask"),
                                 logical_vocab=cfg.vocab), stats

        def loss_fn(params, batch):
            return loss_and_stats(params, batch)[0]

        def init_cache(params, batch_size, buf_len):
            return transformer.init_cache(cfg, batch_size, buf_len)

        def decode_step(params, cache, tokens, pos):
            return transformer.decode_step(params, cfg, cache, tokens, pos)

        def train_batch_spec(global_batch, seq):
            tok = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32)
            return {"tokens": tok, "labels": tok,
                    "mask": jax.ShapeDtypeStruct((global_batch, seq),
                                                 jnp.float32)}

        def init_paged_cache(params, n_slots, n_pages, page_size):
            return transformer.init_paged_cache(cfg, n_slots, n_pages,
                                                page_size)

        def paged_decode_step(params, cache, tokens, positions, page_table,
                              advance=None):
            return transformer.paged_decode_step(params, cfg, cache, tokens,
                                                 positions, page_table,
                                                 advance)

        decodes = not transformer.training_only(cfg)
        return ModelAPI(cfg=cfg, init=init, loss_fn=loss_fn, apply=apply,
                        init_cache=init_cache, decode_step=decode_step,
                        train_batch_spec=train_batch_spec,
                        has_decode=decodes,
                        init_paged_cache=init_paged_cache,
                        paged_decode_step=paged_decode_step,
                        reset_slot=transformer.reset_slot, has_paged=decodes,
                        loss_and_stats=loss_and_stats)

    # ---------------------------------------------------------------- VLM --
    elif cfg.family == "vlm":
        P = cfg.n_frontend_tokens

        def init(key):
            return transformer.init_params(key, cfg)

        def apply(params, batch):
            S_text = batch["tokens"].shape[1]
            pos = _mrope_positions(cfg, P, S_text)
            return transformer.apply(params, cfg, batch["tokens"],
                                     positions=pos,
                                     extra_embeds=batch["patch_embeds"])

        def loss_fn(params, batch):
            logits = apply(params, batch)[:, P:, :]
            return cross_entropy(logits, batch["labels"], batch.get("mask"),
                                 logical_vocab=cfg.vocab)

        def init_cache(params, batch_size, buf_len):
            return transformer.init_cache(cfg, batch_size, buf_len)

        def decode_step(params, cache, tokens, pos):
            return transformer.decode_step(params, cfg, cache, tokens, pos)

        def train_batch_spec(global_batch, seq):
            s_text = seq - P
            tok = jax.ShapeDtypeStruct((global_batch, s_text), jnp.int32)
            return {"tokens": tok, "labels": tok,
                    "mask": jax.ShapeDtypeStruct((global_batch, s_text),
                                                 jnp.float32),
                    "patch_embeds": jax.ShapeDtypeStruct(
                        (global_batch, P, cfg.d_model), act_dt)}

    # ------------------------------------------------------------- audio --
    elif cfg.family == "audio":
        def init(key):
            return encdec.init_params(key, cfg)

        def apply(params, batch):
            return encdec.apply(params, cfg, batch["frames"], batch["tokens"])

        def loss_fn(params, batch):
            logits = apply(params, batch)
            return cross_entropy(logits, batch["labels"], batch.get("mask"),
                                 logical_vocab=cfg.vocab)

        def init_cache(params, frames, buf_len):
            return encdec.init_cache(params, cfg, frames, buf_len)

        def decode_step(params, cache, tokens, pos):
            return encdec.decode_step(params, cfg, cache, tokens, pos)

        def train_batch_spec(global_batch, seq):
            s = seq // 2
            tok = jax.ShapeDtypeStruct((global_batch, s), jnp.int32)
            return {"frames": jax.ShapeDtypeStruct((global_batch, s,
                                                    cfg.d_model), act_dt),
                    "tokens": tok, "labels": tok,
                    "mask": jax.ShapeDtypeStruct((global_batch, s),
                                                 jnp.float32)}

    else:
        raise ValueError(cfg.family)

    return ModelAPI(cfg=cfg, init=init, loss_fn=loss_fn, apply=apply,
                    init_cache=init_cache, decode_step=decode_step,
                    train_batch_spec=train_batch_spec,
                    has_decode=True,
                    loss_and_stats=lambda p, b: (loss_fn(p, b), {}))


def make_synthetic_batch(cfg: ModelConfig, key, global_batch: int, seq: int):
    """Concrete random batch matching train_batch_spec (for smoke tests)."""
    api = build_model(cfg)
    spec = api.train_batch_spec(global_batch, seq)

    def fill(s):
        if jnp.issubdtype(s.dtype, jnp.integer):
            return jax.random.randint(key, s.shape, 0, cfg.vocab, s.dtype)
        if "mask" in str(s.shape):
            return jnp.ones(s.shape, s.dtype)
        return jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype) * 0.1

    batch = {k: fill(v) for k, v in spec.items()}
    if "mask" in batch:
        batch["mask"] = jnp.ones(spec["mask"].shape, jnp.float32)
    return batch
