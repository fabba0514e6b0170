"""A configuration file -> the program's model, and its weights from a seed.

The configuration files under ``bench/configs`` keep the published keys of
the model's own ``config.json``.  ``tensor_parallel`` states the deployment
this chip stands in for: it holds 1/tensor_parallel of every layer's query
heads, KV heads and FFN columns, and of the vocabulary rows.

The weights are the benchmark's, not the program's: ``make_params`` draws
every leaf of the program's parameter layout from the seed in one jitted
call, on the device, in the dtype the program serves or trains in.  The
plain reference (``reference.py``) reads the same arrays.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Shard:
    """What one chip holds of the configuration."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    dtype: str


def shard_of(cfg: dict) -> Shard:
    tp = int(cfg.get("tensor_parallel", 1))
    heads = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", heads)
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    for key in ("num_attention_heads", "num_key_value_heads",
                "intermediate_size", "vocab_size"):
        if cfg.get(key, heads) % tp:
            raise ValueError(f"{cfg['name']}: {key} does not split "
                             f"{tp} ways")
    return Shard(n_layers=cfg["num_hidden_layers"],
                 d_model=cfg["hidden_size"], n_heads=heads // tp,
                 n_kv_heads=kv // tp, head_dim=hd,
                 d_ff=cfg["intermediate_size"] // tp,
                 vocab=cfg["vocab_size"] // tp,
                 norm_eps=float(cfg["rms_norm_eps"]),
                 rope_theta=float(cfg["rope_theta"]),
                 dtype=cfg["torch_dtype"])


def model_config(cfg: dict, dtype: str | None = None):
    """The program's ModelConfig for one chip's shard of ``cfg``.

    ``dtype`` overrides the configuration's (the control runs the
    program's own lower-precision path)."""
    from repro.configs.base import ModelConfig
    s = shard_of(cfg)
    if cfg.get("hidden_act", "silu") != "silu" or cfg.get(
            "tie_word_embeddings"):
        raise ValueError(f"{cfg['name']}: the program's dense block is "
                         "SwiGLU with an untied head")
    dt = dtype or s.dtype
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=s.n_layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_kv_heads,
        head_dim=s.head_dim, d_ff=s.d_ff, vocab=s.vocab,
        rope_theta=s.rope_theta, norm_eps=s.norm_eps, param_dtype=dt,
        compute_dtype=dt, source=cfg["source"], **cfg.get("program", {}))


def build(cfg: dict, dtype: str | None = None):
    from repro.models import build_model
    return build_model(model_config(cfg, dtype))


def _leaf_init(path, spec, key):
    name = "/".join(str(getattr(p, "key", p)) for p in path)
    if "norm" in name:                   # RMSNorm weight 1 (= 1 + 0)
        return jnp.zeros(spec.shape, spec.dtype)
    scale = 0.02 if name == "embed" else 1.0 / math.sqrt(spec.shape[-2])
    x = jax.random.normal(key, spec.shape, spec.dtype)
    return x * jnp.asarray(scale, spec.dtype)


def param_specs(api):
    return jax.eval_shape(api.init, jax.random.PRNGKey(0))


def make_params(api, seed: int, sharding=None):
    """Every weight of the program's layout, from ``seed``, in one jitted
    call on the device (``sharding``: where to place them)."""
    specs = param_specs(api)
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def make(key):
        leaves = [_leaf_init(p, s, jax.random.fold_in(key, i))
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(make, **kw)(seed_key(seed))


def seed_key(seed: int, *tags: int):
    """A key from a seed of any size (seeds may exceed 32 bits).

    The RBG generator: the TPU's own bit generator draws a model's weights
    far faster than threefry."""
    key = jax.random.key(seed % (1 << 31), impl="rbg")
    key = jax.random.fold_in(key, (seed >> 31) % (1 << 31))
    for t in tags:
        key = jax.random.fold_in(key, t)
    return key
