"""A DeepSeek-V3-class configuration file -> the program's model for one
chip's share, and its weights from a seed.

The file keeps the published keys of the model's ``config.json``, with the
cut ones listed in its ``reduced``: ``n_routed_experts`` is the number of
experts held here (``experts_held_first`` onwards), ``router_experts`` the
router's published width, ``vocab_size`` the slice of the vocabulary held
here.  ``model_config`` maps the keys onto the program's ``ModelConfig``;
``make_params`` draws every leaf as ``model.make_params`` does, and the
selection bias (a buffer the router adds for selection only) from
N(0, ``BIAS_STD``^2).
"""
from __future__ import annotations

import dataclasses

import jax

from . import model as M

BIAS_STD = 0.01


@dataclasses.dataclass(frozen=True)
class Share:
    """What one chip holds of the configuration."""
    n_layers: int
    dense_layers: int
    d_model: int
    n_heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    dense_ff: int
    expert_ff: int
    shared_ff: int
    router_experts: int
    held_first: int
    held: int
    top_k: int
    routed_scaling: float
    norm_topk: bool
    vocab: int
    norm_eps: float
    rope_theta: float
    dtype: str

    @property
    def moe_layers(self) -> int:
        return self.n_layers - self.dense_layers


def share_of(cfg: dict) -> Share:
    if cfg.get("model_type") != "deepseek_v3":
        raise ValueError(f"{cfg['name']}: not a deepseek_v3 configuration")
    if cfg.get("q_lora_rank") or cfg.get("scoring_func") != "sigmoid" \
            or cfg.get("topk_method") != "noaux_tc" \
            or cfg.get("n_group", 1) != 1 or cfg.get("moe_layer_freq") != 1 \
            or cfg.get("num_nextn_predict_layers") \
            or cfg.get("hidden_act") != "silu" \
            or cfg.get("tie_word_embeddings"):
        raise ValueError(f"{cfg['name']}: the program builds plain-q MLA "
                         "with sigmoid noaux_tc routing in one group, MoE in "
                         "every layer after the dense ones, SwiGLU and an "
                         "untied head")
    return Share(
        n_layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        dense_ff=cfg["intermediate_size"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        router_experts=cfg["router_experts"],
        held_first=cfg["experts_held_first"], held=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]), vocab=cfg["vocab_size"],
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), dtype=cfg["torch_dtype"])


def model_config(cfg: dict, dtype: str | None = None):
    """The program's ModelConfig for one chip's share of ``cfg``
    (``dtype`` overrides the configuration's: the control).  A program
    without latent attention cannot build it: this raises at once."""
    from repro.configs.base import ModelConfig
    if "attn_kind" not in ModelConfig.__dataclass_fields__:
        raise RuntimeError("this program has no latent attention (MLA)")
    s = share_of(cfg)
    dt = dtype or s.dtype
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=s.n_layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv_heads=s.n_heads,
        d_ff=s.expert_ff, vocab=s.vocab, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, attn_kind="mla", kv_lora_rank=s.kv_rank,
        qk_nope_head_dim=s.nope, qk_rope_head_dim=s.rope,
        v_head_dim=s.v_dim, n_experts=s.router_experts,
        experts_per_tok=s.top_k, experts_held=(s.held_first, s.held),
        first_dense_layers=s.dense_layers, dense_d_ff=s.dense_ff,
        shared_d_ff=s.shared_ff, router_scoring="sigmoid",
        routed_scaling=s.routed_scaling, norm_topk=s.norm_topk,
        param_dtype=dt, compute_dtype=dt, source=cfg["source"],
        **cfg.get("program", {}))


def build(cfg: dict, dtype: str | None = None):
    from repro.models import build_model
    return build_model(model_config(cfg, dtype))


def _leaf_init(path, spec, key):
    name = "/".join(str(getattr(p, "key", p)) for p in path)
    if name.endswith("select_bias"):
        return jax.random.normal(key, spec.shape, spec.dtype) * BIAS_STD
    return M._leaf_init(path, spec, key)


def make_params(api, seed: int, sharding=None):
    """Every weight of the program's layout, from ``seed``, in one jitted
    call on the device."""
    specs = M.param_specs(api)
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def make(key):
        leaves = [_leaf_init(p, s, jax.random.fold_in(key, i))
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(make, **kw)(M.seed_key(seed))


def n_params(s: Share) -> int:
    """Parameters one chip holds, by the published equations."""
    d, H = s.d_model, s.n_heads
    mla = (d * H * (s.nope + s.rope) + d * (s.kv_rank + s.rope) + s.kv_rank
           + s.kv_rank * H * (s.nope + s.v_dim) + H * s.v_dim * d)
    dense = mla + 3 * d * s.dense_ff + 2 * d
    moe = (mla + d * s.router_experts + s.router_experts
           + 3 * d * s.expert_ff * s.held + 3 * d * s.shared_ff + 2 * d)
    return (s.dense_layers * dense + s.moe_layers * moe
            + 2 * d * s.vocab + d)

