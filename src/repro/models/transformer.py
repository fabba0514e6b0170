"""Decoder-only LM assembled per ModelConfig.

Depth is expressed as n_periods x period, where a *period* is the repeating
heterogeneous block pattern (gemma2: [local, global]; jamba: 7 mamba + 1 attn
with MoE every 2nd layer; xlstm: [mLSTM, sLSTM]; dense: [attn]).  The stack is
a lax.scan over stacked period params, so the HLO is O(period), not O(depth)
— essential for compiling 88-layer models on one CPU core in the dry-run.

Layer spec = (mixer, mlp) with mixer in {attn, attn_local, mla, mamba,
mlstm, slstm} and mlp in {dense, moe, none}.

``cfg.first_dense_layers`` leading layers (DeepSeek-V3's
``first_k_dense_replace``) run before the periods as a scan of their own
over ``params["lead"]`` (periods of one layer): the period's mixer with a dense SwiGLU of width
``cfg.dense_d_ff``, remat per layer as the periods are.  Such a model, and
one with latent attention (``mla``), is built for training only: it has no
decode cache.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (attn_decode, attn_decode_paged, attn_forward,
                        init_attn_cache, init_attn_params,
                        init_paged_attn_cache)
from .layers import (apply_mrope, apply_rope, dense_init, dtype_of,
                     embed_init, rms_norm, softcap)
from .mla import init_mla_params, mla_forward
from .mamba import (init_mamba_cache, init_mamba_params, mamba_decode,
                    mamba_forward)
from .moe import init_moe_params, moe_forward
from .xlstm import (init_mlstm_cache, init_mlstm_params, init_slstm_cache,
                    init_slstm_params, mlstm_block_decode, mlstm_block_forward,
                    slstm_block_decode, slstm_block_forward)


# ---------------------------------------------------------------------------
# period spec
# ---------------------------------------------------------------------------

def period_spec(cfg: ModelConfig) -> Tuple[Tuple[str, str], ...]:
    if cfg.block_period:
        spec = []
        for i, mixer in enumerate(cfg.block_period):
            if cfg.attn_layer_offset >= 0 and i == cfg.attn_layer_offset:
                mixer = "attn"
            if mixer in ("mlstm", "slstm"):
                mlp = "none"
            elif cfg.n_experts and cfg.moe_every and (i % cfg.moe_every
                                                      == cfg.moe_every - 1):
                mlp = "moe"
            else:
                mlp = "dense"
            spec.append((mixer, mlp))
        return tuple(spec)
    mlp = "moe" if cfg.n_experts else "dense"
    if cfg.attn_kind == "mla":
        return (("mla", mlp),)
    if cfg.attn_pattern == "local_global":
        return (("attn_local", mlp), ("attn", mlp))
    if cfg.attn_pattern == "sliding":
        return (("attn_local", mlp),)
    return (("attn", mlp),)


def n_periods(cfg: ModelConfig) -> int:
    p = len(period_spec(cfg))
    n = cfg.n_layers - cfg.first_dense_layers
    assert n % p == 0, (n, p)
    return n // p


def lead_spec(cfg: ModelConfig) -> Tuple[str, str]:
    """(mixer, mlp) of each leading dense layer."""
    return (period_spec(cfg)[0][0], "dense")


def training_only(cfg: ModelConfig) -> bool:
    return cfg.attn_kind == "mla" or cfg.first_dense_layers > 0


def _refuse_decode(cfg: ModelConfig):
    if training_only(cfg):
        raise ValueError(f"{cfg.name}: latent attention and leading dense "
                         "layers are built for training only; there is no "
                         "decode cache")


# ---------------------------------------------------------------------------
# rope closure
# ---------------------------------------------------------------------------

def make_rope_fn(cfg: ModelConfig):
    if not cfg.use_rope:
        return None
    if cfg.mrope_sections:
        return lambda x, pos: apply_mrope(x, pos, cfg.rope_theta,
                                          cfg.mrope_sections)
    return lambda x, pos: apply_rope(x, pos, cfg.rope_theta)


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, mixer: str, mlp: str,
                d_ff: int = 0):
    dt = dtype_of(cfg.param_dtype)
    d = cfg.d_model
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": jnp.zeros((d,), jnp.float32)}
    if mixer in ("attn", "attn_local"):
        p["mixer"] = init_attn_params(k1, d, cfg.n_heads, cfg.n_kv_heads,
                                      cfg.head_dim_, dt)
    elif mixer == "mla":
        p["mixer"] = init_mla_params(k1, cfg, dt)
    elif mixer == "mamba":
        p["mixer"] = init_mamba_params(k1, d, expand=cfg.ssm_expand,
                                       state=cfg.ssm_state, conv=cfg.ssm_conv,
                                       dtype=dt)
    elif mixer == "mlstm":
        p["mixer"] = init_mlstm_params(k1, d, cfg.n_heads, dt)
    elif mixer == "slstm":
        p["mixer"] = init_slstm_params(k1, d, cfg.n_heads, dt)
    else:
        raise ValueError(mixer)
    if mlp == "dense":
        p["norm2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = {"w1": dense_init(k2, d, d_ff, dt),
                    "w3": dense_init(k3, d, d_ff, dt),
                    "w2": dense_init(jax.random.fold_in(k3, 1), d_ff, d, dt)}
    elif mlp == "moe":
        p["norm2"] = jnp.zeros((d,), jnp.float32)
        p["mlp"] = init_moe_params(
            k2, d, cfg.d_ff, cfg.n_experts, dt, held=cfg.held,
            shared_d_ff=cfg.shared_d_ff,
            select_bias=cfg.router_scoring == "sigmoid")
    return p


def init_params(key, cfg: ModelConfig):
    dt = dtype_of(cfg.param_dtype)
    spec = period_spec(cfg)
    np_ = n_periods(cfg)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    k_lead = jax.random.fold_in(k_blocks, 1)

    def init_period(k):
        ks = jax.random.split(k, len(spec))
        return {f"l{i}": _init_layer(ks[i], cfg, mixer, mlp)
                for i, (mixer, mlp) in enumerate(spec)}

    params = {
        "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dt),
        "periods": jax.vmap(init_period)(jax.random.split(k_blocks, np_)),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if cfg.first_dense_layers:
        mixer, mlp = lead_spec(cfg)
        params["lead"] = jax.vmap(
            lambda k: {"l0": _init_layer(k, cfg, mixer, mlp,
                                         d_ff=cfg.dense_d_ff)})(
            jax.random.split(k_lead, cfg.first_dense_layers))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.padded_vocab, dt)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _moe(mlp_params, h, cfg: ModelConfig, return_stats: bool = False):
    return moe_forward(mlp_params, h, n_experts=cfg.n_experts,
                       top_k=cfg.experts_per_tok, scoring=cfg.router_scoring,
                       routed_scaling=cfg.routed_scaling,
                       norm_topk=cfg.norm_topk, held=cfg.held,
                       return_stats=return_stats)


def _layer_forward(lp, x, cfg: ModelConfig, mixer: str, mlp: str, rope_fn,
                   positions):
    """-> (x, held MoE assignments this layer computed (int32))."""
    from .shard_hints import residual_hint
    x = residual_hint(x)
    held = jnp.zeros((), jnp.int32)
    if mixer == "mla":
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        x = x + mla_forward(lp["mixer"], h, cfg, positions)
    elif mixer in ("attn", "attn_local"):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        win = cfg.window if (mixer == "attn_local"
                             or cfg.attn_pattern == "sliding") else 0
        qpos = positions if not cfg.mrope_sections else positions
        # scalar positions for masking: use the time component for M-RoPE
        mask_pos = positions[0] if cfg.mrope_sections else positions
        h = attn_forward(lp["mixer"], h, n_heads=cfg.n_heads,
                         n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim_,
                         rope_fn=rope_fn, q_positions=qpos,
                         window=win, attn_softcap=cfg.attn_softcap,
                         chunk=cfg.attn_chunk, use_pallas=cfg.use_pallas,
                         mask_positions=mask_pos)
        x = x + h
    elif mixer == "mamba":
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        x = x + mamba_forward(lp["mixer"], h, expand=cfg.ssm_expand,
                              state=cfg.ssm_state, conv=cfg.ssm_conv,
                              scan_chunk=cfg.scan_chunk)
    elif mixer == "mlstm":
        x = mlstm_block_forward(lp["mixer"], x, n_heads=cfg.n_heads,
                                chunk=cfg.scan_chunk, norm_eps=cfg.norm_eps)
    elif mixer == "slstm":
        x = slstm_block_forward(lp["mixer"], x, n_heads=cfg.n_heads,
                                chunk=cfg.scan_chunk, norm_eps=cfg.norm_eps)
    if mlp == "dense":
        h = rms_norm(residual_hint(x), lp["norm2"], cfg.norm_eps)
        h = (jax.nn.silu(h @ lp["mlp"]["w1"]) * (h @ lp["mlp"]["w3"])) \
            @ lp["mlp"]["w2"]
        x = x + h
    elif mlp == "moe":
        h = rms_norm(residual_hint(x), lp["norm2"], cfg.norm_eps)
        y, held = _moe(lp["mlp"], h, cfg, return_stats=True)
        x = x + y
    return x, held


def _scan_layers(x, stacked, spec, cfg: ModelConfig, rope_fn, positions):
    """Scan the stacked per-period params over x; -> (x, held MoE
    assignments summed over the layers)."""

    has_moe = any(mlp == "moe" for _, mlp in spec)

    @jax.checkpoint
    def period_body(x, pp):
        # remat per period: the layer scan would otherwise stack every
        # intermediate activation of every period for the backward pass
        # (measured 96 GB -> ~x/period for xlstm-350m train_4k)
        held = jnp.zeros((), jnp.int32)
        for i, (mixer, mlp) in enumerate(spec):
            x, n = _layer_forward(pp[f"l{i}"], x, cfg, mixer, mlp, rope_fn,
                                  positions)
            held = held + n
        return x, (held if has_moe else None)

    x, held = jax.lax.scan(period_body, x, stacked)
    return x, (jnp.sum(held) if has_moe else None)


def forward(params, cfg: ModelConfig, x, positions,
            return_stats: bool = False):
    """x: (B, S, d) input embeddings; positions: (S,) or (3, S) for M-RoPE.
    Returns final hidden states (B, S, d) [, {"moe_held_assignments"}
    where the model has MoE layers]."""
    rope_fn = make_rope_fn(cfg)
    if cfg.first_dense_layers:
        x, _ = _scan_layers(x, params["lead"], (lead_spec(cfg),), cfg,
                            rope_fn, positions)
    x, held = _scan_layers(x, params["periods"], period_spec(cfg), cfg,
                           rope_fn, positions)
    if not return_stats:
        return x
    return x, ({} if held is None else {"moe_held_assignments": held})


def logits_from_hidden(params, cfg: ModelConfig, x):
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head
    return softcap(logits, cfg.final_softcap)


def embed_tokens(params, cfg: ModelConfig, tokens):
    return params["embed"][tokens] * math.sqrt(cfg.d_model)


def apply(params, cfg: ModelConfig, tokens, positions=None, extra_embeds=None,
          return_stats: bool = False):
    """tokens: (B, S) -> logits (B, S_total, V) [, stats as ``forward``].

    extra_embeds: (B, P, d) frontend stub embeddings (audio frames / vision
    patches) prepended to the token embeddings (vlm / audio families).
    """
    x = embed_tokens(params, cfg, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    if positions is None:
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(jnp.arange(S), (3, S))
        else:
            positions = jnp.arange(S)
    if not return_stats:
        return logits_from_hidden(params, cfg, forward(params, cfg, x,
                                                       positions))
    h, stats = forward(params, cfg, x, positions, return_stats=True)
    return logits_from_hidden(params, cfg, h), stats


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, mixer: str, batch: int, buf_len: int):
    dt = dtype_of(cfg.param_dtype)
    if mixer in ("attn", "attn_local"):
        blen = min(buf_len, cfg.window) if (
            mixer == "attn_local" or cfg.attn_pattern == "sliding") else buf_len
        return init_attn_cache(batch, blen, cfg.n_kv_heads, cfg.head_dim_, dt)
    if mixer == "mamba":
        return init_mamba_cache(batch, cfg.d_model, expand=cfg.ssm_expand,
                                state=cfg.ssm_state, conv=cfg.ssm_conv, dtype=dt)
    if mixer == "mlstm":
        return init_mlstm_cache(batch, cfg.d_model, cfg.n_heads, dtype=dt)
    if mixer == "slstm":
        return init_slstm_cache(batch, cfg.d_model, cfg.n_heads)
    raise ValueError(mixer)


def init_cache(cfg: ModelConfig, batch: int, buf_len: int):
    _refuse_decode(cfg)
    spec = period_spec(cfg)
    np_ = n_periods(cfg)
    one = {f"l{i}": _layer_cache(cfg, mixer, batch, buf_len)
           for i, (mixer, _) in enumerate(spec)}
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (np_,) + x.shape).copy(), one)


def _layer_decode(lp, cc, x, pos, cfg: ModelConfig, mixer: str, mlp: str,
                  rope_fn):
    if mixer in ("attn", "attn_local"):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        rf = rope_fn
        if cfg.mrope_sections and rope_fn is not None:
            rf = lambda xx, p: rope_fn(xx, jnp.broadcast_to(p, (3,) + p.shape))
        h, cc = attn_decode(lp["mixer"], cc, h, pos, n_heads=cfg.n_heads,
                            n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim_,
                            rope_fn=rf, attn_softcap=cfg.attn_softcap)
        x = x + h
    elif mixer == "mamba":
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        h, cc = mamba_decode(lp["mixer"], cc, h, expand=cfg.ssm_expand,
                             state=cfg.ssm_state, conv=cfg.ssm_conv)
        x = x + h
    elif mixer == "mlstm":
        x, cc = mlstm_block_decode(lp["mixer"], cc, x, n_heads=cfg.n_heads,
                                   norm_eps=cfg.norm_eps)
    elif mixer == "slstm":
        x, cc = slstm_block_decode(lp["mixer"], cc, x, n_heads=cfg.n_heads,
                                   norm_eps=cfg.norm_eps)
    if mlp == "dense":
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
        h = (jax.nn.silu(h @ lp["mlp"]["w1"]) * (h @ lp["mlp"]["w3"])) \
            @ lp["mlp"]["w2"]
        x = x + h
    elif mlp == "moe":
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + _moe(lp["mlp"], h, cfg)
    return x, cc


def decode_step(params, cfg: ModelConfig, cache, tokens, pos):
    """tokens: (B, 1); pos: scalar int32.  -> (logits (B, 1, V), new_cache)."""
    spec = period_spec(cfg)
    rope_fn = make_rope_fn(cfg)
    x = embed_tokens(params, cfg, tokens)

    def period_fn(x, inp):
        pp, cc = inp
        new_cc = {}
        for i, (mixer, mlp) in enumerate(spec):
            x, new_cc[f"l{i}"] = _layer_decode(pp[f"l{i}"], cc[f"l{i}"], x,
                                               pos, cfg, mixer, mlp, rope_fn)
        return x, new_cc

    x, new_cache = jax.lax.scan(period_fn, x, (params["periods"], cache))
    return logits_from_hidden(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# paged decode (per-slot positions — the serving path, ISSUE 7 / DESIGN §14)
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ModelConfig, n_slots: int, n_pages: int,
                     page_size: int):
    """Paged decode cache: attention layers share a page pool (no slot
    axis — ownership lives in the scheduler's page table); recurrent mixers
    (mamba/mlstm/slstm) keep their per-slot state caches, which are
    position-free and recycle via ``reset_slot``."""
    _refuse_decode(cfg)
    spec = period_spec(cfg)
    np_ = n_periods(cfg)
    dt = dtype_of(cfg.param_dtype)

    def layer(mixer):
        if mixer in ("attn", "attn_local"):
            return init_paged_attn_cache(n_pages, page_size, cfg.n_kv_heads,
                                         cfg.head_dim_, dt)
        return _layer_cache(cfg, mixer, n_slots, 1)

    one = {f"l{i}": layer(mixer) for i, (mixer, _) in enumerate(spec)}
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (np_,) + x.shape).copy(), one)


def _keep_slots(advance, new_cc, old_cc):
    """Per-slot select on a recurrent layer cache (leading axis = slot):
    slots with advance=False keep their old state bitwise.  Attention
    caches never come through here — their stale writes land in the
    scratch page and are excluded by length masks instead."""
    if advance is None:
        return new_cc

    def sel(n, o):
        return jnp.where(advance.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)

    return jax.tree_util.tree_map(sel, new_cc, old_cc)


def _layer_decode_paged(lp, cc, x, positions, page_table, cfg: ModelConfig,
                        mixer: str, mlp: str, rope_fn, advance):
    if mixer in ("attn", "attn_local"):
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        win = cfg.window if (mixer == "attn_local"
                             or cfg.attn_pattern == "sliding") else 0
        h, cc = attn_decode_paged(lp["mixer"], cc, h, positions, page_table,
                                  n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
                                  head_dim=cfg.head_dim_, rope_fn=rope_fn,
                                  attn_softcap=cfg.attn_softcap, window=win)
        x = x + h
    elif mixer == "mamba":
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        h, cc_new = mamba_decode(lp["mixer"], cc, h, expand=cfg.ssm_expand,
                                 state=cfg.ssm_state, conv=cfg.ssm_conv)
        cc = _keep_slots(advance, cc_new, cc)
        x = x + h
    elif mixer == "mlstm":
        x, cc_new = mlstm_block_decode(lp["mixer"], cc, x, n_heads=cfg.n_heads,
                                       norm_eps=cfg.norm_eps)
        cc = _keep_slots(advance, cc_new, cc)
    elif mixer == "slstm":
        x, cc_new = slstm_block_decode(lp["mixer"], cc, x, n_heads=cfg.n_heads,
                                       norm_eps=cfg.norm_eps)
        cc = _keep_slots(advance, cc_new, cc)
    if mlp == "dense":
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
        h = (jax.nn.silu(h @ lp["mlp"]["w1"]) * (h @ lp["mlp"]["w3"])) \
            @ lp["mlp"]["w2"]
        x = x + h
    elif mlp == "moe":
        h = rms_norm(x, lp["norm2"], cfg.norm_eps)
        x = x + _moe(lp["mlp"], h, cfg)
    return x, cc


def paged_decode_step(params, cfg: ModelConfig, cache, tokens, positions,
                      page_table, advance=None):
    """tokens: (S, 1); positions: (S,) int32 per-slot write positions;
    page_table: (S, max_pages) int32; advance: optional (S,) bool — slots
    with advance=False run through the batch shape-stably but keep their
    recurrent (mamba/mlstm/slstm) state bitwise unchanged (their attention
    write still lands in the scratch page).  The engine uses it for FREE
    and page-stalled slots; None means every slot advances.
    -> (logits (S, 1, V), new_cache).

    The paged cache never wraps: the scheduler enforces
    prompt + max_new_tokens <= max_pages * page_size per slot.
    """
    spec = period_spec(cfg)
    rope_fn = make_rope_fn(cfg)
    x = embed_tokens(params, cfg, tokens)

    def period_fn(x, inp):
        pp, cc = inp
        new_cc = {}
        for i, (mixer, mlp) in enumerate(spec):
            x, new_cc[f"l{i}"] = _layer_decode_paged(
                pp[f"l{i}"], cc[f"l{i}"], x, positions, page_table, cfg,
                mixer, mlp, rope_fn, advance)
        return x, new_cc

    x, new_cache = jax.lax.scan(period_fn, x, (params["periods"], cache))
    return logits_from_hidden(params, cfg, x), new_cache


def reset_slot(cache, slot):
    """Zero slot ``slot``'s recurrent (non-paged) per-slot states so a
    recycled slot starts from the init state.  Paged pools pass through
    untouched: freed pages are reclaimed by the scheduler's allocator and
    stale contents are never read (length masks)."""
    def leaf(path, x):
        if any(getattr(p, "key", None) in ("k_pages", "v_pages")
               for p in path):
            return x
        return x.at[:, slot].set(0)
    return jax.tree_util.tree_map_with_path(leaf, cache)
