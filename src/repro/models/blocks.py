"""Reusable transformer blocks (pre-norm residual, GQA + SwiGLU/MoE)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard_act
from repro.layers.attention import (
    attention_spec,
    block_decode_self_attention,
    cross_attention,
    decode_self_attention,
    paged_block_decode_self_attention,
    paged_decode_self_attention,
    self_attention,
)
from repro.layers.mlp import swiglu, swiglu_spec
from repro.layers.moe import moe, moe_spec
from repro.layers.norm import rmsnorm, rmsnorm_spec
from repro.models.base import ArchConfig


def attn_block_spec(cfg: ArchConfig, *, use_moe: bool = False) -> dict:
    mode = cfg.sharding_mode
    spec = {
        "ln1": rmsnorm_spec(cfg.d_model),
        "attn": attention_spec(
            cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim, mode,
            qkv_bias=cfg.qkv_bias,
        ),
        "ln2": rmsnorm_spec(cfg.d_model),
    }
    if use_moe:
        spec["moe"] = moe_spec(cfg.d_model, cfg.moe_d_ff, cfg.n_experts, mode)
        if cfg.n_shared_experts:
            spec["shared"] = swiglu_spec(
                cfg.d_model, cfg.n_shared_experts * cfg.moe_d_ff, mode
            )
    else:
        spec["ffn"] = swiglu_spec(cfg.d_model, cfg.d_ff, mode)
    return spec


def _ffn_part(params: dict, x: jnp.ndarray, cfg: ArchConfig):
    if "moe" in params:
        y, aux = moe(
            params["moe"], x,
            n_experts=cfg.n_experts, top_k=cfg.top_k,
            n_groups=cfg.moe_groups or 1,
        )
        if "shared" in params:
            y = y + swiglu(params["shared"], x)
        return y, aux
    # int8 down-projection on serve plans (the plan's Quantize pass sets
    # quantized_mlp and calibrates the shifts per weight tensor)
    quant = ((cfg.mlp_x_shift, cfg.mlp_w_shift, cfg.mlp_out_shift)
             if cfg.quantized_mlp else None)
    return swiglu(params["ffn"], x, quant=quant), jnp.zeros((), jnp.float32)


def attn_block(
    params: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: ArchConfig,
    *,
    causal: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x, moe aux loss)."""
    h = rmsnorm(params["ln1"], x)
    h = self_attention(
        params["attn"], h, positions,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, causal=causal, q_chunk=cfg.q_chunk,
    )
    x = x + h
    x = shard_act(x, "batch", "seq", "act_embed")
    h = rmsnorm(params["ln2"], x)
    h, aux = _ffn_part(params, h, cfg)
    x = x + h
    x = shard_act(x, "batch", "seq", "act_embed")
    return x, aux


def attn_block_decode(
    params: dict,
    x: jnp.ndarray,              # [B, 1, d] (or [B, m, d] with ``local``)
    cache_k: jnp.ndarray,        # dense [B,S,KV,hd] or paged [P,ps,KV,hd]
    cache_v: jnp.ndarray,        # (paged with ``layer``: [L,P,ps,KV,hd])
    pos: jnp.ndarray,
    cfg: ArchConfig,
    *,
    window_start: Optional[jnp.ndarray] = None,   # [B] int32 slot windows
    pages=None,                  # models.base.PageView: paged KV layout
    local: Optional[jnp.ndarray] = None,   # [B] int32: local block coords
    layer: Optional[jnp.ndarray] = None,   # [] int32: paged, stacked pool
):
    h = rmsnorm(params["ln1"], x)
    if local is not None and pages is not None:
        # paged local-coordinate block decode (speculative lanes over the
        # page pool): the PageView's local_pos is the block origin, so
        # ``local`` only selects this branch
        h, ck, cv = paged_block_decode_self_attention(
            params["attn"], h, cache_k, cache_v, pages,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
        )
    elif local is not None:
        # dense local-coordinate block decode (speculative lanes): ``pos``
        # and ``window_start`` are unused — each slot indexes, rotates,
        # and masks at its own local positions [local[b], local[b]+m)
        h, ck, cv = block_decode_self_attention(
            params["attn"], h, cache_k, cache_v, local,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta,
        )
    elif pages is not None:
        h, ck, cv = paged_decode_self_attention(
            params["attn"], h, cache_k, cache_v, pages,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, layer=layer,
        )
    else:
        h, ck, cv = decode_self_attention(
            params["attn"], h, cache_k, cache_v, pos,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta, window_start=window_start,
        )
    x = x + h
    h = rmsnorm(params["ln2"], x)
    h, _ = _ffn_part(params, h, cfg)
    return x + h, ck, cv


def cross_block_spec(cfg: ArchConfig, d_memory: Optional[int] = None) -> dict:
    """Cross-attention block (vision layers / enc-dec decoder)."""
    mode = cfg.sharding_mode
    d_mem = d_memory or cfg.d_model
    from repro.layers.linear import linear_spec

    return {
        "ln1": rmsnorm_spec(cfg.d_model),
        "xattn": {
            "wq": linear_spec(cfg.d_model, cfg.n_heads * cfg.head_dim, "col",
                              mode),
            "wk": linear_spec(d_mem, cfg.n_kv * cfg.head_dim, "kv", mode),
            "wv": linear_spec(d_mem, cfg.n_kv * cfg.head_dim, "kv", mode),
            "wo": linear_spec(cfg.n_heads * cfg.head_dim, cfg.d_model, "row",
                              mode),
        },
        "ln2": rmsnorm_spec(cfg.d_model),
        "ffn": swiglu_spec(cfg.d_model, cfg.d_ff, mode),
        "gate": None,  # populated below
    }


def make_cross_block_spec(cfg: ArchConfig, d_memory: Optional[int] = None):
    from repro.dist.sharding import ParamSpec

    spec = cross_block_spec(cfg, d_memory)
    # llama-3.2-V style tanh gate, initialized at zero
    spec["gate"] = ParamSpec((1,), (None,), jnp.bfloat16, init="zeros")
    return spec


def cross_block(
    params: dict,
    x: jnp.ndarray,
    memory: jnp.ndarray,
    cfg: ArchConfig,
    memory_valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    h = rmsnorm(params["ln1"], x)
    h = cross_attention(
        params["xattn"], h, memory,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.head_dim,
        memory_valid=memory_valid, q_chunk=cfg.q_chunk,
    )
    gate = jnp.tanh(params["gate"].astype(jnp.float32)).astype(x.dtype)
    x = x + gate * h
    h = rmsnorm(params["ln2"], x)
    x = x + swiglu(params["ffn"], h)
    return shard_act(x, "batch", "seq", "act_embed")
