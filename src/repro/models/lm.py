"""Decoder-only LM (dense GQA and MoE variants).

Covers: yi-6b, qwen1.5-4b, qwen1.5-110b, mistral-large-123b,
phi3.5-moe-42b-a6.6b, kimi-k2-1t-a32b.

Layers run under a single lax.scan over stacked parameters (HLO size O(1)
in depth); each block is rematerialized when cfg.remat is set.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import ParamSpec, shard_act
from repro.layers.embedding import embed, embedding_spec, lm_head_spec
from repro.layers.norm import rmsnorm, rmsnorm_spec
from repro.models.base import (
    ArchConfig,
    decode_block_head_logits,
    decode_head_logits,
    lm_loss_chunked,
    stackify,
    token_input_specs,
)
from repro.models.blocks import attn_block, attn_block_decode, attn_block_spec


class DecoderLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.use_moe = cfg.family == "moe"

    # -- parameters -----------------------------------------------------------

    def param_specs(self):
        cfg = self.cfg
        return {
            "embed": embedding_spec(cfg.vocab, cfg.d_model),
            "blocks": stackify(
                attn_block_spec(cfg, use_moe=self.use_moe), cfg.n_layers
            ),
            "ln_f": rmsnorm_spec(cfg.d_model),
            "head": lm_head_spec(cfg.d_model, cfg.vocab),
        }

    # -- training / prefill ---------------------------------------------------

    def backbone(self, params, tokens: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.cfg
        B, S = tokens.shape
        x = embed(params["embed"], tokens)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

        def body(carry, layer_params):
            x, aux = carry
            x, a = attn_block(layer_params, x, positions, cfg)
            return (x, aux + a), None

        fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = jax.lax.scan(
            fn, (x, jnp.zeros((), jnp.float32)), params["blocks"]
        )
        x = rmsnorm(params["ln_f"], x)
        return x, aux

    def forward(self, params, batch: Dict) -> jnp.ndarray:
        """Prefill entry point: full logits."""
        x, _ = self.backbone(params, batch["tokens"])
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"]["w"],
                            preferred_element_type=jnp.float32)
        return shard_act(logits, "batch", "seq", "vocab")

    def loss(self, params, batch: Dict) -> jnp.ndarray:
        x, aux = self.backbone(params, batch["tokens"])
        ce = lm_loss_chunked(params["head"]["w"], x, batch["labels"])
        return ce + 0.01 * aux

    # -- decode ---------------------------------------------------------------

    def decode_state_specs(self, batch: int, max_len: int):
        cfg = self.cfg
        shape = (cfg.n_layers, batch, max_len, cfg.n_kv, cfg.head_dim)
        axes = ("layers", "batch", "seq", "cache_heads", "cache_hd")
        return {
            "cache_k": ParamSpec(shape, axes, jnp.bfloat16, init="zeros"),
            "cache_v": ParamSpec(shape, axes, jnp.bfloat16, init="zeros"),
        }

    def decode_step(self, params, state: Dict, tokens: jnp.ndarray,
                    pos: jnp.ndarray, *, window_start=None, pages=None):
        """One token for every sequence. tokens [B] int32; pos [] int32.

        ``window_start`` ([B] int32, optional) limits each slot's
        attention to cache positions >= its own window start — the
        continuous-batching slot-reuse contract (see
        ``make_masked_decode_step``). With ``pages`` (a
        ``models.base.PageView``) the KV leaves are the shared page pool
        instead of per-slot slabs and ``window_start`` is unused: each
        slot indexes (and RoPE-rotates at) its own local position. The
        pool then rides the layer scan's carry, not its ``xs``/``ys``:
        each layer writes its rows at ``(layer, page, offset)`` and
        gathers its pages from ``(layer, table)`` in the whole stacked
        pool, so the donated buffer is updated in place and no layer
        slab is sliced out or stacked back.
        """
        cfg = self.cfg
        x = embed(params["embed"], tokens[:, None])

        if pages is not None:
            def paged_body(carry, inp):
                x, ck, cv = carry
                layer_params, layer = inp
                x, ck, cv = attn_block_decode(layer_params, x, ck, cv, pos,
                                              cfg, pages=pages, layer=layer)
                return (x, ck, cv), None

            layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
            (x, ck, cv), _ = jax.lax.scan(
                paged_body, (x, state["cache_k"], state["cache_v"]),
                (params["blocks"], layers))
        else:
            def body(x, inp):
                layer_params, ck, cv = inp
                x, ck, cv = attn_block_decode(layer_params, x, ck, cv, pos,
                                              cfg, window_start=window_start)
                return x, (ck, cv)

            x, (ck, cv) = jax.lax.scan(
                body, x,
                (params["blocks"], state["cache_k"], state["cache_v"]))
        x = rmsnorm(params["ln_f"], x)
        logits = decode_head_logits(params["head"]["w"], x, cfg)
        return logits, {"cache_k": ck, "cache_v": cv}

    def decode_block(self, params, state: Dict, tokens: jnp.ndarray,
                     local: jnp.ndarray, *, pages=None):
        """Score a block of m consecutive tokens per sequence in one pass.

        tokens [B, m] int32; ``local`` [B] int32 is each slot's LOCAL
        position for ``tokens[:, 0]`` (see
        ``block_decode_self_attention`` for the coordinate contract —
        RoPE, cache writes, and the per-query validity mask all use
        ``local[b] + j``). With ``pages`` (a ``models.base.PageView``
        whose ``local_pos`` equals ``local``) the KV leaves are the
        shared page pool and the writes land in the slot's page run.
        Returns (logits [B, m, V], state):
        ``logits[b, j]`` is the next-token distribution after consuming
        ``tokens[b, :j+1]``, exactly what ``m`` sequential
        ``decode_step`` calls would produce up to float re-association.
        This is both the speculative draft's step (m == 1) and the
        target's teacher-forced verify pass (m == micro-run length).
        """
        cfg = self.cfg
        x = embed(params["embed"], tokens)

        def body(x, inp):
            layer_params, ck, cv = inp
            x, ck, cv = attn_block_decode(layer_params, x, ck, cv, None,
                                          cfg, local=local, pages=pages)
            return x, (ck, cv)

        x, (ck, cv) = jax.lax.scan(
            body, x, (params["blocks"], state["cache_k"], state["cache_v"])
        )
        x = rmsnorm(params["ln_f"], x)
        logits = decode_block_head_logits(params["head"]["w"], x, cfg)
        return logits, {"cache_k": ck, "cache_v": cv}

    # -- dry-run input specs --------------------------------------------------

    def input_specs(self, shape) -> Dict:
        if shape.kind in ("train", "prefill"):
            return token_input_specs(shape.global_batch, shape.seq_len)
        return {
            "tokens": jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
