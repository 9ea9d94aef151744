"""Model API shared by all assigned architectures.

Every architecture is an ``ArchConfig`` (exact configs live in
``repro/configs/<id>.py``) consumed by a family-specific model class built
via :func:`build_model`. All models expose:

  * ``param_specs()``                 -> ParamSpec pytree
  * ``loss(params, batch)``           -> scalar (training objective)
  * ``forward(params, batch)``        -> logits (prefill entry point)
  * ``decode_state_specs(batch, S)``  -> ParamSpec pytree (KV cache / SSM state)
  * ``decode_step(params, state, tokens, pos)`` -> (logits, state)
  * ``input_specs(shape_name)``       -> ShapeDtypeStruct stand-ins (dry-run)

Modality frontends are stubs per the assignment: the vision/audio entries
take precomputed patch/frame embeddings as inputs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.dist.sharding import ParamSpec


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | vlm | encdec | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    # moe
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    moe_groups: int = 0          # >1: group-limited dispatch (§Perf iter 3)
    # vlm (llama-3.2-vision): one cross-attn layer per `cross_attn_every`
    cross_attn_every: int = 0
    n_image_tokens: int = 4096
    # enc-dec (seamless): encoder depth; decoder length = seq // dec_ratio
    n_enc_layers: int = 0
    dec_ratio: int = 4
    # ssm / hybrid
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    attn_every: int = 0          # zamba2: shared attn block every k mamba layers
    is_rwkv: bool = False
    # execution
    sharding_mode: str = "megatron"   # "cascade" = paper-faithful baseline
    microbatches: int = 1             # gradient-accumulation factor
    remat: bool = True
    q_chunk: int = 512
    ssd_chunk: int = 128
    optimizer: str = "adamw"     # "adafactor" for the very large configs
    quantized: bool = False      # serve: int8 qmatmul LM head (--quantized)
    # serve: also route the MLP down-projection through the qmatmul kernel
    # (a16w8: int16 activations, int8 weights, int16 SRS out). The shifts
    # are per-tensor calibrated by the plan's Quantize pass
    # (repro.plan.passes.calibrate_mlp_shifts); the defaults below are the
    # analytic fallback for silu-gated activations on unit-RMS inputs
    # (absmax < 16 -> x_shift 11, fan-in-scaled weights -> w_shift 8).
    quantized_mlp: bool = False
    mlp_x_shift: int = 11
    mlp_w_shift: int = 8
    mlp_out_shift: int = 11
    notes: str = ""

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
    # one 16 GB TPU v5e serving yi-6b at full depth: ~12.1 GB of bf16
    # weights leave room for 8 slots x 2048 positions of paged KV
    # (64 KiB/token, ~1.2 GB with the short bucket) plus step temporaries
    "serve_v5e": ShapeSpec("serve_v5e", 2048, 8, "decode"),
}

# long_500k needs sub-quadratic attention: only SSM/hybrid/linear-attention
# families run it (see DESIGN.md §5 for the skip list).
_LONG_OK_FAMILIES = ("ssm", "hybrid")


def supports_shape(cfg: ArchConfig, shape: str) -> Tuple[bool, str]:
    if shape == "long_500k" and cfg.family not in _LONG_OK_FAMILIES:
        return False, (
            "pure full-attention architecture: 500k-token decode would need "
            "sub-quadratic attention (skip noted in DESIGN.md)"
        )
    return True, ""


def token_input_specs(batch: int, seq: int) -> Dict[str, Any]:
    return {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }


def stackify(tree, n: int):
    """Prepend a scan-layer dim to every ParamSpec in a tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.logical,
                            s.dtype, s.init, s.scale),
        tree,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def state_batch_axes(sspecs):
    """Per-leaf index of the "batch" logical axis in a decode-state tree.

    Continuous batching resets ONE batch lane of a live state (a reused
    slot must not inherit its predecessor's KV/SSM); the specs name the
    batch axis logically, so the lookup works for KV caches and SSM/conv
    states alike. Shared by the in-step fresh lane
    (``make_masked_decode_step``) and the host-side
    ``StatePool.reset_slots`` so the two resets can never diverge.

    Leaves with no batch axis map to ``-1`` (a sentinel, not ``None`` —
    ``None`` is an empty pytree node and would break the tree.map) — the
    paged KV pool has a page axis instead of a batch axis, needs no
    per-slot wipe (stale pages are masked by the local-position validity
    window), and :func:`wipe_state_slots` skips them.
    """
    return jax.tree.map(
        lambda s: s.logical.index("batch") if "batch" in s.logical else -1,
        sspecs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def wipe_state_slots(state, slot_mask, batch_axes):
    """Zero the masked batch lanes of every state leaf.

    ``slot_mask`` is a [batch] bool vector; ``batch_axes`` comes from
    :func:`state_batch_axes` over the matching decode-state specs.
    Leaves whose axis entry is ``-1`` (the shared page pool) pass
    through untouched. Traceable (used inside the masked decode step)
    and jit-friendly with donation (used by the pool's per-slot reset).
    """
    batch = slot_mask.shape[0]

    def one(leaf, axis):
        if axis < 0:
            return leaf
        shape = [1] * leaf.ndim
        shape[axis] = batch
        return jnp.where(slot_mask.reshape(shape), jnp.zeros_like(leaf),
                         leaf)

    return jax.tree.map(one, state, batch_axes)


class PageView(NamedTuple):
    """Traceable view of the paged KV layout for one decode step.

    ``table`` [B, max_len // page_size] int32 maps each slot's logical
    page index to a physical page in the shared pool; ``local_pos`` [B]
    int32 is each slot's position in its OWN sequence (the page-local
    coordinate system — RoPE and cache indexing both use it, which is
    what makes prefix pages position-independent and bit-reusable);
    ``page_size`` is static. See ``docs/memory_model.md``.
    """

    table: Any
    local_pos: Any
    page_size: int


# Decode-state leaves that move to the paged layout. Cross-attention
# caches (``cross_k``/``cross_v``) and recurrent SSM/conv/RWKV state are
# per-slot by construction and stay dense.
PAGED_STATE_KEYS = ("cache_k", "cache_v")


def is_paged_state_key(name: str) -> bool:
    """True for leaves that live in the shared page pool: the
    self-attention KV caches and their ``draft_``-prefixed speculative
    twins (the draft's KV rides the SAME page tables — one page id
    indexes both pools at matching local positions)."""
    if name in PAGED_STATE_KEYS:
        return True
    return (name.startswith("draft_")
            and name[len("draft_"):] in PAGED_STATE_KEYS)


def paged_state_specs(sspecs, page_count: int, page_size: int):
    """Rewrite self-attention KV leaves to the shared-pool layout.

    A dense leaf ``[..., batch, max_len, kv, hd]`` (axes ``...,
    "batch", "seq", ...``) becomes ``[..., page_count, page_size, kv,
    hd]`` with both new axes replicated (logical ``None``): pages are
    shared between slots and buckets, so neither maps onto a mesh data
    axis. Head/hd sharding is preserved. ``draft_``-prefixed KV twins
    (speculative lanes) are rewritten the same way — they share the
    slot's page table, so their pool has the same page axes. All other
    leaves — cross caches, SSM/conv/RWKV state — pass through unchanged.
    """
    out = {}
    for name, s in sspecs.items():
        if not is_paged_state_key(name):
            out[name] = s
            continue
        b = s.logical.index("batch")
        q = s.logical.index("seq")
        assert q == b + 1, (name, s.logical)
        shape = s.shape[:b] + (page_count, page_size) + s.shape[q + 1:]
        logical = s.logical[:b] + (None, None) + s.logical[q + 1:]
        out[name] = ParamSpec(shape, logical, s.dtype, "zeros")
    return out


def build_model(cfg: ArchConfig):
    if cfg.family in ("dense", "moe"):
        from repro.models.lm import DecoderLM
        return DecoderLM(cfg)
    if cfg.family == "vlm":
        from repro.models.vision_lm import VisionLM
        return VisionLM(cfg)
    if cfg.family == "encdec":
        from repro.models.encdec import EncDecLM
        return EncDecLM(cfg)
    if cfg.family == "ssm":
        from repro.models.rwkv_model import RWKVModel
        return RWKVModel(cfg)
    if cfg.family == "hybrid":
        from repro.models.hybrid import HybridModel
        return HybridModel(cfg)
    raise ValueError(f"unknown family {cfg.family}")


# ---------------------------------------------------------------------------
# Shared decode head: fp32 einsum, or the paper's int8 qmatmul path
# ---------------------------------------------------------------------------


def decode_head_logits(head_w: jnp.ndarray, x: jnp.ndarray,
                       cfg: ArchConfig) -> jnp.ndarray:
    """Final-token logits [B, V] from decode hiddens ``x`` [B, 1, d].

    With ``cfg.quantized`` the projection routes through the Pallas
    qmatmul kernel (int8 operands, int16 SRS output): the GEMV that
    dominates the decode step is exactly the op the paper quantizes.
    Shifts are sized to the observed ranges: rmsnorm'd activations (unit
    RMS, absmax just under 4 -> x_shift 5) and fan-in-scaled head weights
    (absmax just under 0.5 -> w_shift 8); out_shift 11 keeps ~5e-4 logit
    resolution over a +-16 range. Greedy argmax matches the float path on
    the debug configs; logit gaps below the ~0.05 quantization noise can
    still flip — that is the int8 contract, not a bug.
    """
    if cfg.quantized:
        from repro.layers.linear import quantized_linear

        return quantized_linear(
            {"w": head_w}, x[:, 0],
            x_shift=5, w_shift=8, out_shift=11, out_dtype="int16",
            out_float_dtype=jnp.float32,
        )
    return jnp.einsum("bsd,dv->bsv", x, head_w,
                      preferred_element_type=jnp.float32)[:, 0]


def decode_block_head_logits(head_w: jnp.ndarray, x: jnp.ndarray,
                             cfg: ArchConfig) -> jnp.ndarray:
    """Logits [B, m, V] for a block of m decode hiddens ``x`` [B, m, d].

    The block form of :func:`decode_head_logits` (same shifts, same int8
    contract on the quantized path) for speculative block verification:
    the target model scores every position of a drafted micro-run in one
    projection instead of m GEMVs.
    """
    if cfg.quantized:
        from repro.layers.linear import quantized_linear

        return quantized_linear(
            {"w": head_w}, x,
            x_shift=5, w_shift=8, out_shift=11, out_dtype="int16",
            out_float_dtype=jnp.float32,
        )
    return jnp.einsum("bsd,dv->bsv", x, head_w,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Self-speculative draft: an early-exit layer prefix of the target
# ---------------------------------------------------------------------------


def spec_state_specs(sspecs, draft_layers: int, prefix: str = "draft_"):
    """Draft-model decode-state leaves for the layer-prefix draft.

    Every target state leaf with a ``"layers"`` logical axis gets a
    ``draft_``-prefixed twin whose layers dim is ``draft_layers`` — the
    KV the self-speculative draft (the first ``draft_layers`` blocks of
    the target, sharing embed/final-norm/head) accumulates while it
    proposes tokens. Merging these into the target's state pytree keeps
    the whole StatePool lifecycle (acquire/release, donated per-slot
    wipes, batch-axis discovery) a single uniform tree.
    """
    out = {}
    for name, s in sspecs.items():
        li = s.logical.index("layers")
        shape = s.shape[:li] + (draft_layers,) + s.shape[li + 1:]
        out[prefix + name] = ParamSpec(shape, s.logical, s.dtype, s.init)
    return out


def split_spec_state(state, prefix: str = "draft_"):
    """Split a merged decode state into (target tree, draft tree).

    The draft tree's keys have the prefix stripped so the same
    ``decode_block`` consumes either half.
    """
    target = {k: v for k, v in state.items() if not k.startswith(prefix)}
    draft = {k[len(prefix):]: v for k, v in state.items()
             if k.startswith(prefix)}
    return target, draft


def draft_prefix_params(params, draft_layers: int):
    """The self-speculative draft's parameter view: the target's stacked
    blocks sliced to the first ``draft_layers`` layers, embed/ln_f/head
    shared verbatim. A pure (traceable) slice — no extra parameters, no
    extra host transfer."""
    out = dict(params)
    out["blocks"] = jax.tree.map(lambda a: a[:draft_layers],
                                 params["blocks"])
    return out


# ---------------------------------------------------------------------------
# Shared loss: chunked cross-entropy that never materializes [B,S,V] fp32
# ---------------------------------------------------------------------------


def lm_loss_chunked(
    head_w: jnp.ndarray,     # [d, V]
    x: jnp.ndarray,          # [B, S, d] final hidden states
    labels: jnp.ndarray,     # [B, S] int32 (next-token targets)
    chunk: int = 512,
) -> jnp.ndarray:
    """Mean next-token CE, computed per sequence chunk under remat so the
    full logits tensor is never resident (vocab can be 150k+)."""
    B, S, d = x.shape
    if S % chunk:
        chunk = S
    n = S // chunk
    xc = x.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def step(acc, inp):
        xb, lb = inp
        logits = jnp.einsum("bsd,dv->bsv", xb, head_w,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (B * S)
