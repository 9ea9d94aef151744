"""Attention: GQA self-attention, cross-attention, and KV-cache decode.

Prefill/training use a query-chunked attention (lax.scan over query blocks
with per-chunk rematerialization) so the score matrix never materializes at
[B,H,S,S] — the flash-attention memory behavior expressed in pure JAX. This
is what the multi-pod dry-run lowers; a Pallas flash kernel can replace the
inner block on real TPUs without changing the call signature.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import ParamSpec, shard_act
from repro.layers.linear import linear, linear_spec
from repro.layers.rope import apply_rope, rope_freqs

NEG_INF = -1e30

# TPU deployment switch: route the inner attention block through the Pallas
# flash kernel (kernels/flash_attention). Off by default so the CPU dry-run
# lowers the pure-JAX path; see EXPERIMENTS.md §Perf for the roofline delta.
USE_FLASH_KERNEL = False


def attention_spec(
    d_model: int,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    mode: str,
    *,
    qkv_bias: bool = False,
    stack: Optional[int] = None,
    dtype=jnp.bfloat16,
) -> dict:
    return {
        "wq": linear_spec(d_model, n_heads * head_dim, "col", mode,
                          use_bias=qkv_bias, stack=stack, dtype=dtype),
        "wk": linear_spec(d_model, n_kv * head_dim, "kv", mode,
                          use_bias=qkv_bias, stack=stack, dtype=dtype),
        "wv": linear_spec(d_model, n_kv * head_dim, "kv", mode,
                          use_bias=qkv_bias, stack=stack, dtype=dtype),
        "wo": linear_spec(n_heads * head_dim, d_model, "row", mode,
                          stack=stack, dtype=dtype),
    }


def _attend_block(
    q: jnp.ndarray,          # [B, Cq, H, hd]
    k: jnp.ndarray,          # [B, Sk, H, hd]  (kv heads already repeated)
    v: jnp.ndarray,          # [B, Sk, H, hd]
    q_pos0,                  # scalar: global position of q[.,0]
    kv_valid: Optional[jnp.ndarray],  # [B, Sk] / [B, Sq, Sk] bool or None
    causal: bool,
    scale: float,
) -> jnp.ndarray:
    scores = jnp.einsum(
        "bqhd,bshd->bhqs", q, k, preferred_element_type=jnp.float32
    ) * scale
    Sq, Sk = q.shape[1], k.shape[1]
    if causal:
        qi = q_pos0 + jnp.arange(Sq)
        si = jnp.arange(Sk)
        mask = si[None, :] <= qi[:, None]          # [Sq, Sk]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    if kv_valid is not None:
        # [B, Sk] masks every query row alike; [B, Sq, Sk] is the
        # per-query form block-verify decode needs (query j of slot b may
        # see one more cache row than query j-1)
        mask = kv_valid[:, None, None, :] if kv_valid.ndim == 2 \
            else kv_valid[:, None]
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqs,bshd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(v.dtype)


def mha(
    q: jnp.ndarray,          # [B, Sq, H, hd]
    k: jnp.ndarray,          # [B, Sk, KV, hd]
    v: jnp.ndarray,          # [B, Sk, KV, hd]
    *,
    causal: bool = True,
    q_start: int | jnp.ndarray = 0,
    kv_valid: Optional[jnp.ndarray] = None,
    q_chunk: int = 1024,
) -> jnp.ndarray:
    """Grouped-query attention with query chunking.

    GQA is computed in HEAD-REPEAT form: kv heads are broadcast up to the
    full H so every tensor keeps the q-head dim intact. The obvious
    alternative — reshaping q to [B,S,KV,G,hd] — silently BREAKS head
    sharding under GSPMD when neither KV nor G divides the model axis
    (e.g. 96 heads = 8 kv x 12 groups on TP=16), replicating the whole
    score computation on every model shard. Measured on
    mistral-large x train_4k this inflated per-device attention traffic
    ~16x; see EXPERIMENTS.md §Perf iteration 1.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    assert H % KV == 0, (H, KV)
    if kv_valid is not None and kv_valid.ndim == 3:
        # per-query masks ([B, Sq, Sk]) are a short-block decode feature;
        # the q-chunk scan below would need per-chunk mask slices
        assert Sq <= q_chunk or Sq % q_chunk, (Sq, q_chunk)
    G = H // KV
    scale = hd**-0.5
    if G > 1:
        k = jnp.repeat(k, G, axis=2)               # [B, Sk, H, hd]
        v = jnp.repeat(v, G, axis=2)
    # seq first: a seq-sharded KV cache (flash-decoding layout, megatron_sp)
    # takes precedence over head sharding; fit_pspec drops the duplicate.
    k = shard_act(k, "batch", "seq", "act_heads", None)
    v = shard_act(v, "batch", "seq", "act_heads", None)

    if USE_FLASH_KERNEL and kv_valid is None and Sq == k.shape[1] \
            and hd % 8 == 0:
        from repro.kernels.flash_attention import flash_attention

        qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
        kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
        vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
        o = flash_attention(qf, kf, vf, causal=causal, q_start=int(q_start)
                            if not hasattr(q_start, "shape") else 0)
        return o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)

    if Sq <= q_chunk or Sq % q_chunk:
        return _attend_block(q, k, v, q_start, kv_valid, causal, scale)

    n_chunks = Sq // q_chunk
    qc = q.reshape(B, n_chunks, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def step(_, args):
        qblk, idx = args
        o = _attend_block(
            qblk, k, v, q_start + idx * q_chunk, kv_valid, causal, scale
        )
        return None, o

    _, outs = jax.lax.scan(step, None, (qc, jnp.arange(n_chunks)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def self_attention(
    params: dict,
    x: jnp.ndarray,              # [B, S, d]
    positions: jnp.ndarray,      # [B, S]
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 10000.0,
    causal: bool = True,
    q_chunk: int = 1024,
) -> jnp.ndarray:
    B, S, _ = x.shape
    q = linear(params["wq"], x).reshape(B, S, n_heads, head_dim)
    k = linear(params["wk"], x).reshape(B, S, n_kv, head_dim)
    v = linear(params["wv"], x).reshape(B, S, n_kv, head_dim)
    inv_freq = rope_freqs(head_dim, rope_theta)
    q = apply_rope(q, positions, inv_freq)
    k = apply_rope(k, positions, inv_freq)
    q = shard_act(q, "batch", "seq", "act_heads", None)
    o = mha(q, k, v, causal=causal, q_chunk=q_chunk)
    o = shard_act(o, "batch", "seq", "act_heads", None)
    return linear(params["wo"], o.reshape(B, S, n_heads * head_dim))


def cross_attention(
    params: dict,
    x: jnp.ndarray,              # [B, Sq, d]
    memory: jnp.ndarray,         # [B, Sm, d_mem] (encoder / vision states)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    memory_valid: Optional[jnp.ndarray] = None,
    q_chunk: int = 1024,
) -> jnp.ndarray:
    B, Sq, _ = x.shape
    Sm = memory.shape[1]
    q = linear(params["wq"], x).reshape(B, Sq, n_heads, head_dim)
    k = linear(params["wk"], memory).reshape(B, Sm, n_kv, head_dim)
    v = linear(params["wv"], memory).reshape(B, Sm, n_kv, head_dim)
    q = shard_act(q, "batch", "seq", "act_heads", None)
    o = mha(q, k, v, causal=False, kv_valid=memory_valid, q_chunk=q_chunk)
    return linear(params["wo"], o.reshape(B, Sq, n_heads * head_dim))


# ---------------------------------------------------------------------------
# KV-cache decode (the paper's GEMV regime: one token, resident state)
# ---------------------------------------------------------------------------


def init_cache_spec(
    batch: int, max_len: int, n_kv: int, head_dim: int, n_layers: int,
    dtype=jnp.bfloat16,
) -> dict:
    axes = ("layers", "batch", "seq", "cache_heads", "cache_hd")
    shape = (n_layers, batch, max_len, n_kv, head_dim)
    return {
        "k": ParamSpec(shape, axes, dtype, init="zeros"),
        "v": ParamSpec(shape, axes, dtype, init="zeros"),
    }


def decode_self_attention(
    params: dict,
    x: jnp.ndarray,              # [B, 1, d] current token hidden
    cache_k: jnp.ndarray,        # [B, S, KV, hd] this layer's cache
    cache_v: jnp.ndarray,
    pos: jnp.ndarray,            # [] int32: index of the new token
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 10000.0,
    window_start: Optional[jnp.ndarray] = None,   # [B] int32 or None
):
    """One decode step: project, rotate, append to cache, attend over cache.

    ``window_start`` restricts sequence ``b`` to cache positions
    ``[window_start[b], pos]`` — the continuous-batching contract where a
    reused slot's request began at a nonzero global position and must
    never see its predecessor's KV. RoPE scores depend only on relative
    position, so a request windowed at ``s`` attends exactly as it would
    from position 0. ``None`` keeps the classic full-prefix window.

    Returns (out [B,1,d], new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    S = cache_k.shape[1]
    q = linear(params["wq"], x).reshape(B, 1, n_heads, head_dim)
    k = linear(params["wk"], x).reshape(B, 1, n_kv, head_dim)
    v = linear(params["wv"], x).reshape(B, 1, n_kv, head_dim)
    posb = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    inv_freq = rope_freqs(head_dim, rope_theta)
    q = apply_rope(q, posb, inv_freq)
    k = apply_rope(k, posb, inv_freq)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, pos, axis=1)
    kv_valid = (jnp.arange(S)[None, :] <= pos).astype(bool)
    kv_valid = jnp.broadcast_to(kv_valid, (B, S))
    if window_start is not None:
        kv_valid = kv_valid & (
            jnp.arange(S)[None, :] >= window_start[:, None])
    o = mha(q, cache_k, cache_v, causal=False, kv_valid=kv_valid)
    out = linear(params["wo"], o.reshape(B, 1, n_heads * head_dim))
    return out, cache_k, cache_v


def paged_decode_self_attention(
    params: dict,
    x: jnp.ndarray,              # [B, 1, d] current token hidden
    cache_k: jnp.ndarray,        # [P, ps, KV, hd], or [L, P, ps, KV, hd]
    cache_v: jnp.ndarray,
    pages,                       # models.base.PageView (table, local_pos, ps)
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 10000.0,
    layer: Optional[jnp.ndarray] = None,   # [] int32: index into [L, ...]
):
    """One decode step against the paged KV layout.

    Each slot ``b`` lives in its OWN coordinate system: ``local_pos[b]``
    is its position within its own sequence, page ``j`` of its table
    holds local positions ``[j*ps, (j+1)*ps)``, and RoPE rotates by the
    LOCAL position. That makes a page's contents a pure function of the
    token prefix it encodes — the property the prefix cache relies on to
    map one physical page read-only into many slots (see
    ``docs/memory_model.md``). The dense path instead indexes at global
    position with a ``window_start`` validity floor; both produce the
    same scores because RoPE attention depends only on relative offsets.

    Writes scatter the new K/V row to ``(table[b, local//ps],
    local % ps)``; empty or self-masked lanes carry per-lane scratch
    pages in their tables, so an inactive lane's write lands on a page
    nothing reads. Reads gather the slot's whole table back into
    ``[B, S, KV, hd]`` and mask to ``local_index <= local_pos[b]``.

    With ``layer`` set, ``cache_k``/``cache_v`` are the whole stacked
    pool ``[L, P, ps, KV, hd]`` that the caller's layer scan carries: the
    row is scattered to ``(layer, page, offset)``, one head at a time,
    and the table gathered from ``(layer, table)``, so no layer slab is
    sliced out or written back and the donated pool is updated in place
    (``DecoderLM``'s paged decode). ``layer=None`` takes one layer's
    ``[P, ps, KV, hd]`` slab, as the per-layer callers (encoder-decoder,
    hybrid) pass it.

    Returns (out [B,1,d], new_pool_k, new_pool_v).
    """
    B = x.shape[0]
    ps = pages.page_size
    n_pages = pages.table.shape[1]
    S = n_pages * ps
    q = linear(params["wq"], x).reshape(B, 1, n_heads, head_dim)
    k = linear(params["wk"], x).reshape(B, 1, n_kv, head_dim)
    v = linear(params["wv"], x).reshape(B, 1, n_kv, head_dim)
    local = jnp.clip(pages.local_pos.astype(jnp.int32), 0, S - 1)
    inv_freq = rope_freqs(head_dim, rope_theta)
    q = apply_rope(q, local[:, None], inv_freq)
    k = apply_rope(k, local[:, None], inv_freq)
    page_ids = jnp.take_along_axis(
        pages.table, (local // ps)[:, None], axis=1)[:, 0]
    offs = local % ps
    if layer is None:
        row, read = (page_ids, offs), (pages.table,)
    else:
        # one [hd] row per slot and head: contiguous in whichever order
        # the device lays a page's rows out in (the TPU compiler keeps
        # qwen1.5-4b's 20-head pool heads-major, yi-6b's 4-head pool
        # row-major), so the carried pool keeps its entry layout and is
        # never relaid out on the way in and out
        row = (layer, page_ids[:, None], offs[:, None],
               jnp.arange(n_kv)[None, :])
        read = (layer, pages.table)
    cache_k = cache_k.at[row].set(k[:, 0])
    cache_v = cache_v.at[row].set(v[:, 0])
    k_all = cache_k[read].reshape(B, S, n_kv, head_dim)
    v_all = cache_v[read].reshape(B, S, n_kv, head_dim)
    kv_valid = jnp.arange(S)[None, :] <= local[:, None]
    o = mha(q, k_all, v_all, causal=False, kv_valid=kv_valid)
    out = linear(params["wo"], o.reshape(B, 1, n_heads * head_dim))
    return out, cache_k, cache_v


def block_decode_self_attention(
    params: dict,
    x: jnp.ndarray,              # [B, m, d] block of token hiddens
    cache_k: jnp.ndarray,        # [B, S, KV, hd] this layer's cache
    cache_v: jnp.ndarray,
    local: jnp.ndarray,          # [B] int32: LOCAL position of x[:, 0]
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 10000.0,
):
    """Decode a block of ``m`` consecutive tokens per slot in ONE pass.

    The dense sibling of the paged path's local-coordinate contract:
    slot ``b``'s token ``j`` lives at cache row ``local[b] + j`` of its
    OWN lane — RoPE rotates by that local index and the per-query
    validity mask admits rows ``<= local[b] + j``, so every row a query
    can see was written by this request's own (teacher-forced or
    accepted) tokens. That is what makes host-side rewind free for
    speculative decoding: rejecting a drafted suffix is just a bump of
    the slot's start cursor — the garbage rows it leaves behind sit at
    locals at-or-above the rewound cursor, where the next block's write
    front overwrites them before any mask ever admits them. The
    global-coordinate dense path cannot do this (its contiguous
    ``[window_start, pos]`` window has no way to mask a rejected hole).

    ``m == 1`` is the draft scan's single-token step; ``m == k`` the
    target's verify pass over a whole micro-run.

    Returns (out [B,m,d], new_cache_k, new_cache_v).
    """
    B, m, _ = x.shape
    S = cache_k.shape[1]
    q = linear(params["wq"], x).reshape(B, m, n_heads, head_dim)
    k = linear(params["wk"], x).reshape(B, m, n_kv, head_dim)
    v = linear(params["wv"], x).reshape(B, m, n_kv, head_dim)
    posb = local[:, None].astype(jnp.int32) + jnp.arange(m, dtype=jnp.int32)
    inv_freq = rope_freqs(head_dim, rope_theta)
    q = apply_rope(q, posb, inv_freq)
    k = apply_rope(k, posb, inv_freq)
    rows = jnp.arange(B)[:, None]
    cache_k = cache_k.at[rows, posb].set(k)
    cache_v = cache_v.at[rows, posb].set(v)
    # query j of slot b sees exactly rows [0, local[b] + j]
    kv_valid = jnp.arange(S)[None, None, :] <= posb[:, :, None]
    o = mha(q, cache_k, cache_v, causal=False, kv_valid=kv_valid)
    out = linear(params["wo"], o.reshape(B, m, n_heads * head_dim))
    return out, cache_k, cache_v


def paged_block_decode_self_attention(
    params: dict,
    x: jnp.ndarray,              # [B, m, d] block of token hiddens
    cache_k: jnp.ndarray,        # [P, ps, KV, hd] this layer's page pool
    cache_v: jnp.ndarray,
    pages,                       # models.base.PageView; local_pos = x[:,0]'s
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float = 10000.0,
):
    """Block decode of ``m`` consecutive tokens against the page pool.

    The paged twin of :func:`block_decode_self_attention`: slot ``b``'s
    token ``j`` lives at local position ``local_pos[b] + j`` of its OWN
    page run — RoPE rotates by the UNCLAMPED local position and the
    per-query validity mask admits local rows ``<= local_pos[b] + j``,
    exactly as the dense block path does, so the two produce the same
    floats. Clamping is applied to INDEXING only, and only on the gather
    side: the dense path's out-of-range writes are dropped by the
    scatter's OOB semantics, so here an out-of-range local is routed to
    page id ``P`` (one past the pool) and dropped the same way — a clamp
    would instead alias it onto a real row and corrupt it.

    Speculative rewind works like the dense block path, per page run:
    rejected draft rows sit at-or-above the rewound cursor, where the
    next micro-run's write front (into fresh draft pages, or back into
    the kept partial page) overwrites them before any mask admits them.

    Returns (out [B,m,d], new_pool_k, new_pool_v).
    """
    B, m, _ = x.shape
    ps = pages.page_size
    n_pages = pages.table.shape[1]
    S = n_pages * ps
    q = linear(params["wq"], x).reshape(B, m, n_heads, head_dim)
    k = linear(params["wk"], x).reshape(B, m, n_kv, head_dim)
    v = linear(params["wv"], x).reshape(B, m, n_kv, head_dim)
    posb = (pages.local_pos.astype(jnp.int32)[:, None]
            + jnp.arange(m, dtype=jnp.int32))
    inv_freq = rope_freqs(head_dim, rope_theta)
    q = apply_rope(q, posb, inv_freq)
    k = apply_rope(k, posb, inv_freq)
    in_range = (posb >= 0) & (posb < S)
    posc = jnp.where(in_range, posb, 0)
    page_ids = jnp.take_along_axis(pages.table, posc // ps, axis=1)
    page_ids = jnp.where(in_range, page_ids, cache_k.shape[0])
    offs = posc % ps
    cache_k = cache_k.at[page_ids, offs].set(k)
    cache_v = cache_v.at[page_ids, offs].set(v)
    k_all = cache_k[pages.table].reshape(B, S, n_kv, head_dim)
    v_all = cache_v[pages.table].reshape(B, S, n_kv, head_dim)
    # query j of slot b sees exactly local rows [0, local_pos[b] + j]
    kv_valid = jnp.arange(S)[None, None, :] <= posb[:, :, None]
    o = mha(q, k_all, v_all, causal=False, kv_valid=kv_valid)
    out = linear(params["wo"], o.reshape(B, m, n_heads * head_dim))
    return out, cache_k, cache_v
