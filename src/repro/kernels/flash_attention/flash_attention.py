"""Pallas TPU flash-attention (forward) — beyond-paper serving kernel.

The roofline analysis (EXPERIMENTS.md §Roofline) shows the prefill cells are
memory-bound on attention-score traffic: the pure-JAX chunked attention
materializes softmax(QK^T) blocks in HBM. This kernel keeps the running
online-softmax state (m, l, acc) in VMEM across KV blocks, so scores never
leave the core — the standard flash schedule mapped onto the same
BlockSpec/VMEM machinery as the paper's qmatmul kernel.

Grid = (BH, Sq/bq, Sk/bk), KV innermost ("arbitrary"); scratch carries the
per-(q-row) max, sum, and output accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, nk: int, bq: int, bk: int, scale: float,
                  causal: bool, q_start: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32)                    # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                           # [bq, bk]

    if causal:
        qpos = q_start + iq * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]                                 # [bq]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])                     # [bq, bk]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,   # [BH, Sq, hd]
    k: jnp.ndarray,   # [BH, Sk, hd]
    v: jnp.ndarray,   # [BH, Sk, hd]
    *,
    causal: bool = True,
    q_start: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    grid = (BH, Sq // block_q, Sk // block_k)
    scale = hd**-0.5
    kernel = functools.partial(
        _flash_kernel,
        nk=Sk // block_k, bq=block_q, bk=block_k, scale=scale,
        causal=causal, q_start=q_start,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
