"""Pallas TPU kernel: blocked quantized matmul with fused bias + ReLU + SRS.

TPU adaptation of the paper's aie::mmul 2x2-accumulator linear kernel
(Sec. III-A). The mapping:

  AIE concept                       ->  this kernel
  ---------------------------------------------------------------------
  aie::mmul <M,K,N> native tile     ->  MXU-aligned VMEM blocks (bm,bk,bn)
  2x2 accumulator scheme C00..C11   ->  (qm x qn) macro-tile: each grid step
                                        loads qm A-blocks and qn W-blocks and
                                        updates qm*qn accumulator quadrants,
                                        reusing every loaded block qn (resp.
                                        qm) times — same arithmetic-intensity
                                        amplification as the paper's scheme
  bias loaded into acc in prologue  ->  acc initialized from bias on k==0
  SRS fused into the store (VST.SRS)->  shift-round-saturate on k==K-1,
                                        single store of the finished tile
  ReLU in the epilogue              ->  max(y,0) after SRS, before the store
  ping-pong local buffers           ->  Pallas software pipelining across the
                                        grid (automatic multi-buffering of
                                        HBM->VMEM block streams)

Grid = (M/(qm*bm), N/(qn*bn), K/bk) with K innermost ("arbitrary" semantics)
so the int32 accumulator scratch lives in VMEM across the contraction, and
M/N dimensions are "parallel" — the same loop nest as Algorithm 1.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.srs import INT_RANGE

_NEUTRAL = 0


def _srs_block(acc, shift: int, out_dtype: str, rounding: str):
    """Shift-round-saturate a finished accumulator block (int32 math)."""
    if shift > 0:
        if rounding == "floor":
            acc = acc >> shift
        elif rounding == "half_up":
            acc = (acc + jnp.int32(1 << (shift - 1))) >> shift
        elif rounding == "half_even":
            floor = acc >> shift
            rem = acc & jnp.int32((1 << shift) - 1)
            half = jnp.int32(1 << (shift - 1))
            bump = (rem > half) | ((rem == half) & ((floor & 1) == 1))
            acc = floor + bump.astype(jnp.int32)
        else:
            raise ValueError(f"unknown rounding {rounding}")
    lo, hi = INT_RANGE[out_dtype]
    return jnp.clip(acc, lo, hi).astype(out_dtype)


def _split_int16(a):
    """int16 ``a`` -> int8 ``(hi, lo)`` with ``a == 256*hi + lo + 128``.

    ``hi`` is the arithmetic high byte; ``lo`` is the low byte re-centred
    into the signed int8 range, so both halves feed an int8 MXU dot.
    """
    a32 = a.astype(jnp.int32)
    hi = (a32 >> 8).astype(jnp.int8)
    lo = ((a32 & 0xFF) - 128).astype(jnp.int8)
    return hi, lo


def _int_dot(a, b):
    """Exact int32 ``a @ b`` for int8/int16 operands using int8 dots only.

    Mosaic refuses a matmul whose integer operands differ in width (and
    has no int16 MXU path), so each int16 operand is split into two int8
    bytes. The byte dots accumulate in int32 and recombine with shifts and
    the ``+128`` re-centring term, all in wrapping int32 arithmetic — the
    same modular result as the reference's int32 ``dot``.
    """
    if a.dtype == jnp.int16:
        hi, lo = _split_int16(a)
        colsum = jnp.sum(b.astype(jnp.int32), axis=0, keepdims=True)
        return ((_int_dot(hi, b) << 8) + _int_dot(lo, b)
                + (colsum << 7))
    if b.dtype == jnp.int16:
        hi, lo = _split_int16(b)
        rowsum = jnp.sum(a.astype(jnp.int32), axis=1, keepdims=True)
        return ((_int_dot(a, hi) << 8) + _int_dot(a, lo)
                + (rowsum << 7))
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _qmatmul_kernel(
    x_ref, w_ref, b_ref, o_ref, acc_ref,
    *, nk: int, qm: int, qn: int, bm: int, bn: int,
    shift: int, relu: bool, use_bias: bool,
    out_dtype: str, rounding: str,
):
    k = pl.program_id(2)

    # ---- prologue: ACC_INIT / BIAS_LOAD (Algorithm 1 lines 3-6) ----
    @pl.when(k == 0)
    def _init():
        if use_bias:
            bias_row = b_ref[0, :].astype(jnp.int32)  # (qn*bn,)
            acc_ref[...] = jnp.broadcast_to(bias_row[None, :], acc_ref.shape)
        else:
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.int32)

    # ---- steady state: the (qm x qn) accumulator scheme ----
    # Load each A row-block and W col-block once; update all quadrants.
    for i in range(qm):
        a_i = x_ref[i * bm:(i + 1) * bm, :]
        for j in range(qn):
            w_j = w_ref[:, j * bn:(j + 1) * bn]
            acc_ref[i * bm:(i + 1) * bm, j * bn:(j + 1) * bn] += _int_dot(
                a_i, w_j)

    # ---- epilogue: SRS -> ReLU -> VST (Algorithm 1 lines 12-16) ----
    @pl.when(k == nk - 1)
    def _store():
        y = _srs_block(acc_ref[...], shift, out_dtype, rounding)
        if relu:
            y = jnp.maximum(y, jnp.zeros((), dtype=y.dtype))
        o_ref[...] = y


def qmatmul_pallas(
    x: jnp.ndarray,            # (M, K) int8/int16, M % (qm*bm) == 0
    w: jnp.ndarray,            # (K, N) int8/int16
    bias: Optional[jnp.ndarray],  # (N,) int32 or None
    *,
    shift: int,
    relu: bool = False,
    out_dtype: str = "int8",
    rounding: str = "half_up",
    block: tuple = (128, 128, 128),   # (bm, bk, bn)
    acc_blocks: tuple = (2, 2),       # (qm, qn) — the paper's 2x2 scheme
    interpret: bool = False,
) -> jnp.ndarray:
    """Raw blocked kernel; dimensions must already be padded to macro blocks.

    Use :func:`repro.kernels.qmatmul.ops.qlinear` for the padding wrapper.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (K, K2)
    bm, bk, bn = block
    qm, qn = acc_blocks
    MB_M, MB_N = qm * bm, qn * bn
    if M % MB_M or N % MB_N or K % bk:
        raise ValueError(
            f"shape ({M},{K},{N}) not padded to macro blocks "
            f"({MB_M},{bk},{MB_N})"
        )
    nk = K // bk
    grid = (M // MB_M, N // MB_N, nk)

    use_bias = bias is not None
    if not use_bias:
        bias = jnp.zeros((N,), jnp.int32)
    bias2d = bias.reshape(1, N)

    kernel = functools.partial(
        _qmatmul_kernel,
        nk=nk, qm=qm, qn=qn, bm=bm, bn=bn,
        shift=shift, relu=relu, use_bias=use_bias,
        out_dtype=out_dtype, rounding=rounding,
    )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((MB_M, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, MB_N), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, MB_N), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((MB_M, MB_N), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((MB_M, MB_N), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, bias2d)
