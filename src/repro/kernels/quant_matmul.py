"""Pallas TPU quantized-matmul kernels (W8A8 / W8A16 / W4A16).

Three tiers (DESIGN.md §3):

* **W8A8** (``_mm_kernel_w8a8``): activations arrive PRE-quantized to
  int8 with per-row absmax scales (ops.py does the dynamic rowwise
  quantization once per call, over the full K axis); the kernel runs an
  int8 x int8 dot with **int32 accumulation** on the MXU and applies a
  single per-(row, output-channel) rescale ``acc * sx * sw`` at writeout
  on the last K step.  No f32 weight tile is ever materialized — HBM
  *and* MXU both see the low-bit operands.  int32 is overflow-safe:
  |acc| <= 127*127*K < 2^31 for K < ~133k, far beyond any d_model/d_ff
  served here.

* **W8A16 / W4A16** (``_mm_kernel_int8`` / ``_mm_kernel_int4``): the
  high-accuracy fallback — int8/int4 weights stream HBM->VMEM in
  (block_k, block_n) tiles, are dequantized *in VMEM* (vector unit), and
  feed the MXU as f32 tiles, so the HBM side sees alpha x fewer bytes
  while the MXU sees ordinary matmuls.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary") so a VMEM scratch
accumulator (f32 for the A16 tiers, int32 for W8A8) carries partial sums
across K steps; the result is rescaled/cast and written once on the last
K step.

int4: weights arrive packed two-rows-per-int8 (quant/ptq.py layout:
row 2i -> low nibble, row 2i+1 -> high nibble), so the weight BlockSpec
tiles (bk/2, bn) and the kernel unpacks to (bk, bn) with an index-free
even/odd reconstruction (``_unpack_int4_tile``) — the packed form is
what lives in HBM/VMEM, which is the point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256


def _unpack_int4_tile(packed: jax.Array) -> jax.Array:
    """(R, C) packed int8 -> (2R, C) int4 values in [-8, 7], index-free.

    Output row r reads packed row r//2 (a sublane repeat — no
    stack+reshape interleave tile in VMEM), then a parity-selected shift
    pair sign-extends the right nibble in int32 (Mosaic has no int8
    shifts): even rows ``(x << 28) >> 28`` (low nibble), odd rows
    ``(x << 24) >> 28`` (high nibble).  Values and order equal
    ``quant.ptq.unpack_int4`` bit for bit (pinned in the tests).
    """
    rep = jnp.repeat(packed.astype(jnp.int32), 2, axis=0)  # (2R, C)
    row = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 0)
    lshift = jnp.where(row % 2 == 0, 28, 24)
    return ((rep << lshift) >> 28).astype(jnp.int8)


def _mm_kernel_int8(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """W8A16: one (bm, bn) output tile, accumulating over K blocks."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = q_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_kernel_int4(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """W4A16: as _mm_kernel_int8 but unpacking the nibble-packed tile."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack_int4_tile(q_ref[...])                     # (bk, bn) int8
    w = q.astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_kernel_w8a8(x_ref, sx_ref, q_ref, s_ref, o_ref, acc_ref, *,
                    n_k: int):
    """W8A8: int8 x int8 -> int32 accumulation, ONE rescale at writeout.

    x_ref holds pre-quantized int8 activations, sx_ref their per-row f32
    scales (full-K absmax/127, so the scale is K-block-invariant and the
    rescale factorizes out of the accumulation); s_ref the per-channel
    weight scales.  The MXU consumes the int8 operands directly.
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], q_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == n_k - 1)
    def _():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * sx_ref[...].astype(jnp.float32)
                      * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def quant_matmul(x: jax.Array, q: jax.Array, scale: jax.Array,
                 bits: int = 8, *, x_scale: jax.Array = None,
                 out_dtype=None, block_m: int = DEFAULT_BM,
                 block_n: int = DEFAULT_BN, block_k: int = DEFAULT_BK,
                 interpret: bool = False) -> jax.Array:
    """x (M,K) @ dequant(q (K,N) or packed (K/2,N), scale (N,)) -> (M,N).

    With ``x_scale`` (M, 1) the W8A8 tier runs: x must already be int8
    (rowwise-quantized by ops.py) and the output is
    ``(x_int32 @ q_int32) * x_scale * scale`` in ``out_dtype``.
    M, K, N must be divisible by the block sizes (ops.py pads).
    """
    M, K = x.shape
    N = scale.shape[0]
    a8 = x_scale is not None
    if bits == 4:
        assert q.shape == (K // 2, N), (q.shape, K, N)
        assert block_k % 2 == 0
    else:
        assert q.shape == (K, N), (q.shape, K, N)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, \
        (M, N, K, block_m, block_n, block_k)
    n_k = K // block_k
    out_dtype = out_dtype if out_dtype is not None else x.dtype

    if a8:
        assert bits == 8 and x.dtype == jnp.int8, (bits, x.dtype)
        assert x_scale.shape == (M, 1), x_scale.shape
        return pl.pallas_call(
            functools.partial(_mm_kernel_w8a8, n_k=n_k),
            grid=(M // block_m, N // block_n, n_k),
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
                pl.BlockSpec((block_m, 1), lambda i, j, k: (i, 0)),
                pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
                pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
            compiler_params=compiler_params("parallel", "parallel",
                                            "arbitrary"),
            interpret=interpret,
        )(x, x_scale.astype(jnp.float32), q,
          scale.reshape(1, N).astype(jnp.float32))

    kern = _mm_kernel_int4 if bits == 4 else _mm_kernel_int8
    wk = block_k // 2 if bits == 4 else block_k
    return pl.pallas_call(
        functools.partial(kern, n_k=n_k),
        grid=(M // block_m, N // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((wk, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, q, scale.reshape(1, N).astype(jnp.float32))
