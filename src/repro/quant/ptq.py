"""Post-training quantization substrate (real PTQ, not the analytic model).

Per-channel symmetric round-to-nearest weight quantization along the
reduction axis (-2), scales per output channel (-1):

  w[..., :, j]  ~=  q[..., :, j] * scale[..., 0, j],
  q int8 (8-bit) or int4 (packed two-rows-per-int8 along -2),
  scale = max|w| / qmax  over axis -2 (keepdims).

Leading axes are PRESERVED — a scan-stacked layer tree (L, K, N) quantizes
to q (L, K, N) + scale (L, 1, N), so ``jax.lax.scan`` over layers slices
``QTensor`` leaves exactly like fp weights (QTensor is a registered pytree
whose children are (q, scale)).

``quantize_tree`` converts every >=2D floating leaf of a model's params
(embeddings included) and leaves small vectors (norm gains, biases)
untouched — matching how real deployments quantize (matmul weights only).

The paper's ``alpha`` (memory scale) is *measured* from these trees via
``tree_bytes`` (see calibration.py) rather than assumed; the paper's values
fall out as the w-bits/16 ratio they predicted.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp

Params = Any

INT4_MAX = 7
INT8_MAX = 127


@jax.tree_util.register_pytree_node_class
@dataclass
class QTensor:
    """Per-channel symmetric quantized weight.

    q: int8 carrier, same shape as the source except axis -2 is halved for
    bits=4 (two nibbles per int8: row 2i -> low, row 2i+1 -> high);
    scale: (..., 1, N) float32.  ``shape``/``dtype`` describe the logical
    dequantized tensor at quantization time; only its last-two dims are
    relied on after pytree slicing (scan strips leading axes).

    ``act_bits`` records the ACTIVATION precision this weight should be
    consumed at (16 = fp activations, 8 = dynamic per-row int8 -> the
    W8A8 int8-accumulation kernel).  It rides in the pytree aux so the
    serving method survives scan slicing and jit boundaries.
    """
    q: jax.Array
    scale: jax.Array
    bits: int
    shape: Tuple[int, ...]
    dtype: Any
    act_bits: int = 16

    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.shape, self.dtype,
                                      self.act_bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, *aux)

    @property
    def nbytes(self) -> int:
        return self.q.size * self.q.dtype.itemsize \
            + self.scale.size * self.scale.dtype.itemsize


def pack_int4(q: jax.Array) -> jax.Array:
    """Pack int4 values (int8 storage, [-8,7]) pairwise along axis -2.
    Rows must be even: row 2i -> low nibble, row 2i+1 -> high nibble."""
    lo = q[..., 0::2, :] & 0x0F
    hi = (q[..., 1::2, :] & 0x0F) << 4
    return (lo | hi).astype(jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of pack_int4: (..., R/2, C) int8 -> (..., R, C) in [-8, 7].

    Index-free even/odd reconstruction: output row r reads packed row
    r//2 (a repeat along -2, no stack+reshape interleave tile), then a
    parity-selected shift sign-extends the right nibble — even rows
    ``(x << 4) >> 4`` (low), odd rows ``x >> 4`` (high), both arithmetic
    on int8.  Bitwise-identical to the historical stack-based unpack.
    """
    rep = jnp.repeat(packed, 2, axis=-2)
    row = jax.lax.broadcasted_iota(jnp.int32, rep.shape, rep.ndim - 2)
    lshift = jnp.where(row % 2 == 0, 4, 0).astype(jnp.int8)
    return ((rep << lshift) >> 4).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits",))
def _quantize_values(w: jax.Array, qmax: jax.Array, bits: int):
    """(q, scale) of :func:`quantize`, under ``jit`` so the f32 copy of
    a stacked (L, K, N) leaf fuses away instead of materializing (3 GB
    for bloom-3b's FFN weights).  ``qmax`` is an operand, not a
    constant: XLA rewrites division by a constant into a reciprocal
    multiply, which would move the scales off the eager values."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -qmax - 1, qmax).astype(jnp.int8)
    if bits == 4:
        if q.shape[-2] % 2:
            pad = [(0, 0)] * q.ndim
            pad[-2] = (0, 1)
            q = jnp.pad(q, pad)
        q = pack_int4(q)
    return q, scale


def quantize(w: jax.Array, bits: int = 8, act_bits: int = 16) -> QTensor:
    """Per-output-channel symmetric RTN quantization (reduction axis -2)."""
    assert bits in (4, 8), bits
    assert act_bits in (8, 16), act_bits
    assert w.ndim >= 2, w.shape
    qmax = INT4_MAX if bits == 4 else INT8_MAX
    q, scale = _quantize_values(w, jnp.float32(qmax), bits)
    return QTensor(q=q, scale=scale, bits=bits, shape=tuple(w.shape),
                   dtype=w.dtype, act_bits=act_bits)


def quantize_rowwise(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dynamic per-row symmetric activation quantization (absmax / 127).

    x (..., K) -> (int8 values (..., K), f32 scales (..., 1)).  The
    reduction runs over the full K axis so one scale per row suffices
    for the whole int32 accumulation of an x @ w contraction — the
    rescale can then happen ONCE at writeout (kernels/quant_matmul.py).
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    # multiply by the f32 reciprocal, NOT divide: XLA strength-reduces a
    # constant-divisor division to this multiply under jit but not in
    # eager mode, and the kernel/oracle pair needs bitwise-equal scales
    scale = jnp.where(absmax > 0, absmax * jnp.float32(1.0 / INT8_MAX), 1.0)
    q = jnp.clip(jnp.round(xf / scale),
                 -INT8_MAX - 1, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize(t: QTensor) -> jax.Array:
    q = t.q
    if t.bits == 4:
        q = unpack_int4(q)[..., :t.shape[-2], :]
    w = q.astype(jnp.float32) * t.scale
    return w.astype(t.dtype)


def fake_quantize(w: jax.Array, bits: int = 8) -> jax.Array:
    """Quantize-dequantize roundtrip (activation fake-quant / tests)."""
    return dequantize(quantize(w, bits))


def _is_weight(leaf: Any) -> bool:
    return (isinstance(leaf, jax.Array) and leaf.ndim >= 2
            and jnp.issubdtype(leaf.dtype, jnp.floating))


# Param names that are true matmul weights consumed through common.mm() /
# maybe_dequant().  Scan stacking prepends a layer axis to every leaf, so
# shape alone cannot distinguish a stacked norm gain (L, dm) from an
# embedding (V, dm) — names can.
MATMUL_KEYS = frozenset({
    "wq", "wk", "wv", "wo", "w1", "w2", "w3", "router", "lm_head", "embed",
})


def _leaf_key(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def quantize_tree(params: Params, bits: int = 8,
                  keys: frozenset = MATMUL_KEYS,
                  act_bits: int = 16) -> Params:
    """Quantize the named matmul leaves; keep everything else fp.

    ``act_bits=8`` tags every quantized leaf for int8-activation serving
    (the W8A8 kernel path); weights themselves are identical to
    ``act_bits=16`` — the tag only changes how ``common.mm`` consumes
    them."""
    def maybe(path, w):
        if _leaf_key(path) in keys and _is_weight(w):
            return quantize(w, bits, act_bits=act_bits)
        return w
    return jax.tree_util.tree_map_with_path(maybe, params)


def dequantize_tree(params: Params) -> Params:
    return jax.tree.map(
        lambda l: dequantize(l) if isinstance(l, QTensor) else l, params,
        is_leaf=lambda l: isinstance(l, QTensor))


def tree_bytes(params: Params) -> int:
    """Total parameter bytes of a (possibly quantized) tree."""
    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda l: isinstance(l, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes
        elif isinstance(leaf, jax.Array):
            total += leaf.size * leaf.dtype.itemsize
    return total
