"""Jit'd public wrappers for the Pallas kernels.

These handle shape padding (kernels need block-divisible dims), dtype
plumbing, and the interpret-mode switch (``INTERPRET``: interpreted on
the CPU backend, compiled on the TPU, refused elsewhere).  Model code
calls only these.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_decode as _fd
from repro.kernels import quant_matmul as _qm
from repro.quant.ptq import QTensor


def interpret_mode(backend: str) -> bool:
    """Whether the Pallas kernels run interpreted: on the CPU (tests,
    reduced-width runs) they do, on the TPU they compile.  Any other
    backend is refused rather than interpreted in silence."""
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel path for JAX backend {backend!r}: kernels "
        f"compile for 'tpu' and run interpreted on 'cpu' only")


INTERPRET = interpret_mode(jax.default_backend())


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("bits", "act_bits", "block_m",
                                             "block_n", "block_k"))
def quant_matmul(x: jax.Array, q: jax.Array, scale: jax.Array,
                 bits: int = 8, act_bits: int = 16, block_m: int = 128,
                 block_n: int = 128, block_k: int = 256) -> jax.Array:
    """x (..., K) @ dequant(q, scale) -> (..., N).  Pads to block multiples.

    ``act_bits=8`` (with ``bits=8``) runs the W8A8 tier: x is dynamically
    quantized per row (absmax/127 over the full K axis) HERE, outside the
    grid, so the kernel sees int8 operands and one (M, 1) scale — the
    int8 x int8 dot accumulates in int32 and rescales once at writeout.
    """
    *lead, K = x.shape
    N = scale.shape[0]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    a8 = act_bits == 8 and bits == 8

    # int8 operands need a (32, 128) min tile on real TPUs (interpret
    # mode doesn't care); f32 needs (8, 128)
    bm = min(block_m, max(32 if a8 else 8, 1 << (M - 1).bit_length()))
    if a8:
        from repro.quant.ptq import quantize_rowwise
        xq, sx = quantize_rowwise(x2)
        x2 = _pad_to(xq, 0, bm)
        sxp = _pad_to(sx, 0, bm)
    else:
        x2 = _pad_to(x2, 0, bm)
        sxp = None
    x2 = _pad_to(x2, 1, block_k)
    Kp = x2.shape[1]
    if bits == 4:
        qp = _pad_to(q, 0, block_k // 2)
        assert qp.shape[0] == Kp // 2, (qp.shape, Kp)
    else:
        qp = _pad_to(q, 0, block_k)
    qp = _pad_to(qp, 1, block_n)
    sp = _pad_to(scale.reshape(-1), 0, block_n)

    out = _qm.quant_matmul(x2, qp, sp, bits, x_scale=sxp,
                           out_dtype=x.dtype, block_m=bm, block_n=block_n,
                           block_k=block_k, interpret=INTERPRET)
    return out[:M, :N].reshape(*lead, N)


def qmatmul(x: jax.Array, w) -> jax.Array:
    """Dispatch on weight type: QTensor -> Pallas kernel; array -> XLA.
    QTensor leaves tagged ``act_bits=8`` route to the W8A8 tier."""
    if isinstance(w, QTensor):
        return quant_matmul(x, w.q, w.scale, w.bits, act_bits=w.act_bits)
    return x @ w


def _rope_rows(pos, dh: int, theta: float):
    """cos/sin (1, dh/2) rows for the current decode position (the same
    angle convention as models/common.apply_rope)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.asarray(pos, jnp.float32) * freqs
    return jnp.cos(ang).reshape(1, -1), jnp.sin(ang).reshape(1, -1)


def fusable_decode(p, cfg) -> bool:
    """True when a layer's attention params can take the fused quantized
    decode kernel: all four projections are int8 QTensors (W8A16 or W8A8
    — int4 stays on the unfused tier), no qk-norm (applied between
    projection and rope, which the fused grid doesn't model), and the
    head dim is lane-aligned unless we're interpreting."""
    ws = [p.get("wq"), p.get("wk"), p.get("wv"), p.get("wo")]
    return (all(isinstance(w, QTensor) and w.bits == 8 for w in ws)
            and not cfg.qk_norm
            and (cfg.d_head % 128 == 0 or INTERPRET))


def decode_kernel_tier(p, cfg) -> str:
    """Which decode-attention tier a kernel-routed step takes for layer
    params ``p`` under ``cfg`` (mirrors the dispatch in
    ``models/common.decode_attention[_paged]``): ``"kv8"`` — int8 KV
    cache, kernels bypassed (the dequant-read path has no kernel tier);
    ``"fused"`` — int8 projections through ``flash_decode_fused``;
    ``"flash"`` — fp weights through ``flash_decode``.  Introspection
    for engines/tests asserting what ``use_kernel=True`` actually
    routes to — dequantized trees (interpret-mode serving) report
    ``"flash"`` because ``fusable_decode`` is False for them."""
    if cfg.kv_bits == 8:
        return "kv8"
    return "fused" if fusable_decode(p, cfg) else "flash"


@functools.partial(jax.jit, static_argnames=("rope_theta", "use_rope",
                                             "block_s"))
def flash_decode_fused(x: jax.Array, wq, wk, wv, wo, cache_k: jax.Array,
                       cache_v: jax.Array, pos, rope_theta: float = 1e4,
                       use_rope: bool = True, block_s: int = 512):
    """Fused quantized decode attention (contiguous cache).

    x (B, D) pre-norm hidden rows; wq/wk/wv/wo int8 QTensors; caches
    (B, W, nkv, dh) PRE-write.  The QKV/output projections run on int8
    weight tiles inside the decode grid (W8A8 when the tensors carry
    ``act_bits=8``); the kernel attends over the pre-write cache plus the
    freshly-projected current token, so its output equals project ->
    rope -> cache_write -> flash_decode -> wo on the post-write cache.
    Returns (o (B, D), k1 (B, nkv, dh), v1 (B, nkv, dh)); the CALLER
    writes k1/v1 at slot pos % W.
    """
    B, W, nkv, dh = cache_k.shape[0], cache_k.shape[1], cache_k.shape[2], \
        cache_k.shape[3]
    assert wq.bits == 8 and wo.bits == 8, (wq.bits, wo.bits)
    assert dh % 128 == 0 or INTERPRET, dh
    a8 = wq.act_bits == 8
    bs = min(block_s, max(128, 1 << (W - 1).bit_length()))
    ck = _pad_to(cache_k, 1, bs)
    cv = _pad_to(cache_v, 1, bs)
    posi = jnp.asarray(pos, jnp.int32)
    nv = jnp.broadcast_to(jnp.minimum(posi, W), (B,))
    # slot the current token is about to overwrite: invalid in the
    # pre-write read once the window has wrapped (pos >= W)
    ev = jnp.broadcast_to(jnp.where(posi >= W, posi % W, -1), (B,))
    cos, sin = _rope_rows(posi, dh, rope_theta)
    return _fd.flash_decode_fused(
        x, wq.q, wq.scale.reshape(1, -1), wk.q, wk.scale.reshape(1, -1),
        wv.q, wv.scale.reshape(1, -1), wo.q, wo.scale.reshape(1, -1),
        ck, cv, nv, ev, cos, sin, block_s=bs, use_rope=use_rope, a8=a8,
        interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("rope_theta", "use_rope"))
def flash_decode_fused_paged(x: jax.Array, wq, wk, wv, wo,
                             k_pages: jax.Array, v_pages: jax.Array,
                             table: jax.Array, pos,
                             rope_theta: float = 1e4,
                             use_rope: bool = True):
    """Paged-table flavor of :func:`flash_decode_fused`: k/v_pages
    (P, block_tokens, nkv, dh) arena slices (tail-sliced to the model's
    geometry by the caller), table (B, n_b) int32.  Returns (o, k1, v1);
    the caller writes k1/v1 into page ``table[b, pos // bt]``."""
    B = x.shape[0]
    bt, dh = k_pages.shape[1], k_pages.shape[3]
    W = table.shape[1] * bt
    assert wq.bits == 8 and wo.bits == 8, (wq.bits, wo.bits)
    assert dh % 128 == 0 or INTERPRET, dh
    a8 = wq.act_bits == 8
    posi = jnp.asarray(pos, jnp.int32)
    nv = jnp.broadcast_to(jnp.minimum(posi, W), (B,))
    ev = jnp.broadcast_to(jnp.where(posi >= W, posi % W, -1), (B,))
    cos, sin = _rope_rows(posi, dh, rope_theta)
    return _fd.flash_decode_fused_paged(
        x, wq.q, wq.scale.reshape(1, -1), wk.q, wk.scale.reshape(1, -1),
        wv.q, wv.scale.reshape(1, -1), wo.q, wo.scale.reshape(1, -1),
        k_pages, v_pages, jnp.asarray(table, jnp.int32), nv, ev, cos, sin,
        use_rope=use_rope, a8=a8, interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("block_s",))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 n_valid, block_s: int = 512) -> jax.Array:
    """GQA decode attention: q (B, nh, dh) against k/v (B, W, nkv, dh).

    Pads W up to a block multiple (padded slots are masked by n_valid),
    dh up to 128 lanes.
    """
    B, nh, dh = q.shape
    W = k.shape[1]
    bs = min(block_s, max(128, 1 << (W - 1).bit_length()))
    k = _pad_to(k, 1, bs)
    v = _pad_to(v, 1, bs)
    if dh % 128:
        # kernel scales by 1/sqrt(padded dh); compensate so the net
        # softmax scale stays 1/sqrt(true dh)
        dh_p = dh + (128 - dh % 128)
        q = q * jnp.asarray((dh_p / dh) ** 0.5, q.dtype)
        q = _pad_to(q, 2, 128)
        k = _pad_to(k, 3, 128)
        v = _pad_to(v, 3, 128)
    out = _fd.flash_decode(q, k, v, jnp.asarray(n_valid, jnp.int32),
                           block_s=bs, interpret=INTERPRET)
    return out[..., :dh]


@jax.jit
def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       table: jax.Array, n_valid) -> jax.Array:
    """GQA decode attention through a block table (DESIGN.md §2.3).

    q (B, nh, dh) against a page arena k/v (P, block_tokens, nkv, dh);
    table (B, n_b) int32 maps logical block j of row b to its physical
    page.  Pads dh up to 128 lanes (with softmax-scale compensation, as
    in ``flash_decode``); pages are fixed-size so no W padding is needed.
    """
    dh = q.shape[2]
    if dh % 128:
        dh_p = dh + (128 - dh % 128)
        q = q * jnp.asarray((dh_p / dh) ** 0.5, q.dtype)
        q = _pad_to(q, 2, 128)
        k_pages = _pad_to(k_pages, 3, 128)
        v_pages = _pad_to(v_pages, 3, 128)
    out = _fd.flash_decode_paged(q, k_pages, v_pages,
                                 jnp.asarray(table, jnp.int32),
                                 jnp.asarray(n_valid, jnp.int32),
                                 interpret=INTERPRET)
    return out[..., :dh]
