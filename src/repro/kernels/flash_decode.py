"""Pallas TPU flash-decoding kernel for the auto-regressive stage.

The paper's t_A (decode latency) is memory-bound: one token's query reads
the whole KV cache.  TPU-native design (DESIGN.md §3): the cache streams
HBM->VMEM in (block_s, dh) tiles; an online-softmax accumulator (running
max m, denominator l, weighted sum acc) lives in VMEM scratch across the
sequence-block grid steps, so each KV byte is read exactly once.  GQA
grouping puts the G = nh/nkv query heads of one KV head together in the
tile so the MXU sees (G, dh) x (dh, block_s) matmuls.

Grid: (B, nkv/hb, W/block_s), sequence innermost ("arbitrary").  A grid
step takes a block of hb KV heads (``head_block``: Mosaic needs a K/V
block's (heads, dh) minor dims tile-aligned or whole) and the body walks
them one by one.  The slot mask (slot < n_valid) handles both
partially-filled caches and the rolling sliding-window layout (validity
is a count, order is irrelevant under softmax since rope was applied
before caching).

PAGED variant (``flash_decode_paged``, DESIGN.md §2.3): K/V live in a
node-wide block-pool arena of fixed ``block_tokens`` pages instead of one
contiguous (B, W) slab.  The grid still walks LOGICAL sequence blocks;
the physical page holding logical block j of row b is resolved per grid
step through a scalar-prefetched block table — the index map reads
``table[b, j]`` and the pipeline DMAs that page, so the kernel body is
byte-for-byte the contiguous kernel with ``block_s = block_tokens``.
Driven with a logical-order table over the same values it is therefore
bit-identical to ``flash_decode`` at the same block size (the oracle the
paged tests pin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params

DEFAULT_BS = 512
NEG = -1e30


def head_block(nkv: int) -> int:
    """KV heads per grid step.  Mosaic needs the last two dims of every
    block to be (8, 128)-aligned or whole, and a K/V block's last two
    dims are (heads, dh): so 8 heads when they divide ``nkv``, else all
    of them.  The kernel bodies loop over the block's heads."""
    return 8 if nkv % 8 == 0 else nkv


def _softmax_step(i, q, k, v, valid, m_ref, l_ref, acc_ref):
    """One online-softmax block for head ``i`` of the grid step's head
    block: q (G, dh) f32 pre-scaled, k/v (bs, dh) f32, valid (G, bs)."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)      # (G, bs)
    s = jnp.where(valid, s, NEG)
    m_prev = m_ref[i][:, :1]                                     # (G, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)                   # (G, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                       # (G, bs)
    alpha = jnp.exp(m_prev - m_new)                              # (G, 1)
    l_new = alpha * l_ref[i][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[i] = acc_ref[i] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[i] = jnp.broadcast_to(m_new, m_ref.shape[1:])
    l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])


def _softmax_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _decode_kernel(nv_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, n_s: int, block_s: int):
    """One (batch, kv-head block) pair; grid axis 2 walks the sequence
    blocks (logical blocks for the paged layout: the page indirection
    happened in the BlockSpec index map, so the body is shared).

    q_ref:  (1, hb, G, dh)   queries of the block's head groups
    k_ref:  (1, block_s, hb, dh)
    v_ref:  (1, block_s, hb, dh)
    nv_ref: (B,) int32       valid-slot counts (scalar-prefetch, SMEM);
                             indexed by the batch grid position
    o_ref:  (1, hb, G, dh)
    scratch: m/l (hb, G, 128), acc (hb, G, dh)  [f32]
    """
    ss = pl.program_id(2)

    @pl.when(ss == 0)
    def _():
        _softmax_init(m_ref, l_ref, acc_ref)

    hb, G, dh = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    slot = ss * block_s + jax.lax.broadcasted_iota(jnp.int32, (G, block_s), 1)
    valid = slot < nv_ref[pl.program_id(0)]
    for i in range(hb):
        q = q_ref[0, i].astype(jnp.float32) * (1.0 / (dh ** 0.5))  # (G, dh)
        k = k_ref[0, :, i].astype(jnp.float32)                      # (bs, dh)
        v = v_ref[0, :, i].astype(jnp.float32)
        _softmax_step(i, q, k, v, valid, m_ref, l_ref, acc_ref)

    @pl.when(ss == n_s - 1)
    def _():
        out = acc_ref[...] / jnp.maximum(l_ref[..., :1], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def _decode_scratch(hb: int, G: int, dh: int):
    return [pltpu.VMEM((hb, G, 128), jnp.float32),
            pltpu.VMEM((hb, G, 128), jnp.float32),
            pltpu.VMEM((hb, G, dh), jnp.float32)]


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 n_valid: jax.Array, *, block_s: int = DEFAULT_BS,
                 interpret: bool = False) -> jax.Array:
    """GQA decode attention.  q: (B, nh, dh); k/v: (B, W, nkv, dh);
    n_valid: scalar or (B,) valid-slot count.  Returns (B, nh, dh)."""
    B, nh, dh = q.shape
    W, nkv = k.shape[1], k.shape[2]
    G = nh // nkv
    hb = head_block(nkv)
    block_s = min(block_s, W)
    assert W % block_s == 0, (W, block_s)
    n_s = W // block_s
    nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))

    qg = q.reshape(B, nkv, G, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nkv // hb, n_s),
        in_specs=[
            pl.BlockSpec((1, hb, G, dh), lambda b, h, s, nv: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, hb, dh),
                         lambda b, h, s, nv: (b, s, h, 0)),
            pl.BlockSpec((1, block_s, hb, dh),
                         lambda b, h, s, nv: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, hb, G, dh),
                               lambda b, h, s, nv: (b, h, 0, 0)),
        scratch_shapes=_decode_scratch(hb, G, dh),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, n_s=n_s, block_s=block_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, G, dh), q.dtype),
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(nv, qg, k, v)
    return out.reshape(B, nh, dh)


def _paged_decode_kernel(nv_ref, tbl_ref, *refs, **kw):
    """Paged flavor: the block table is consumed only by the BlockSpec
    index maps (``tbl_ref[b, j]``); the body is the contiguous kernel at
    ``block_s = block_tokens``."""
    del tbl_ref
    _decode_kernel(nv_ref, *refs, **kw)


def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       table: jax.Array, n_valid: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """GQA decode attention through a block table.

    q: (B, nh, dh); k_pages/v_pages: (P, block_tokens, nkv, dh) — the
    node-wide page arena; table: (B, n_b) int32, logical block j of row b
    lives in physical page ``table[b, j]``; n_valid: scalar or (B,) valid
    LOGICAL slot count.  Returns (B, nh, dh).
    """
    B, nh, dh = q.shape
    P, bt, nkv, _ = k_pages.shape
    n_b = table.shape[1]
    G = nh // nkv
    hb = head_block(nkv)
    nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    tbl = jnp.asarray(table, jnp.int32)

    qg = q.reshape(B, nkv, G, dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nkv // hb, n_b),
        in_specs=[
            pl.BlockSpec((1, hb, G, dh),
                         lambda b, h, j, nv, tbl: (b, h, 0, 0)),
            # page indirection: logical block j -> physical page tbl[b, j]
            pl.BlockSpec((1, bt, hb, dh),
                         lambda b, h, j, nv, tbl: (tbl[b, j], 0, h, 0)),
            pl.BlockSpec((1, bt, hb, dh),
                         lambda b, h, j, nv, tbl: (tbl[b, j], 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, hb, G, dh),
                               lambda b, h, j, nv, tbl: (b, h, 0, 0)),
        scratch_shapes=_decode_scratch(hb, G, dh),
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, n_s=n_b, block_s=bt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, G, dh), q.dtype),
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(nv, tbl, qg, k_pages, v_pages)
    return out.reshape(B, nh, dh)


# ---------------------------------------------------------------------------
# Fused QUANTIZED flash-decode (DESIGN.md §3): the QKV/output projections
# consume int8 weight tiles directly inside the decode grid, so one kernel
# covers hidden-state -> attention output and the HBM side never sees an
# fp weight copy.  Layout per grid step (b, h, ss), h a block of hb kv
# heads (``head_block``) whose heads the body walks one by one:
#
#   ss == 0      : project q/k1/v1 for each head of (b, h) from x (1, D)
#                  and its slice of the int8 tiles wq (D, hb*G*dh) /
#                  wk, wv (D, hb*dh); apply rope from precomputed cos/sin
#                  rows; stash in VMEM scratch and emit k1/v1 as outputs
#                  (the caller writes the cache — the kernel attends over
#                  the PRE-write cache and folds the current token in as
#                  a final online-softmax step, which is equivalent
#                  because slot pos is masked out of the pre-write reads).
#   every ss     : one online-softmax block per head over the cache,
#                  exactly ``_decode_kernel``.
#   ss == n_s-1  : fold in the current token, normalize, and project each
#                  (G, dh) head group through its slice of the wo tile
#                  (hb*G*dh, D), accumulating head by head into an f32
#                  (1, D) scratch that the last head block writes out
#                  (axis 1 is "arbitrary" so the output block stays
#                  resident in VMEM; summing 32 heads in a bf16 output
#                  cost 1.5% of the output's scale on the chip).
#
# ``a8=True`` additionally quantizes the projection activations per row
# (absmax/127, in-kernel) and runs int8 x int8 -> int32 dots — the W8A8
# tier inside the decode grid.  Attention itself stays f32 (the cache is
# fp here; int8-KV decode keeps its own dequant path in models/common).
# Rows travel as (B, 1, D) and k1/v1 as (B, nkv, 1, dh) so every block's
# last two dims are whole.
# ---------------------------------------------------------------------------

# Scoped VMEM for the fused grid: the four int8 weight tiles of one head
# block are double-buffered (4 x 2 x D x hb*G*dh bytes — 32 MiB at
# bloom-7b1's D=4096, hb*G*dh=1024) and each head's tile slice is
# dequantized to f32 in VMEM.  The v5e compile refuses that layer at the
# 16 MiB default and at 32 MiB, and takes it at 48 MiB; v5e has 128 MiB.
FUSED_VMEM_BYTES = 64 * 1024 * 1024


def _qproject(xr, w, s, a8: bool):
    """(1, Din) f32 @ dequant(w (Din, Dout) int8, s (1, Dout)) -> (1, Dout).

    a8: dynamic rowwise activation quantization feeding an int8 x int8
    dot with int32 accumulation and a single rescale at writeout (the
    in-grid copy of the quant_matmul W8A8 tier)."""
    if a8:
        amax = jnp.max(jnp.abs(xr), axis=-1, keepdims=True)
        sx = jnp.where(amax > 0, amax * jnp.float32(1.0 / 127.0), 1.0)
        xq = jnp.clip(jnp.round(xr / sx), -128, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(xq, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sx * s.astype(jnp.float32)
    wf = w.astype(jnp.float32) * s.astype(jnp.float32)
    return jnp.dot(xr, wf, preferred_element_type=jnp.float32)


def _rot_half(t, cos, sin):
    """Rope rotation on (R, dh) rows; cos/sin (1, dh/2) — the same
    split-halves convention as models/common.apply_rope."""
    h = t.shape[-1] // 2
    t1, t2 = t[:, :h], t[:, h:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                           axis=-1)


def _fused_body(nv_ref, ev_ref, x_ref, cos_ref, sin_ref, wq_ref, sq_ref,
                wk_ref, sk_ref, wv_ref, sv_ref, wo_ref, so_ref, k_ref,
                v_ref, o_ref, k1_ref, v1_ref, q_s, k1_s, v1_s, m_ref,
                l_ref, acc_ref, o_acc, *, n_s: int, block_s: int,
                use_rope: bool, a8: bool):
    """Shared body of the contiguous and paged fused kernels (the paged
    variant only changes how k_ref/v_ref blocks are addressed)."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    ss = pl.program_id(2)
    hb, G, dh = q_s.shape
    gd = G * dh
    inv_sqrt = 1.0 / (dh ** 0.5)

    @pl.when(ss == 0)
    def _():
        xr = x_ref[0].astype(jnp.float32)                        # (1, D)
        for i in range(hb):
            qh = _qproject(xr, wq_ref[:, i * gd:(i + 1) * gd],
                           sq_ref[:, i * gd:(i + 1) * gd], a8)
            qh = qh.reshape(G, dh)
            k1 = _qproject(xr, wk_ref[:, i * dh:(i + 1) * dh],
                           sk_ref[:, i * dh:(i + 1) * dh], a8)   # (1, dh)
            v1 = _qproject(xr, wv_ref[:, i * dh:(i + 1) * dh],
                           sv_ref[:, i * dh:(i + 1) * dh], a8)
            if use_rope:
                cos, sin = cos_ref[...], sin_ref[...]
                qh = _rot_half(qh, cos, sin)
                k1 = _rot_half(k1, cos, sin)
            q_s[i] = qh
            k1_s[i] = k1
            v1_s[i] = v1
            k1_ref[0, i] = k1.astype(k1_ref.dtype)
            v1_ref[0, i] = v1.astype(v1_ref.dtype)
        _softmax_init(m_ref, l_ref, acc_ref)

    slot = ss * block_s + jax.lax.broadcasted_iota(jnp.int32, (G, block_s), 1)
    # pre-write cache: nv slots are valid, minus the one the current
    # token is about to overwrite (rolling windows at pos >= W)
    valid = (slot < nv_ref[b]) & (slot != ev_ref[b])
    for i in range(hb):
        k = k_ref[0, :, i].astype(jnp.float32)                   # (bs, dh)
        v = v_ref[0, :, i].astype(jnp.float32)
        _softmax_step(i, q_s[i] * inv_sqrt, k, v, valid, m_ref, l_ref,
                      acc_ref)

    @pl.when(ss == n_s - 1)
    def _():
        @pl.when(h == 0)
        def _():
            o_acc[...] = jnp.zeros_like(o_acc)

        for i in range(hb):
            # the current token as one more online-softmax step
            qf = q_s[i] * inv_sqrt
            s_cur = jnp.dot(qf, k1_s[i].T,
                            preferred_element_type=jnp.float32)  # (G, 1)
            m_prev = m_ref[i][:, :1]
            m_fin = jnp.maximum(m_prev, s_cur)
            p = jnp.exp(s_cur - m_fin)
            alpha = jnp.exp(m_prev - m_fin)
            l_fin = alpha * l_ref[i][:, :1] + p
            acc_fin = acc_ref[i] * alpha + jnp.dot(
                p, v1_s[i], preferred_element_type=jnp.float32)
            attn = acc_fin / jnp.maximum(l_fin, 1e-30)           # (G, dh)
            o_c = _qproject(attn.reshape(1, gd),
                            wo_ref[i * gd:(i + 1) * gd, :], so_ref[...], a8)
            o_acc[...] += o_c

        @pl.when(h == pl.num_programs(1) - 1)
        def _():
            o_ref[0] = o_acc[...].astype(o_ref.dtype)


def _fused_paged_body(nv_ref, ev_ref, tbl_ref, *rest, **kw):
    """Paged flavor: the block table is consumed only by the BlockSpec
    index maps; the body itself is the contiguous kernel."""
    del tbl_ref
    _fused_body(nv_ref, ev_ref, *rest, **kw)


def _fused_call(body, n_prefetch, kv_spec, n_s, block_s, prefetch, x, cos,
                sin, wq, sq, wk, sk, wv, sv, wo, so, k, v, *, use_rope, a8,
                interpret):
    """The pallas_call shared by both fused layouts: everything but the
    K/V BlockSpec (``kv_spec``) and the scalar-prefetch operands."""
    B, D = x.shape
    nkv, dh = k.shape[2], k.shape[3]
    G = wq.shape[1] // dh // nkv
    hb = head_block(nkv)
    gd = G * dh

    def spec(shape, index):
        return pl.BlockSpec(shape, lambda b, h, s, *pf: index(b, h))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(B, nkv // hb, n_s),
        in_specs=[
            spec((1, 1, D), lambda b, h: (b, 0, 0)),               # x
            spec((1, dh // 2), lambda b, h: (0, 0)),               # cos
            spec((1, dh // 2), lambda b, h: (0, 0)),               # sin
            spec((D, hb * gd), lambda b, h: (0, h)),               # wq
            spec((1, hb * gd), lambda b, h: (0, h)),
            spec((D, hb * dh), lambda b, h: (0, h)),               # wk
            spec((1, hb * dh), lambda b, h: (0, h)),
            spec((D, hb * dh), lambda b, h: (0, h)),               # wv
            spec((1, hb * dh), lambda b, h: (0, h)),
            spec((hb * gd, D), lambda b, h: (h, 0)),               # wo
            spec((1, D), lambda b, h: (0, 0)),
            kv_spec(hb),                                           # k
            kv_spec(hb),                                           # v
        ],
        out_specs=[
            spec((1, 1, D), lambda b, h: (b, 0, 0)),               # o
            spec((1, hb, 1, dh), lambda b, h: (b, h, 0, 0)),       # k1
            spec((1, hb, 1, dh), lambda b, h: (b, h, 0, 0)),       # v1
        ],
        scratch_shapes=[pltpu.VMEM((hb, G, dh), jnp.float32),     # q
                        pltpu.VMEM((hb, 1, dh), jnp.float32),     # k1
                        pltpu.VMEM((hb, 1, dh), jnp.float32),     # v1
                        *_decode_scratch(hb, G, dh),              # m, l, acc
                        pltpu.VMEM((1, D), jnp.float32)],         # o
    )
    out_shapes = [jax.ShapeDtypeStruct((B, 1, D), x.dtype),
                  jax.ShapeDtypeStruct((B, nkv, 1, dh), x.dtype),
                  jax.ShapeDtypeStruct((B, nkv, 1, dh), x.dtype)]
    o, k1, v1 = pl.pallas_call(
        functools.partial(body, n_s=n_s, block_s=block_s,
                          use_rope=use_rope, a8=a8),
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=compiler_params(
            "parallel", "arbitrary", "arbitrary",
            vmem_limit_bytes=FUSED_VMEM_BYTES),
        interpret=interpret,
    )(*prefetch, x.reshape(B, 1, D), cos, sin, wq, sq, wk, sk, wv, sv, wo,
      so, k, v)
    return o.reshape(B, D), k1.reshape(B, nkv, dh), v1.reshape(B, nkv, dh)


def flash_decode_fused(x, wq, sq, wk, sk, wv, sv, wo, so, k_cache, v_cache,
                       n_valid, evict, cos, sin, *, block_s: int = DEFAULT_BS,
                       use_rope: bool = True, a8: bool = False,
                       interpret: bool = False):
    """Fused quantized decode-attention over a contiguous slot cache.

    x (B, D) hidden rows; wq (D, nh*dh)/wk, wv (D, nkv*dh) int8 with
    (1, cols) f32 scales; wo (nh*dh, D) int8 + (1, D) scale; k/v_cache
    (B, W, nkv, dh) PRE-write; n_valid (B,) valid slots (= pos), evict
    (B,) slot the current token will overwrite (-1 = none); cos/sin
    (1, dh/2) rope rows for the current position.  Returns
    (o (B, D), k1 (B, nkv, dh), v1 (B, nkv, dh)) — the caller writes
    k1/v1 at slot pos.
    """
    W, dh = k_cache.shape[1], k_cache.shape[3]
    block_s = min(block_s, W)
    assert W % block_s == 0, (W, block_s)

    def kv_spec(hb):
        return pl.BlockSpec((1, block_s, hb, dh),
                            lambda b, h, s, *pf: (b, s, h, 0))

    prefetch = (jnp.asarray(n_valid, jnp.int32),
                jnp.asarray(evict, jnp.int32))
    return _fused_call(_fused_body, 2, kv_spec, W // block_s, block_s,
                       prefetch, x, cos, sin, wq, sq, wk, sk, wv, sv, wo,
                       so, k_cache, v_cache, use_rope=use_rope, a8=a8,
                       interpret=interpret)


def flash_decode_fused_paged(x, wq, sq, wk, sk, wv, sv, wo, so, k_pages,
                             v_pages, table, n_valid, evict, cos, sin, *,
                             use_rope: bool = True, a8: bool = False,
                             interpret: bool = False):
    """Paged-table flavor of :func:`flash_decode_fused`: K/V live in the
    node-wide page arena (P, block_tokens, nkv, dh) and grid axis 2
    walks LOGICAL blocks through the scalar-prefetched table, exactly as
    ``flash_decode_paged``.  Returns (o, k1, v1); the caller writes
    k1/v1 into page ``table[b, pos // bt]`` offset ``pos % bt``.
    """
    bt, dh = k_pages.shape[1], k_pages.shape[3]

    def kv_spec(hb):
        # page indirection: logical block j -> physical page tbl[b, j]
        return pl.BlockSpec((1, bt, hb, dh),
                            lambda b, h, j, nv, ev, tbl: (tbl[b, j], 0, h, 0))

    prefetch = (jnp.asarray(n_valid, jnp.int32),
                jnp.asarray(evict, jnp.int32), jnp.asarray(table, jnp.int32))
    return _fused_call(_fused_paged_body, 3, kv_spec, table.shape[1], bt,
                       prefetch, x, cos, sin, wq, sq, wk, sk, wv, sv, wo,
                       so, k_pages, v_pages, use_rope=use_rope, a8=a8,
                       interpret=interpret)
