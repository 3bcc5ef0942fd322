"""Shared building blocks: norms, rotary embeddings, GQA attention (full /
sliding-window / decode-with-cache), FFN, and MoE layers.

All functions are functional (params passed explicitly) and scan-friendly.
Sharding is expressed through logical-axis constraints that no-op outside a
launcher-installed axis context (see utils/sharding.py).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.utils.sharding import axis_divisor, constrain

Params = Dict[str, Any]


def mm(x: jax.Array, w) -> jax.Array:
    """Matmul that dispatches quantized weights to the Pallas dequant-matmul
    (QTensor leaves appear after quant.quantize_tree; plain arrays use XLA)."""
    from repro.quant.ptq import QTensor
    if isinstance(w, QTensor):
        from repro.kernels import ops as kops
        return kops.quant_matmul(x, w.q, w.scale.reshape(-1), w.bits,
                                 act_bits=w.act_bits)
    return x @ w


def maybe_dequant(w):
    """Dense-ify a possibly-quantized weight (for einsum/gather sites)."""
    from repro.quant.ptq import QTensor, dequantize
    if isinstance(w, QTensor):
        return dequantize(w)
    return w

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def make_norm_params(cfg: ModelConfig, key, dtype) -> Optional[jax.Array]:
    if cfg.norm == "nonparam_ln":
        return None
    return jnp.ones((cfg.d_model,), dtype)


def apply_norm(kind: str, w: Optional[jax.Array], x: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:  # layernorm / nonparam_ln
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Per-head RMS norm over the head dim (Qwen3 qk-norm)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)                       # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def make_attn_params(cfg: ModelConfig, key, dtype) -> Params:
    dm, dh = cfg.d_model, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (dm, cfg.n_heads * dh), 0, dtype),
        "wk": dense_init(ks[1], (dm, cfg.n_kv_heads * dh), 0, dtype),
        "wv": dense_init(ks[2], (dm, cfg.n_kv_heads * dh), 0, dtype),
        "wo": dense_init(ks[3], (cfg.n_heads * dh, dm), 0, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def qkv_proj(p: Params, cfg: ModelConfig, x: jax.Array,
             positions: jax.Array, use_rope: bool = True
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> q (B,S,nh,dh), k/v (B,S,nkv,dh)."""
    B, S, _ = x.shape
    dh = cfg.d_head
    q = mm(x, p["wq"]).reshape(B, S, cfg.n_heads, dh)
    k = mm(x, p["wk"]).reshape(B, S, cfg.n_kv_heads, dh)
    v = mm(x, p["wv"]).reshape(B, S, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, None, None)
    v = constrain(v, "batch", None, None, None)
    return q, k, v


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  mask: Optional[jax.Array]) -> jax.Array:
    """Grouped-query attention.

    q: (B, Sq, nh, dh); k, v: (B, Sk, nkv, dh); mask broadcastable to
    (B, 1, 1, Sq, Sk) with True = attend.  Returns (B, Sq, nh, dh).
    """
    B, Sq, nh, dh = q.shape
    nkv = k.shape[2]
    G = nh // nkv
    qg = q.reshape(B, Sq, nkv, G, dh)
    scale = 1.0 / math.sqrt(dh)
    # f32 accumulation via preferred_element_type, NOT astype: an explicit
    # convert of k/v is loop-invariant-hoisted by XLA out of the layer scan,
    # materializing the entire stacked KV cache in f32.
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, nh, dh).astype(q.dtype)


def seq_shard(x: jax.Array) -> jax.Array:
    """Sequence-shard a (B, S, D) residual over the model axis (Megatron
    sequence parallelism).  The scan-over-layers carry is what backward
    saves per layer — sharding it is the difference between O(TB) and
    O(GB) of saved activations for the 80+ layer archs.  No-op when S is
    not divisible or no mesh context is installed."""
    return constrain(x, "batch", "model", None)


def _attn_logits_shard(logits: jax.Array) -> jax.Array:
    """Shard (B, H, Q, Sk) attention logits: prefer heads on 'model',
    fall back to the key dim (sequence-parallel softmax) when the head
    count doesn't divide (e.g. 56 heads on a 16-way axis)."""
    d = axis_divisor("model")
    if d <= 1:
        return logits
    H, Sk = logits.shape[1], logits.shape[3]
    if H % d == 0:
        return constrain(logits, "batch", "model", None, None)
    if Sk % d == 0:
        return constrain(logits, "batch", None, None, "model")
    return constrain(logits, "batch", None, None, None)


def chunked_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             window: int = 0, chunk: int = 512,
                             q_offset: int = 0) -> jax.Array:
    """Blocked causal attention: lax.scan over query chunks so the S x S
    score matrix never materializes (XLA-level flash attention; the Pallas
    decode kernel covers the serve path).  Falls back to the direct masked
    form for short sequences.  q: (B,S,nh,dh), k/v: (B,Sk,nkv,dh)."""
    B, S, nh, dh = q.shape
    Sk, nkv = k.shape[1], k.shape[2]
    G = nh // nkv
    if S <= chunk or S % chunk:
        mask = causal_mask(S, Sk, window, q_offset)
        return gqa_attention(q, k, v, mask)
    nb = S // chunk
    k_r = jnp.repeat(k, G, axis=2) if G > 1 else k    # (B, Sk, nh, dh)
    v_r = jnp.repeat(v, G, axis=2) if G > 1 else v
    k_r = constrain(k_r, "batch", None, "model", None)
    v_r = constrain(v_r, "batch", None, "model", None)
    scale = 1.0 / math.sqrt(dh)
    kpos = jnp.arange(Sk)[None, :]

    def body(carry, inp):
        i, qb = inp                                   # qb (B, chunk, nh, dh)
        logits = jnp.einsum("bqhd,bshd->bhqs", qb, k_r,
                            preferred_element_type=jnp.float32) * scale
        logits = _attn_logits_shard(logits)
        qpos = (i * chunk + q_offset) + jnp.arange(chunk)[:, None]
        m = kpos <= qpos
        if window > 0:
            m &= kpos > qpos - window
        logits = jnp.where(m[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", probs, v_r,
                         preferred_element_type=jnp.float32)
        return carry, out.astype(q.dtype)

    qb = q.reshape(B, nb, chunk, nh, dh).swapaxes(0, 1)
    _, outs = jax.lax.scan(jax.checkpoint(body), 0,
                           (jnp.arange(nb), qb))
    return outs.swapaxes(0, 1).reshape(B, S, nh, dh)


def causal_mask(Sq: int, Sk: int, window: int = 0,
                q_offset: int = 0) -> jax.Array:
    """(1,1,1,Sq,Sk) boolean mask; window=0 => plain causal; window>0 adds a
    sliding-window lower bound.  q_offset shifts query positions (cross-epoch
    chunked prefill)."""
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m[None, None, None]


def attention_block(p: Params, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, window: int = 0,
                    bidirectional: bool = False,
                    use_rope: bool = True) -> jax.Array:
    """Full (training / prefill) self-attention with residual projection.
    Returns attn output (B, S, D) (no residual add)."""
    B, S, _ = x.shape
    q, k, v = qkv_proj(p, cfg, x, positions, use_rope)
    if bidirectional:
        out = gqa_attention(q, k, v, None)
    else:
        out = chunked_causal_attention(q, k, v, window)
    out = out.reshape(B, S, cfg.n_heads * cfg.d_head)
    out = mm(out, p["wo"])
    return constrain(out, "batch", None, None)


# ---------------------------------------------------------------------------
# Decode attention over a slot cache
# ---------------------------------------------------------------------------
# Cache layout: k/v (B, W, nkv, dh) where W = cache capacity (= full seq for
# dense, = window for SWA).  Position p writes slot p % W; since rope is
# applied before caching, attention is permutation-invariant over slots and a
# validity count suffices for masking.
#
# kv_bits=8 (paper §II-B.3 applied to the serving runtime): the cache
# stores int8 values + per-(slot, kv-head) f32 scales.  At decode the
# 32k x 128-request cache is THE dominant HBM traffic (1.5 TB vs 246 GB of
# weights for mistral-large), so halving its bytes halves the memory
# roofline term; dequant happens tile-wise on the way into the MXU.


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(B, S, nkv, dh) -> int8 values + per-(B,S,nkv) f32 scales."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 -128, 127).astype(jnp.int8)
    return q, scale[..., 0]


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_write(cache_k: jax.Array, cache_v: jax.Array, k1: jax.Array,
                v1: jax.Array, pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write one token's k/v (B,1,nkv,dh) at slot pos % W.

    Implemented as a one-hot ``where`` (elementwise) rather than
    dynamic_update_slice: updating a slot-sharded cache must not force
    GSPMD to re-gather the 32k-slot dim on every decode step.
    """
    W = cache_k.shape[1]
    idx = (pos % W).astype(jnp.int32)
    hit = (jnp.arange(W) == idx)[None, :, None, None]
    ck = jnp.where(hit, k1.astype(cache_k.dtype), cache_k)
    cv = jnp.where(hit, v1.astype(cache_v.dtype), cache_v)
    return ck, cv


def decode_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                     cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, use_rope: bool = True,
                     use_kernel: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode step.  x: (B, 1, D); pos: scalar current position.
    Returns (out (B,1,D), new_cache_k, new_cache_v)."""
    B = x.shape[0]
    if use_kernel:
        from repro.kernels import ops as kops
        if kops.fusable_decode(p, cfg):
            # fused tier: QKV/output projections consume the int8 weight
            # tiles inside the decode grid; the kernel attends over the
            # pre-write cache + current token, caller writes k1/v1 after
            o, k1, v1 = kops.flash_decode_fused(
                x[:, 0], p["wq"], p["wk"], p["wv"], p["wo"], cache_k,
                cache_v, pos, rope_theta=cfg.rope_theta, use_rope=use_rope)
            ck, cv = cache_write(cache_k, cache_v, k1[:, None], v1[:, None],
                                 pos)
            return constrain(o[:, None], "batch", None, None), ck, cv
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k1, v1 = qkv_proj(p, cfg, x, positions, use_rope)
    ck, cv = cache_write(cache_k, cache_v, k1, v1, pos)
    W = ck.shape[1]
    n_valid = jnp.minimum(pos + 1, W)
    mask = (jnp.arange(W) < n_valid)[None, None, None, None, :]
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.flash_decode(q[:, 0], ck, cv, n_valid)
        out = out[:, None]
    else:
        out = gqa_attention(q, ck, cv, mask)
    out = mm(out.reshape(B, 1, cfg.n_heads * cfg.d_head), p["wo"])
    return constrain(out, "batch", None, None), ck, cv


def decode_attention_cache(p: Params, cfg: ModelConfig, x: jax.Array,
                           cache: Dict[str, jax.Array], pos: jax.Array,
                           use_kernel: bool = False
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dict-cache decode step supporting int8 KV (cfg.kv_bits == 8).

    cache: {"k","v"} (+ {"ks","vs"} scales when quantized).  Returns
    (out (B,1,D), new cache dict).  ``use_kernel`` routes the fp-cache
    path through the Pallas decode kernels (fused quantized flavor when
    the projections are int8 QTensors).
    """
    if cfg.kv_bits != 8:
        out, ck, cv = decode_attention(p, cfg, x, cache["k"], cache["v"],
                                       pos, use_kernel=use_kernel)
        return out, {"k": ck, "v": cv}
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k1, v1 = qkv_proj(p, cfg, x, positions)
    k1q, k1s = quantize_kv(k1)
    v1q, v1s = quantize_kv(v1)
    W = cache["k"].shape[1]
    idx = (pos % W).astype(jnp.int32)
    hit = (jnp.arange(W) == idx)[None, :, None]
    ck = jnp.where(hit[..., None], k1q, cache["k"])
    cv = jnp.where(hit[..., None], v1q, cache["v"])
    ks = jnp.where(hit, k1s, cache["ks"])
    vs = jnp.where(hit, v1s, cache["vs"])
    dt = _dt = x.dtype
    # dequant tile-wise into the attention reads (fused on TPU)
    kd = dequantize_kv(ck, ks, dt)
    vd = dequantize_kv(cv, vs, dt)
    n_valid = jnp.minimum(pos + 1, W)
    mask = (jnp.arange(W) < n_valid)[None, None, None, None, :]
    out = gqa_attention(q, kd, vd, mask)
    out = mm(out.reshape(B, 1, cfg.n_heads * cfg.d_head), p["wo"])
    return constrain(out, "batch", None, None), \
        {"k": ck, "v": cv, "ks": ks, "vs": vs}


def paged_write_slot(table: jax.Array, pos: jax.Array, bt: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Where one decode step's token lands in the arena, the same for
    every layer: page ``table[b, pos // bt]`` (B,) at offset ``pos % bt``
    (scalar)."""
    B = table.shape[0]
    blk = (pos // bt).astype(jnp.int32)
    page = jnp.take_along_axis(table, jnp.broadcast_to(blk, (B,))[:, None],
                               axis=1)[:, 0]
    return page, (pos % bt).astype(jnp.int32)


def write_paged_tokens(pages: Dict[str, jax.Array],
                       tokens: Dict[str, jax.Array], page: jax.Array,
                       off: jax.Array) -> Dict[str, jax.Array]:
    """Write one decode step's tokens of every layer into the arena: one
    scatter per leaf of ``tokens[name]`` (L, B, nkv[, dh]) at
    ``[:, page[b], off]``, into the leading (nkv, dh) corner of the page
    tail.  On a donated arena it updates the buffers in place.  Rows on
    ``TRASH_PAGE`` may scatter to the same index; that page is
    don't-care."""
    out = {}
    for name, pleaf in pages.items():
        tok = tokens[name]
        corner = tuple(slice(0, d) for d in tok.shape[2:])
        out[name] = pleaf.at[(slice(None), page, off) + corner].set(
            tok.astype(pleaf.dtype))
    return out


def decode_attention_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                           pages: Dict[str, jax.Array], layer: jax.Array,
                           table: jax.Array, pos: jax.Array,
                           use_kernel: bool = False
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode step of layer ``layer`` over a PAGED cache
    (DESIGN.md §2.3).

    pages: the node-wide arena, read only — {"k","v"} of shape
    (L, P, block_tokens, nkv', dh') (+ {"ks","vs"} (L, P, block_tokens,
    nkv') scales when cfg.kv_bits == 8); table: (B, n_b) int32 mapping
    logical block j of row b to its physical page.  Page tails may be
    LARGER than this model's (nkv, dh) — the node pool provisions the
    max over hosted cohorts — so every read slices the leading (nkv, dh)
    corner; the padding is zero-initialized and never observed.

    Returns (out (B, 1, D), tokens): this step's keys and values in the
    page dtype, {"k","v"} (B, nkv, dh) (+ {"ks","vs"} (B, nkv)).  The
    caller writes them at ``paged_write_slot`` after the layer scan
    (``write_paged_tokens``), so the arena is never restacked per layer.
    Attention gathers the row's logical blocks from the pre-write pages
    into the (B, n_b*bt, nkv, dh) view and puts this step's token at
    position ``pos``: the view the write-then-gather order gives, since
    a live row's write page is its own lease — bitwise the contiguous
    cache, which is what makes the paged engine path bit-identical to
    the slab path (rows whose table points at the shared trash page are
    dead and never emit again).  ``use_kernel`` routes the read through
    the fused tier (which attends over the pre-write pages plus the
    current token) or ``flash_decode_paged`` (TPU path, fp cache only),
    whose operand is this layer's slice with the token written in.
    """
    B = x.shape[0]
    nkv, dh = cfg.n_kv_heads, cfg.d_head
    n_b = table.shape[1]
    W = n_b * pages["k"].shape[2]
    dt = pages["k"].dtype

    def layer_slice(pleaf):
        return pleaf[layer, ..., :nkv, :dh]

    if use_kernel and cfg.kv_bits != 8:
        from repro.kernels import ops as kops
        if kops.fusable_decode(p, cfg):
            o, k1f, v1f = kops.flash_decode_fused_paged(
                x[:, 0], p["wq"], p["wk"], p["wv"], p["wo"],
                layer_slice(pages["k"]), layer_slice(pages["v"]),
                table, pos, rope_theta=cfg.rope_theta)
            return constrain(o[:, None], "batch", None, None), \
                {"k": k1f.astype(dt), "v": v1f.astype(dt)}
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k1, v1 = qkv_proj(p, cfg, x, positions)
    if cfg.kv_bits == 8:
        k1q, k1s = quantize_kv(k1)
        v1q, v1s = quantize_kv(v1)
        tokens = {"k": k1q[:, 0], "v": v1q[:, 0],
                  "ks": k1s[:, 0], "vs": v1s[:, 0]}
    else:
        tokens = {"k": k1[:, 0].astype(dt), "v": v1[:, 0].astype(dt)}
    n_valid = jnp.minimum(pos + 1, W)
    if use_kernel and cfg.kv_bits != 8:
        from repro.kernels import ops as kops
        page, off = paged_write_slot(table, pos, pages["k"].shape[2])
        pk, pv = (layer_slice(pages[n]).at[page, off].set(tokens[n])
                  for n in ("k", "v"))
        out = kops.flash_decode_paged(q[:, 0], pk, pv, table, n_valid)
        out = out[:, None]
    else:
        def view(name):
            """Row-major view of a row's logical blocks with this step's
            token at ``pos``, tail-sliced to this model's geometry:
            (B, W, nkv[, dh]).  A select, not an update-slice, so the
            splice fuses into the attention's reads."""
            g = pages[name][layer, table]        # (B, n_b, bt, *tail')
            g = g[..., :nkv, :dh] if g.ndim == 5 else g[..., :nkv]
            g = g.reshape((B, W) + g.shape[3:])
            hit = (jnp.arange(W) == pos).reshape((1, W)
                                                 + (1,) * (g.ndim - 2))
            return jnp.where(hit, tokens[name][:, None], g)

        if cfg.kv_bits == 8:
            kd = dequantize_kv(view("k"), view("ks"), x.dtype)
            vd = dequantize_kv(view("v"), view("vs"), x.dtype)
        else:
            kd, vd = view("k"), view("v")
        mask = (jnp.arange(W) < n_valid)[None, None, None, None, :]
        out = gqa_attention(q, kd, vd, mask)
    out = mm(out.reshape(B, 1, cfg.n_heads * cfg.d_head), p["wo"])
    return constrain(out, "batch", None, None), tokens


def prefill_cache_from_kv(k: jax.Array, v: jax.Array, W: int
                          ) -> Tuple[jax.Array, jax.Array]:
    """Build the slot cache from prefill k/v (B, S, nkv, dh).

    Positions p land at slot p % W; only the last W positions survive.
    """
    B, S, nkv, dh = k.shape
    ck = jnp.zeros((B, W, nkv, dh), k.dtype)
    cv = jnp.zeros((B, W, nkv, dh), v.dtype)
    start = max(0, S - W)
    pos = jnp.arange(start, S)
    slots = pos % W
    ck = ck.at[:, slots].set(k[:, start:])
    cv = cv.at[:, slots].set(v[:, start:])
    # slot caches shard over batch + slots (32k x 128-batch caches are the
    # dominant serving footprint; see launch/steps.cache_specs)
    ck = constrain(ck, "batch", "model", None, None)
    cv = constrain(cv, "batch", "model", None, None)
    return ck, cv


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def make_ffn_params(cfg: ModelConfig, key, dtype, d_ff: Optional[int] = None
                    ) -> Params:
    dm = cfg.d_model
    df = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "silu":   # gated (SwiGLU)
        return {"w1": dense_init(ks[0], (dm, df), 0, dtype),
                "w3": dense_init(ks[1], (dm, df), 0, dtype),
                "w2": dense_init(ks[2], (df, dm), 0, dtype)}
    return {"w1": dense_init(ks[0], (dm, df), 0, dtype),
            "w2": dense_init(ks[2], (df, dm), 0, dtype)}


def ffn_apply(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        h = jax.nn.silu(mm(x, p["w1"])) * mm(x, p["w3"])
    elif cfg.act == "gelu":
        h = jax.nn.gelu(mm(x, p["w1"]))
    else:
        h = jax.nn.relu(mm(x, p["w1"]))
    h = constrain(h, "batch", None, "model")
    out = mm(h, p["w2"])
    return constrain(out, "batch", None, None)


# ---------------------------------------------------------------------------
# MoE (token-choice top-k with capacity dispatch)
# ---------------------------------------------------------------------------


def make_moe_params(cfg: ModelConfig, key, dtype) -> Params:
    E, dm, df = cfg.moe.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    p = {"router": dense_init(ks[0], (dm, E), 0, dtype),
         "w1": dense_init(ks[1], (E, dm, df), 1, dtype),
         "w2": dense_init(ks[2], (E, df, dm), 1, dtype)}
    if cfg.act == "silu":
        p["w3"] = dense_init(ks[3], (E, dm, df), 1, dtype)
    return p


def moe_apply(p: Params, cfg: ModelConfig, x: jax.Array,
              capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """Top-k token-choice MoE with per-expert capacity.

    x: (B, S, D).  Returns (out, aux_loss).  Dispatch/combine are one-hot
    scatter/gathers so the per-expert compute is E*C*D*F (≈ active FLOPs ×
    capacity_factor), not E×T full compute.
    """
    B, S, D = x.shape
    E, K = cfg.moe.n_experts, cfg.moe.top_k
    T = B * S
    d = axis_divisor("model")
    expert_parallel = E % d == 0
    # Non-expert-parallel (E doesn't divide the axis, e.g. Mixtral's 8 on
    # 16): token dims sharded over the batch axes throughout — GSPMD
    # cannot propagate through the dispatch scatter and every (.., C, ..)
    # buffer would otherwise materialize at GLOBAL capacity.  The
    # expert-parallel path must NOT get these: token constraints fight the
    # E-sharded scatter and replicate the (T*K, D) dispatch instead
    # (measured: granite-moe train 15 -> 131 GiB).
    tok = (lambda a: constrain(a, "batch", *([None] * (a.ndim - 1)))) \
        if not expert_parallel else (lambda a: a)
    xt = tok(x.reshape(T, D))
    gate_logits = mm(xt, p["router"]).astype(jnp.float32)      # (T, E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    gate_w, gate_idx = jax.lax.top_k(probs, K)                # (T, K)
    gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    # load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    C = int(math.ceil(T * K / E * capacity_factor))
    C = max(C, 1)
    # position of each (token, k) assignment within its expert's buffer
    flat_idx = gate_idx.reshape(-1)                            # (T*K,)
    onehot = jax.nn.one_hot(flat_idx, E, dtype=jnp.int32)      # (T*K, E)
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)           # pre-count
    pos = jnp.take_along_axis(pos_in_e, flat_idx[:, None], axis=1)[:, 0]
    keep = pos < C
    buf = jnp.zeros((E, C, D), xt.dtype)
    tok_ids = jnp.repeat(jnp.arange(T), K)
    safe_pos = jnp.where(keep, pos, C - 1)
    buf = buf.at[flat_idx, safe_pos].add(
        jnp.where(keep[:, None], xt[tok_ids], 0).astype(xt.dtype))

    # Two MoE layouts (must AGREE with launch/steps param rules — fighting
    # the weight sharding makes GSPMD materialize (E, C, d_ff) unsharded):
    #  * E % model == 0: expert parallel — buf/h/eout sharded on E;
    #  * otherwise: per-expert tensor parallel — h sharded on d_ff exactly
    #    like w1/w3; w2's contraction over d_ff psums back to replicated.
    buf = constrain(buf, "model", None, None) if expert_parallel \
        else constrain(buf, None, "batch", None)

    # expert FFN over (E, C, D)
    if cfg.act == "silu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, maybe_dequant(p["w1"]))) \
            * jnp.einsum("ecd,edf->ecf", buf, maybe_dequant(p["w3"]))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, maybe_dequant(p["w1"])))
    h = constrain(h, "model", None, None) if expert_parallel \
        else constrain(h, None, "batch", "model")
    eout = jnp.einsum("ecf,efd->ecd", h, maybe_dequant(p["w2"]))
    eout = constrain(eout, "model", None, None) if expert_parallel \
        else constrain(eout, None, "batch", None)

    # combine
    gathered = eout[flat_idx, safe_pos]                        # (T*K, D)
    gathered = tok(jnp.where(keep[:, None], gathered, 0))
    w = gate_w.reshape(-1)[:, None].astype(gathered.dtype)
    out = jnp.zeros((T, D), xt.dtype).at[tok_ids].add(gathered * w)
    out = tok(out)
    return out.reshape(B, S, D), aux
