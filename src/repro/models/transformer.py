"""Generic decoder-only transformer covering the dense, MoE and VLM families.

* scan-over-layers (stacked layer params) so HLO size is O(1) in depth;
* GQA attention with optional sliding window / qk-norm;
* MoE FFN (top-k capacity dispatch) when ``cfg.is_moe``;
* VLM: the stub vision frontend supplies patch embeddings that are prepended
  to the text embeddings (deliverable carve-out, DESIGN.md §4).

Cache layout for decode: k/v slot caches (L, B, W, nkv, dh) where
W = sliding window (if any) else full context capacity.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.models import common
from repro.models.api import Model, cross_entropy
from repro.utils.remat import maybe_remat
from repro.utils.sharding import constrain

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = _dtype(cfg)
    k_embed, k_layers, k_final, k_head = jax.random.split(key, 4)
    Vp = cfg.vocab_padded()

    def layer_init(lkey):
        ka, kf, kn = jax.random.split(lkey, 3)
        p = {"attn": common.make_attn_params(cfg, ka, dt),
             "norm1": common.make_norm_params(cfg, kn, dt),
             "norm2": common.make_norm_params(cfg, kn, dt)}
        if cfg.is_moe:
            p["moe"] = common.make_moe_params(cfg, kf, dt)
        else:
            p["ffn"] = common.make_ffn_params(cfg, kf, dt)
        return p

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(layer_init)(layer_keys)

    params = {
        "embed": common.embed_init(k_embed, (Vp, cfg.d_model), dt),
        "layers": layers,
        "final_norm": common.make_norm_params(cfg, k_final, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(k_head, (cfg.d_model, Vp), 0, dt)
    return params


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def _layer_fwd(cfg: ModelConfig, lp: Params, x: jax.Array,
               positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Single layer; returns (x, aux_loss)."""
    h = common.apply_norm(cfg.norm, lp["norm1"], x)
    x = x + common.attention_block(lp["attn"], cfg, h, positions,
                                   window=cfg.sliding_window)
    h = common.apply_norm(cfg.norm, lp["norm2"], x)
    if cfg.is_moe:
        out, aux = common.moe_apply(lp["moe"], cfg, h)
    else:
        out, aux = common.ffn_apply(lp["ffn"], cfg, h), jnp.zeros((), jnp.float32)
    return common.seq_shard(x + out), aux


def _embed_inputs(cfg: ModelConfig, params: Params, batch) -> jax.Array:
    tok = batch["tokens"]
    x = common.maybe_dequant(params["embed"])[tok]
    if cfg.family == "vlm":
        # stub ViT frontend output, already projected to d_model
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    return constrain(x, "batch", None, None)


def _unembed(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ common.maybe_dequant(params["embed"]).T
    else:
        logits = common.mm(x, params["lm_head"])
    return logits


def forward(cfg: ModelConfig, params: Params, batch) -> jax.Array:
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(carry, lp):
        x, aux = carry
        x, a = _layer_fwd(cfg, lp, x, positions)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(maybe_remat(body),
                               (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    logits = _unembed(cfg, params, x)
    return logits, aux


def loss_fn(cfg: ModelConfig, params: Params, batch):
    logits, aux = forward(cfg, params, batch)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if cfg.family == "vlm":
        # image positions carry no LM loss
        n_img = cfg.vlm.n_img_tokens
        logits = logits[:, n_img:]
    loss = cross_entropy(logits, labels, cfg.vocab, mask)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------


def cache_capacity(cfg: ModelConfig, context_len: int) -> int:
    return min(context_len, cfg.sliding_window) if cfg.sliding_window \
        else context_len


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    W = cache_capacity(cfg, cache_len)
    shape = (cfg.n_layers, batch, W, cfg.n_kv_heads, cfg.d_head)
    if cfg.kv_bits == 8:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.ones(shape[:-1], jnp.float32),
                "vs": jnp.ones(shape[:-1], jnp.float32)}
    return {"k": jnp.zeros(shape, _dtype(cfg)),
            "v": jnp.zeros(shape, _dtype(cfg))}


def prefill(cfg: ModelConfig, params: Params, batch, cache_len: int = 0):
    """Run the prompt through the stack; return (last-token logits, cache).

    ``cache_len`` sets decode cache capacity (0 => prompt length).
    """
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    W = cache_capacity(cfg, cache_len or S)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, lp):
        h = common.apply_norm(cfg.norm, lp["norm1"], x)
        q, k, v = common.qkv_proj(lp["attn"], cfg, h, positions)
        att = common.chunked_causal_attention(q, k, v, cfg.sliding_window)
        att = common.mm(att.reshape(B, S, cfg.n_heads * cfg.d_head), lp["attn"]["wo"])
        x = x + constrain(att, "batch", None, None)
        h = common.apply_norm(cfg.norm, lp["norm2"], x)
        if cfg.is_moe:
            out, _ = common.moe_apply(lp["moe"], cfg, h)
        else:
            out = common.ffn_apply(lp["ffn"], cfg, h)
        if cfg.kv_bits == 8:
            kq, ks = common.quantize_kv(k)
            vq, vs = common.quantize_kv(v)
            ck, cv = common.prefill_cache_from_kv(kq, vq, W)
            cks, cvs = common.prefill_cache_from_kv(ks[..., None],
                                                    vs[..., None], W)
            layer_cache = {"k": ck, "v": cv,
                           "ks": cks[..., 0], "vs": cvs[..., 0]}
        else:
            ck, cv = common.prefill_cache_from_kv(k, v, W)
            layer_cache = {"k": ck, "v": cv}
        return common.seq_shard(x + out), layer_cache

    x, cache = jax.lax.scan(body, x, params["layers"])
    x = common.apply_norm(cfg.norm, params["final_norm"], x[:, -1:])
    logits = _unembed(cfg, params, x)[:, 0]
    return logits, cache


def decode_step(cfg: ModelConfig, params: Params, cache, tokens: jax.Array,
                pos: jax.Array, use_kernel: bool = False):
    """One decode iteration.  tokens: (B, 1) int32; pos: scalar int32 giving
    the position of this token (cache holds positions < pos).
    ``use_kernel`` routes attention through the Pallas decode kernels
    (fused quantized flavor when the weights are int8 QTensors)."""
    x = common.maybe_dequant(params["embed"])[tokens]
    x = constrain(x, "batch", None, None)

    def body(x, inputs):
        lp, layer_cache = inputs
        h = common.apply_norm(cfg.norm, lp["norm1"], x)
        att, layer_cache = common.decode_attention_cache(
            lp["attn"], cfg, h, layer_cache, pos, use_kernel)
        x = x + att
        h = common.apply_norm(cfg.norm, lp["norm2"], x)
        if cfg.is_moe:
            out, _ = common.moe_apply(lp["moe"], cfg, h)
        else:
            out = common.ffn_apply(lp["ffn"], cfg, h)
        return x + out, layer_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    logits = _unembed(cfg, params, x)[:, 0]
    return logits, new_cache


def decode_step_paged(cfg: ModelConfig, params: Params, pages, table,
                      tokens: jax.Array, pos: jax.Array,
                      use_kernel: bool = False):
    """One decode iteration over the PAGED cache (DESIGN.md §2.3).

    ``pages``: arena leaves stacked over layers — {"k","v"} of shape
    (L, P, block_tokens, nkv, dh) (+ scale leaves when kv_bits == 8);
    ``table``: (B, n_b) int32 block table, shared by every layer (one
    allocation covers all L layers of a row's block).  Scans layers over
    axis 0 of the params and a layer index; the arena and the table are
    scan-invariant operands that each layer only reads.  The scan
    returns each layer's new token, and one scatter per leaf writes all
    L of them after it, so no op of the step touches more of the arena
    than a gather reads.  Returns (logits, new_pages)."""
    x = common.maybe_dequant(params["embed"])[tokens]
    x = constrain(x, "batch", None, None)
    n_layers = pages["k"].shape[0]

    def body(x, inputs):
        lp, layer = inputs
        h = common.apply_norm(cfg.norm, lp["norm1"], x)
        att, new_tokens = common.decode_attention_paged(
            lp["attn"], cfg, h, pages, layer, table, pos, use_kernel)
        x = x + att
        h = common.apply_norm(cfg.norm, lp["norm2"], x)
        if cfg.is_moe:
            out, _ = common.moe_apply(lp["moe"], cfg, h)
        else:
            out = common.ffn_apply(lp["ffn"], cfg, h)
        return x + out, new_tokens

    x, new_tokens = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    page, off = common.paged_write_slot(table, pos, pages["k"].shape[2])
    new_pages = common.write_paged_tokens(pages, new_tokens, page, off)
    x = common.apply_norm(cfg.norm, params["final_norm"], x)
    logits = _unembed(cfg, params, x)[:, 0]
    return logits, new_pages


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs for the dry-run; no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        n_text = S - (cfg.vlm.n_img_tokens if cfg.family == "vlm" else 0)
        batch = {"tokens": sds((B, n_text), jnp.int32),
                 "labels": sds((B, n_text), jnp.int32)}
        if cfg.family == "vlm":
            batch["patch_embeds"] = sds((B, cfg.vlm.n_img_tokens, cfg.d_model),
                                        _dtype(cfg))
        return batch
    if shape.kind == "prefill":
        n_text = S - (cfg.vlm.n_img_tokens if cfg.family == "vlm" else 0)
        batch = {"tokens": sds((B, n_text), jnp.int32)}
        if cfg.family == "vlm":
            batch["patch_embeds"] = sds((B, cfg.vlm.n_img_tokens, cfg.d_model),
                                        _dtype(cfg))
        return batch
    # decode: one new token against a cache of length S
    return {"tokens": sds((B, 1), jnp.int32)}


# ---------------------------------------------------------------------------
# Model factory
# ---------------------------------------------------------------------------


def make_model(cfg: ModelConfig) -> Model:
    return Model(
        cfg=cfg,
        init=functools.partial(init_params, cfg),
        forward=lambda p, b: forward(cfg, p, b)[0],
        loss_fn=functools.partial(loss_fn, cfg),
        prefill=functools.partial(prefill, cfg),
        decode_step=functools.partial(decode_step, cfg),
        decode_step_paged=functools.partial(decode_step_paged, cfg),
        init_cache=functools.partial(init_cache, cfg),
        input_specs=functools.partial(input_specs, cfg),
    )
