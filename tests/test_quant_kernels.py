"""Quantized kernel tiers: W8A8 int8-accumulation, int4 unpack identity,
activation-quant round-trip, and the fused quantized flash-decode vs its
unfused composition (contiguous + paged layouts)."""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import common
from repro.quant.ptq import (pack_int4, quantize, quantize_rowwise,
                             unpack_int4)

QMM_SHAPES = [(128, 256, 128), (64, 512, 384), (4, 300, 200),
              (1, 128, 128), (130, 260, 76)]


# ---------------------------------------------------------------------------
# W8A8: int8 x int8 -> int32 accumulation, one rescale at writeout
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", QMM_SHAPES)
def test_w8a8_bitwise_vs_oracle(shape):
    """The blocked int32 accumulation is EXACT integer math, and scales
    are computed identically (reciprocal multiply) in kernel and oracle,
    so kernel == oracle bit for bit — including padding-remainder
    shapes, where stray garbage in the pad region would break this."""
    M, K, N = shape
    x = jax.random.normal(jax.random.key(1), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (K, N), jnp.float32)
    t = quantize(w, 8, act_bits=8)
    got = ops.quant_matmul(x, t.q, t.scale.reshape(-1), 8, act_bits=8)
    want = ref.quant_matmul_a8_ref(x, t.q, t.scale.reshape(-1))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", QMM_SHAPES)
def test_w8a8_analytic_bound_vs_f32(shape):
    """|W8A8 - x @ dequant(w)| is bounded by the activation rounding:
    each row's quantization error is <= sx/2 per element, so the output
    error is <= (sx_i / 2) * sum_k |wdq[k, j]| elementwise."""
    M, K, N = shape
    x = jax.random.normal(jax.random.key(3), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.key(4), (K, N), jnp.float32)
    t = quantize(w, 8, act_bits=8)
    wdq = t.q.astype(jnp.float32) * t.scale.astype(jnp.float32)
    got = np.asarray(ops.quant_matmul(x, t.q, t.scale.reshape(-1), 8,
                                      act_bits=8))
    want = np.asarray(x @ wdq)
    _, sx = quantize_rowwise(x)
    bound = 0.5 * np.asarray(sx) * np.abs(np.asarray(wdq)).sum(0)[None, :]
    assert np.all(np.abs(got - want) <= bound + 1e-5)


def test_w8a8_close_to_w8a16():
    """Same int8 weights consumed by both activation tiers: the a8 path
    only adds the (bounded) dynamic activation rounding."""
    x = jax.random.normal(jax.random.key(5), (64, 256), jnp.float32)
    w = jax.random.normal(jax.random.key(6), (256, 192), jnp.float32)
    t = quantize(w, 8)
    a16 = np.asarray(ops.quant_matmul(x, t.q, t.scale.reshape(-1), 8))
    a8 = np.asarray(ops.quant_matmul(x, t.q, t.scale.reshape(-1), 8,
                                     act_bits=8))
    scale = np.abs(a16).max()
    assert np.abs(a8 - a16).max() <= 0.02 * scale


# ---------------------------------------------------------------------------
# int4 unpack: index-free even/odd reconstruction
# ---------------------------------------------------------------------------


def _unpack_int4_stack(packed):
    """The historical stack+reshape interleave unpack (bitwise oracle for
    the index-free rewrite)."""
    lo = ((packed << 4) >> 4).astype(jnp.int8)
    hi = (packed >> 4).astype(jnp.int8)
    inter = jnp.stack([lo, hi], axis=-2)     # (..., R/2, 2, C) interleave
    shape = list(packed.shape)
    shape[-2] *= 2
    return inter.reshape(shape)


@pytest.mark.parametrize("shape", [(8, 16), (30, 7), (3, 10, 12)])
def test_unpack_int4_bitwise_matches_stack(shape):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.integers(-8, 8, size=shape), jnp.int8)
    packed = pack_int4(q)
    got = np.asarray(unpack_int4(packed))
    want = np.asarray(_unpack_int4_stack(packed))
    assert np.array_equal(got, want)
    # and both invert pack_int4 exactly
    assert np.array_equal(got[..., :shape[-2], :], np.asarray(q))


@pytest.mark.parametrize("shape", [(8, 16), (16, 128), (64, 256)])
def test_unpack_int4_tile_bitwise_matches_ptq(shape):
    """The kernel's int32-shift unpack equals ``ptq.unpack_int4`` bit for
    bit over every nibble value."""
    from repro.kernels.quant_matmul import _unpack_int4_tile
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.integers(-8, 8, size=shape), jnp.int8)
    packed = pack_int4(q)
    got = np.asarray(_unpack_int4_tile(packed))
    assert got.dtype == np.int8
    assert np.array_equal(got, np.asarray(unpack_int4(packed)))
    assert np.array_equal(got, np.asarray(q))


def test_quantize_rowwise_roundtrip():
    """|x - q * s| <= s/2 elementwise (symmetric RTN never clips)."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(17, 33)) * 100.0, jnp.float32)
    q, s = quantize_rowwise(x)
    err = np.abs(np.asarray(x) - np.asarray(q, np.float32) * np.asarray(s))
    assert np.all(err <= 0.5 * np.asarray(s) + 1e-7)
    assert np.all(np.asarray(s) > 0)


def test_quantize_rowwise_roundtrip_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.floats(-1e6, 1e6, allow_nan=False,
                              allow_infinity=False, width=32),
                    min_size=1, max_size=64))
    def prop(vals):
        x = jnp.asarray([vals], jnp.float32)
        q, s = quantize_rowwise(x)
        err = np.abs(np.asarray(x)
                     - np.asarray(q, np.float32) * np.asarray(s))
        assert np.all(err <= 0.5 * np.asarray(s) + 1e-6)

    prop()


# ---------------------------------------------------------------------------
# Fused quantized flash-decode vs unfused composition
# ---------------------------------------------------------------------------

B, D, NH, NKV, DH, W = 3, 64, 4, 2, 32, 16
THETA = 1e4
CFG = SimpleNamespace(d_head=DH, n_heads=NH, n_kv_heads=NKV,
                      rope_theta=THETA, qk_norm=False, kv_bits=0,
                      sliding_window=0)


def _fused_params(act_bits, seed=0):
    rng = np.random.default_rng(seed)

    def qw(shape):
        w = jnp.asarray(rng.normal(size=shape), jnp.float32) * 0.2
        return quantize(w, 8, act_bits=act_bits)

    return {"wq": qw((D, NH * DH)), "wk": qw((D, NKV * DH)),
            "wv": qw((D, NKV * DH)), "wo": qw((NH * DH, D))}, \
        jnp.asarray(rng.normal(size=(B, 1, D)), jnp.float32)


def _tol(act_bits):
    # a16: fused == unfused up to f32 accumulation order.  a8: the fused
    # wo projection quantizes per-head-group attention rows (G*dh) while
    # the unfused path sees the full (nh*dh) row — a different dynamic
    # scale, hence the documented looser bound.
    return 1e-4 if act_bits == 16 else 0.15


@pytest.mark.parametrize("act_bits", [16, 8])
@pytest.mark.parametrize("pos_v", [0, 5, W, W + 7])
def test_fused_decode_matches_unfused(act_bits, pos_v):
    """pos sweep covers: empty cache (the all-masked online-softmax pass
    must wash out), partial fill, the wrap boundary, and eviction."""
    p, x = _fused_params(act_bits)
    rng = np.random.default_rng(10 + pos_v)
    pos = jnp.int32(pos_v)
    valid = (np.arange(W) < min(pos_v, W)).astype(np.float32)
    ck = jnp.asarray(rng.normal(size=(B, W, NKV, DH)) *
                     valid[None, :, None, None], jnp.float32)
    cv = jnp.asarray(rng.normal(size=(B, W, NKV, DH)) *
                     valid[None, :, None, None], jnp.float32)

    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k1, v1 = common.qkv_proj(p, CFG, x, positions, True)
    ck2, cv2 = common.cache_write(ck, cv, k1, v1, pos)
    out = ops.flash_decode(q[:, 0], ck2, cv2, jnp.minimum(pos + 1, W))
    out = common.mm(out.reshape(B, 1, NH * DH), p["wo"])[:, 0]

    o, k1f, v1f = ops.flash_decode_fused(
        x[:, 0], p["wq"], p["wk"], p["wv"], p["wo"], ck, cv, pos,
        rope_theta=THETA)

    np.testing.assert_allclose(np.asarray(k1f), np.asarray(k1[:, 0]),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(v1f), np.asarray(v1[:, 0]),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(out),
                               atol=_tol(act_bits), rtol=0)


@pytest.mark.parametrize("act_bits", [16, 8])
@pytest.mark.parametrize("pos_v", [0, 5, 11])
def test_fused_decode_paged_matches_unfused(act_bits, pos_v):
    bt, n_b, P = 8, 2, 7
    p, x = _fused_params(act_bits, seed=1)
    rng = np.random.default_rng(20 + pos_v)
    pos = jnp.int32(pos_v)
    kp = rng.normal(size=(P, bt, NKV, DH)).astype(np.float32)
    vp = rng.normal(size=(P, bt, NKV, DH)).astype(np.float32)
    tbl = rng.permutation(P)[:B * n_b].reshape(B, n_b)
    for b in range(B):            # zero logical slots >= pos (unwritten)
        for j in range(n_b):
            for t in range(bt):
                if j * bt + t >= pos_v:
                    kp[tbl[b, j], t] = 0
                    vp[tbl[b, j], t] = 0
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    table = jnp.asarray(tbl, jnp.int32)

    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k1, v1 = common.qkv_proj(p, CFG, x, positions, True)
    blk, off = pos_v // bt, pos_v % bt
    pk = kp.at[table[:, blk], off].set(k1[:, 0])
    pv = vp.at[table[:, blk], off].set(v1[:, 0])
    out = ops.flash_decode_paged(q[:, 0], pk, pv, table,
                                 jnp.minimum(pos + 1, bt * n_b))
    out = common.mm(out.reshape(B, 1, NH * DH), p["wo"])[:, 0]

    o, k1f, v1f = ops.flash_decode_fused_paged(
        x[:, 0], p["wq"], p["wk"], p["wv"], p["wo"], kp, vp, table, pos,
        rope_theta=THETA)

    np.testing.assert_allclose(np.asarray(k1f), np.asarray(k1[:, 0]),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(o), np.asarray(out),
                               atol=_tol(act_bits), rtol=0)


def test_decode_attention_fused_route_matches():
    """models.common.decode_attention(use_kernel=True) takes the fused
    path for all-int8 params and must agree with the reference route —
    output AND the caches it writes."""
    p, x = _fused_params(16)
    assert ops.fusable_decode(p, CFG)
    pos = jnp.int32(5)
    ck = jnp.zeros((B, W, NKV, DH), jnp.float32)
    cv = jnp.zeros((B, W, NKV, DH), jnp.float32)
    o_ref, ckr, cvr = common.decode_attention(p, CFG, x, ck, cv, pos,
                                              use_kernel=False)
    o_fus, ckf, cvf = common.decode_attention(p, CFG, x, ck, cv, pos,
                                              use_kernel=True)
    np.testing.assert_allclose(np.asarray(o_fus), np.asarray(o_ref),
                               atol=1e-4, rtol=0)
    np.testing.assert_allclose(np.asarray(ckf), np.asarray(ckr),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(cvf), np.asarray(cvr),
                               atol=1e-5, rtol=0)


def test_fusable_decode_gating():
    p16, _ = _fused_params(16)
    assert ops.fusable_decode(p16, CFG)
    # fp params (no QTensors) must not take the quantized fused path
    fp = {k: jnp.zeros((2, 2)) for k in ("wq", "wk", "wv", "wo")}
    assert not ops.fusable_decode(fp, CFG)
    cfg_qk = SimpleNamespace(**{**CFG.__dict__, "qk_norm": True})
    assert not ops.fusable_decode(p16, cfg_qk)
