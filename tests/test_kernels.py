"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode on CPU; TPU is the deploy target)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.quant.ptq import quantize

QMM_SHAPES = [(128, 256, 128), (64, 512, 384), (4, 300, 200),
              (1, 128, 128), (130, 260, 76)]


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("shape", QMM_SHAPES)
def test_quant_matmul_vs_ref(bits, shape):
    M, K, N = shape
    x = jax.random.normal(jax.random.key(1), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (K, N), jnp.float32)
    t = quantize(w, bits)
    got = ops.quant_matmul(x, t.q, t.scale.reshape(-1), bits)
    want = ref.quant_matmul_ref(x, t.q, t.scale.reshape(-1), bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_dtypes(dtype):
    x = jax.random.normal(jax.random.key(1), (32, 256), dtype)
    w = jax.random.normal(jax.random.key(2), (256, 128), jnp.float32)
    t = quantize(w, 8)
    got = ops.quant_matmul(x, t.q, t.scale.reshape(-1), 8)
    assert got.dtype == dtype
    want = ref.quant_matmul_ref(x, t.q, t.scale.reshape(-1), 8)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2)


def test_quant_matmul_batched_lead():
    x = jax.random.normal(jax.random.key(1), (2, 8, 256))
    w = jax.random.normal(jax.random.key(2), (256, 64))
    t = quantize(w, 8)
    got = ops.quant_matmul(x, t.q, t.scale.reshape(-1), 8)
    assert got.shape == (2, 8, 64)


FD_CASES = [
    # (B, nh, nkv, dh, W, nv)
    (2, 8, 2, 64, 1024, 700),
    (1, 4, 4, 128, 512, 512),
    (3, 16, 8, 80, 256, 1),
    (2, 12, 4, 96, 384, 200),
    (1, 8, 1, 128, 2048, 1024),
]


@pytest.mark.parametrize("case", FD_CASES)
def test_flash_decode_vs_ref(case):
    B, nh, nkv, dh, W, nv = case
    q = jax.random.normal(jax.random.key(1), (B, nh, dh), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, W, nkv, dh), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, W, nkv, dh), jnp.float32)
    got = ops.flash_decode(q, k, v, nv)
    want = ref.flash_decode_ref(q, k, v, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_per_batch_validity():
    q = jax.random.normal(jax.random.key(1), (3, 8, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (3, 512, 4, 64), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (3, 512, 4, 64), jnp.float32)
    nv = jnp.array([100, 512, 3])
    got = ops.flash_decode(q, k, v, nv)
    want = ref.flash_decode_ref(q, k, v, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_bf16():
    q = jax.random.normal(jax.random.key(1), (2, 8, 128), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(2), (2, 256, 2, 128), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(3), (2, 256, 2, 128), jnp.bfloat16)
    got = ops.flash_decode(q, k, v, 200)
    want = ref.flash_decode_ref(q, k, v, 200)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_decode_matches_xla_gqa_attention():
    """The kernel must agree with the model's own decode attention math."""
    from repro.models import common
    B, nh, nkv, dh, W = 2, 8, 4, 64, 256
    q = jax.random.normal(jax.random.key(1), (B, 1, nh, dh))
    k = jax.random.normal(jax.random.key(2), (B, W, nkv, dh))
    v = jax.random.normal(jax.random.key(3), (B, W, nkv, dh))
    n_valid = 100
    mask = (jnp.arange(W) < n_valid)[None, None, None, None, :]
    want = common.gqa_attention(q, k, v, mask)[:, 0]
    got = ops.flash_decode(q[:, 0], k, v, n_valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_interpret_mode_only_on_cpu():
    """Kernels interpret on the CPU, compile on the TPU, and refuse any
    other backend instead of interpreting there in silence."""
    assert ops.interpret_mode("cpu") is True
    assert ops.interpret_mode("tpu") is False
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode("gpu")
