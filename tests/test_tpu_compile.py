"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered with ``interpret=False`` against
pre-padded shapes placed on one chip of a described (not attached)
``v5e:2x2`` topology and compiled by the TPU compiler installed with
JAX.  This catches what interpret mode cannot: block shapes Mosaic
refuses, ops it cannot legalize, and kernels that overflow VMEM.

Widths: bloom-3b projections (d_model 2560, d_ff 10240) for the matmul
tiers, bloom-3b attention (32 heads, d_head 80 padded to 128) for
flash-decode, one bloom-7b1 attention layer (d_model 4096, 32 heads,
d_head 128) for the fused tier; a cohort of 8 rows, 512 + 128 cache
slots, 16-token pages.  One whole program besides: the paged decode
segment of bloom-3b at W8A16, compiled to check what it does to the KV
arena.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_decode as fd
from repro.kernels import quant_matmul as qm

B, NH, NKV, DH, W, BT = 8, 32, 32, 128, 1024, 16
P, NB = 2 + B * (512 + 128) // BT, (512 + 128) // BT
D3, F3, D7 = 2560, 10240, 4096
bf16, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a described chip's entries cannot be read back) and the
    TPU compiler's logs disabled."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("M", [8, 4096])
@pytest.mark.parametrize("tier", ["w8a16", "w8a8", "w4a16"])
def test_quant_matmul_compiles(one_chip, tier, M):
    """Decode (M = the cohort's rows, padded to the int8 min tile for
    W8A8) and prefill (M = 8 x 512 prompt tokens) at the FFN up
    projection."""
    bm = min(128, max(32 if tier == "w8a8" else 8, M))
    M = max(M, bm)
    if tier == "w8a8":
        _compile(lambda x, xs, q, s: qm.quant_matmul(
            x, q, s, 8, x_scale=xs, out_dtype=bf16, block_m=bm),
            one_chip, ((M, D3), i8), ((M, 1), f32), ((D3, F3), i8),
            ((F3,), f32))
    else:
        bits = 4 if tier == "w4a16" else 8
        _compile(lambda x, q, s: qm.quant_matmul(x, q, s, bits, block_m=bm),
                 one_chip, ((M, D3), bf16), ((D3 * bits // 8, F3), i8),
                 ((F3,), f32))


def test_flash_decode_compiles(one_chip):
    _compile(lambda q, k, v, nv: fd.flash_decode(q, k, v, nv),
             one_chip, ((B, NH, DH), bf16), ((B, W, NKV, DH), bf16),
             ((B, W, NKV, DH), bf16), ((B,), i32))


def test_flash_decode_paged_compiles(one_chip):
    _compile(lambda q, k, v, t, nv: fd.flash_decode_paged(q, k, v, t, nv),
             one_chip, ((B, NH, DH), bf16), ((P, BT, NKV, DH), bf16),
             ((P, BT, NKV, DH), bf16), ((B, NB), i32), ((B,), i32))


def _fused_weights():
    return [((B, D7), bf16), ((D7, NH * DH), i8), ((1, NH * DH), f32),
            ((D7, NKV * DH), i8), ((1, NKV * DH), f32),
            ((D7, NKV * DH), i8), ((1, NKV * DH), f32),
            ((NH * DH, D7), i8), ((1, D7), f32)]


@pytest.mark.parametrize("a8", [False, True])
def test_flash_decode_fused_compiles(one_chip, a8):
    _compile(lambda *a: fd.flash_decode_fused(*a, a8=a8), one_chip,
             *_fused_weights(), ((B, W, NKV, DH), bf16),
             ((B, W, NKV, DH), bf16), ((B,), i32), ((B,), i32),
             ((1, DH // 2), f32), ((1, DH // 2), f32))


@pytest.mark.parametrize("a8", [False, True])
def test_flash_decode_fused_paged_compiles(one_chip, a8):
    _compile(lambda *a: fd.flash_decode_fused_paged(*a, a8=a8), one_chip,
             *_fused_weights(), ((P, BT, NKV, DH), bf16),
             ((P, BT, NKV, DH), bf16), ((B, NB), i32), ((B,), i32),
             ((B,), i32), ((1, DH // 2), f32), ((1, DH // 2), f32))


def _shapes(text):
    """{instruction name: (opcode, result dims, operand names)} of every
    array-valued instruction in compiled HLO text."""
    out = {}
    inst = r"%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(([^)]*)\)"
    for m in re.finditer(inst, text):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out[m.group(1)] = (m.group(3), dims,
                           re.findall(r"%([\w.\-]+)", m.group(4)))
    return out


def test_paged_decode_segment_writes_tokens_not_the_arena(one_chip,
                                                          monkeypatch):
    """The paged decode segment of bloom-3b at W8A16 (30 layers, 8 rows
    of 512 + 512 positions in 514 pages of 16 tokens, 32 heads of 80 in
    128-lane page tails) holds no op that copies, broadcasts, slices or
    rewrites the arena or a layer of it: every instruction with a
    dimension of 514 pages is a pass-through or an in-place
    dynamic-update-slice of one token window (one page, one offset)."""
    from repro.config import get_arch
    from repro.kernels import ops
    from repro.models.api import build_model
    from repro.quant.ptq import MATMUL_KEYS, quantize_tree
    from repro.serving.engine import ServingEngine
    monkeypatch.setattr(ops, "INTERPRET", False)
    cfg = get_arch("bloom-3b")
    s_max = n_max = 512
    nb = (s_max + n_max) // BT
    n_pages = 2 + B * nb
    init = build_model(cfg).init
    eng = ServingEngine(cfg, params=jax.eval_shape(init, jax.random.key(0)),
                        batch_capacity=B, s_max=s_max, n_max=n_max)
    w8 = jax.eval_shape(     # int8 block matrices, bf16 tied embedding
        lambda k: quantize_tree(init(k), 8, keys=MATMUL_KEYS - {"embed"}),
        jax.random.key(0))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    arena = (cfg.n_layers, n_pages, BT, cfg.n_kv_heads, 128)
    args = (jax.tree.map(lambda a: sds(a.shape, a.dtype), w8),
            {"k": sds(arena, bf16), "v": sds(arena, bf16)},
            sds((B, nb), i32), sds((B,), i32), sds((B, n_max), i32),
            sds((B,), i32), sds((B,), jnp.bool_), sds((B,), i32),
            sds((), i32), sds((), i32), sds((B, n_max), i32),
            sds((B,), i32))
    text = jax.jit(eng._decode_chunk_paged_fn,
                   donate_argnums=(1, 3, 4, 5, 6)).lower(*args) \
        .compile().as_text()
    assert "tpu_custom_call" in text            # the W8A16 matmuls
    shapes = _shapes(text)
    writes = 0
    for name, (op, dims, operands) in shapes.items():
        if n_pages not in dims or op in ("parameter", "get-tuple-element",
                                         "bitcast"):
            continue
        assert op == "dynamic-update-slice", (name, op, dims)
        window = shapes[operands[1]][1]
        assert dims == arena and window[1:3] == (1, 1), (name, window)
        writes += 1
    assert writes == 2                          # one per leaf, k and v
