#!/usr/bin/env python3
"""Bring-up smoke of the serving path on one TPU chip.

Checks every Pallas kernel on the chip against its oracle, then serves a
frozen stream of requests through the normal path (``ContinuousRuntime``
-> ``EngineContinuousExecutor`` -> ``ServingEngine`` -> ``KVArena``) at
the full published width of bloom-3b, with random weights drawn from
``--seed``: once at full precision, once at W8A16, and once at W8A16
with the decode-attention kernels (``use_kernel=True``).

Prints one JSON object per phase, then, as the last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or outside a checkout of this repository, it exits
non-zero and prints no result.  A bring-up check, not a benchmark.

    python chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# One edge node's cohort: 8 rows, prompts padded to 512, 128 output tokens.
BATCH, S_MAX, N_MAX, K_SEG, BLOCK_TOKENS = 8, 512, 128, 16, 16
N_EPOCHS, RATE = 3, 4.0        # ~16 Poisson arrivals over two epochs

# Tolerances, as max |got - want| / max |want| against an f32 oracle at
# "highest" matmul precision.  Kernel outputs are bf16 (2^-8 relative
# rounding); the W8A8 fused tier also quantizes its activations per row
# (1/127 relative per element), and the first-step logits cross 30
# layers of bf16 residuals.
KERNEL_TOL = 2e-2
FUSED_A8_TOL = 1e-1
LOGITS_TOL = 5e-2


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _rel_err(got, want):
    """max |got - want| / max |want|, on the device; inf if ``got`` has a
    non-finite value."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    err = jnp.max(jnp.abs(got - want)) \
        / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
    return jnp.where(jnp.all(jnp.isfinite(got)), err, jnp.inf)


def _oracle(fn, *args):
    """``fn`` traced at "highest" matmul precision (f32 on the MXU)."""
    with jax.default_matmul_precision("highest"):
        return fn(*args)


class Phase:
    """Wall and compile seconds of one phase (compile time from JAX's
    tracing, lowering and backend-compile duration events)."""
    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
    _active = None

    @classmethod
    def _listen(cls, event, duration, **_):
        if cls._active is not None and event in cls._EVENTS:
            cls._active.compile_s += duration

    def __init__(self, name: str):
        self.name, self.compile_s = name, 0.0

    def __enter__(self):
        Phase._active = self
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self.t0
        Phase._active = None

    def record(self, **fields) -> dict:
        stats = jax.devices()[0].memory_stats() or {}
        return {"phase": self.name, "compile_s": self.compile_s,
                "wall_s": self.wall_s,
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                **fields}


# ---------------------------------------------------------------------------
# Kernel phase: each Pallas kernel on the device against its oracle
# ---------------------------------------------------------------------------


def check_quant_matmul(shapes, seed: int = 0) -> dict:
    """W8A16 / W8A8 / W4A16 through ``ops.quant_matmul`` at each (M, K, N)
    against ``ref.quant_matmul_ref`` / ``ref.quant_matmul_a8_ref``."""
    from repro.kernels import ops, ref
    from repro.quant.ptq import quantize
    tiers = (("w8a16", 8, 16), ("w8a8", 8, 8), ("w4a16", 4, 16))

    @functools.partial(jax.jit, static_argnums=(1,))
    def errors(key, shape):
        M, K, N = shape
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (M, K), jnp.bfloat16)
        w = jax.random.normal(kw, (K, N), jnp.float32) / np.sqrt(K)
        out = []
        for _, bits, act in tiers:
            t = quantize(w, bits, act_bits=act)
            s = t.scale.reshape(-1)
            got = ops.quant_matmul(x, t.q, s, bits, act_bits=act)
            xf = x.astype(jnp.float32)
            want = _oracle(ref.quant_matmul_a8_ref, xf, t.q, s) if act == 8 \
                else _oracle(ref.quant_matmul_ref, xf, t.q, s, bits)
            out.append(_rel_err(got, want))
        return out

    errs = {}
    for i, shape in enumerate(shapes):
        got = jax.device_get(errors(jax.random.key(seed + i), tuple(shape)))
        for (tier, _, _), e in zip(tiers, got):
            name = "quant_matmul_{}_{}x{}x{}".format(tier, *shape)
            errs[name] = (float(e), KERNEL_TOL)
    return errs


def check_flash_decode(B, nh, nkv, dh, W, block_tokens, seed: int = 0
                       ) -> dict:
    """``ops.flash_decode`` on a slot cache and ``ops.flash_decode_paged``
    on a scrambled page arena, both against ``ref.flash_decode_ref``."""
    from repro.kernels import ops, ref
    n_b = W // block_tokens

    @jax.jit
    def errors(key):
        ks = jax.random.split(key, 5)
        q = jax.random.normal(ks[0], (B, nh, dh), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, W, nkv, dh), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, W, nkv, dh), jnp.bfloat16)
        nv = jax.random.randint(ks[3], (B,), 1, W + 1)
        f32 = jnp.float32
        want = _oracle(ref.flash_decode_ref, q.astype(f32), k.astype(f32),
                       v.astype(f32), nv)
        table = jax.random.permutation(ks[4], B * n_b).reshape(B, n_b) + 2

        def paged(c):
            return jnp.zeros((B * n_b + 2, block_tokens, nkv, dh),
                             c.dtype).at[table.reshape(-1)].set(
                c.reshape(B * n_b, block_tokens, nkv, dh))

        return (_rel_err(ops.flash_decode(q, k, v, nv), want),
                _rel_err(ops.flash_decode_paged(q, paged(k), paged(v),
                                                table, nv), want))

    e, e_paged = jax.device_get(errors(jax.random.key(seed)))
    return {"flash_decode": (float(e), KERNEL_TOL),
            "flash_decode_paged": (float(e_paged), KERNEL_TOL)}


def check_fused_decode(cfg, B, W, block_tokens, seed: int = 0) -> dict:
    """``ops.flash_decode_fused[_paged]`` at one attention layer of
    ``cfg`` (int8 projections, W8A16 and W8A8) against the XLA
    ``common.decode_attention[_paged]`` route on the dequantized
    weights."""
    from repro.kernels import ops
    from repro.models import common
    from repro.quant.ptq import dequantize, quantize
    D, nh, nkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    n_b = W // block_tokens
    shapes = {"wq": (D, nh * dh), "wk": (D, nkv * dh), "wv": (D, nkv * dh),
              "wo": (nh * dh, D)}

    @functools.partial(jax.jit, static_argnums=(1,))
    def errors(key, act):
        ks = jax.random.split(key, 8)
        w = {n: jax.random.normal(kk, s, jnp.float32) / np.sqrt(s[0])
             for kk, (n, s) in zip(ks, shapes.items())}
        x = jax.random.normal(ks[4], (B, D), jnp.bfloat16)
        ck = jax.random.normal(ks[5], (B, W, nkv, dh), jnp.bfloat16)
        cv = jax.random.normal(ks[6], (B, W, nkv, dh), jnp.bfloat16)
        pos = jnp.int32(W // 2)
        table = jax.random.permutation(ks[7], B * n_b).reshape(B, n_b) + 2
        pages = {n: jnp.zeros((B * n_b + 2, block_tokens, nkv, dh), c.dtype)
                 .at[table.reshape(-1)].set(
                     c.reshape(B * n_b, block_tokens, nkv, dh))
                 for n, c in (("k", ck), ("v", cv))}
        p = {n: quantize(a, 8, act_bits=act) for n, a in w.items()}
        p_deq = {n: dequantize(t) for n, t in p.items()}
        want, _, _ = _oracle(common.decode_attention, p_deq, cfg,
                             x[:, None], ck, cv, pos)
        want_p, _ = _oracle(common.decode_attention_paged, p_deq, cfg,
                            x[:, None], {n: a[None] for n, a in
                                         pages.items()}, 0, table, pos)
        o, _, _ = ops.flash_decode_fused(x, p["wq"], p["wk"], p["wv"],
                                         p["wo"], ck, cv, pos,
                                         rope_theta=cfg.rope_theta)
        o_p, _, _ = ops.flash_decode_fused_paged(
            x, p["wq"], p["wk"], p["wv"], p["wo"], pages["k"], pages["v"],
            table, pos, rope_theta=cfg.rope_theta)
        return _rel_err(o, want[:, 0]), _rel_err(o_p, want_p[:, 0])

    errs = {}
    for act in (16, 8):
        tol = KERNEL_TOL if act == 16 else FUSED_A8_TOL
        e, e_paged = jax.device_get(errors(jax.random.key(seed), act))
        errs[f"flash_decode_fused_a{act}"] = (float(e), tol)
        errs[f"flash_decode_fused_paged_a{act}"] = (float(e_paged), tol)
    return errs


def kernel_phase(qmm_shapes, attn_cfg, fused_cfg, *, batch=BATCH,
                 cache_len=S_MAX + N_MAX, block_tokens=BLOCK_TOKENS,
                 seed: int = 0) -> dict:
    """All kernel checks; ``{name: (max relative error, tolerance)}``."""
    errs = check_quant_matmul(qmm_shapes, seed)
    errs.update(check_flash_decode(batch, attn_cfg.n_heads,
                                   attn_cfg.n_kv_heads, attn_cfg.d_head,
                                   cache_len, block_tokens, seed))
    errs.update(check_fused_decode(fused_cfg, batch, cache_len,
                                   block_tokens, seed))
    return errs


# ---------------------------------------------------------------------------
# Serving phase: the normal path, end to end
# ---------------------------------------------------------------------------


def smoke_traffic(seed: int = 0, rate: float = RATE,
                  n_epochs: int = N_EPOCHS):
    """The frozen arrival stream every serving run replays."""
    from repro.core.environment import paper_env
    from repro.core.request import ReplayGenerator
    T_E = paper_env("bloom-3b").T_E
    return ReplayGenerator.poisson(rate, (n_epochs - 1) * T_E, seed=seed)


def serve_phase(cfg, params, bits, traffic, *, use_kernel: bool = False,
                batch=BATCH, s_max=S_MAX, n_max=N_MAX, k=K_SEG,
                n_epochs=N_EPOCHS, seed: int = 0):
    """Serve ``traffic`` once through ``ContinuousRuntime`` at weight
    precision ``bits`` over a paged arena.  ``params`` is the fp tree, or
    the tree already quantized at ``bits`` (the engine then holds no fp
    master).  Returns (summary dict, engine, arena, generated tokens by
    request id)."""
    from repro.core.environment import paper_env
    from repro.core.request import ReplayGenerator
    from repro.serving.engine import ServingEngine
    from repro.serving.kv_arena import KVArena
    from repro.serving.runtime import (ContinuousRuntime,
                                       EngineContinuousExecutor)
    engine = ServingEngine(cfg, params=params, batch_capacity=batch,
                           s_max=s_max, n_max=n_max, quant_bits=bits,
                           use_kernel=use_kernel)
    arena = KVArena.for_engines(engine, block_tokens=BLOCK_TOKENS)
    ex = EngineContinuousExecutor(engine, seed=seed, quant_bits=bits,
                                  arena=arena, collect_tokens=True)
    env = paper_env(cfg.arch_id, "W8A16")
    m = ContinuousRuntime(env, "dftsp", ex, k=k).run(
        gen=ReplayGenerator(traffic.requests), n_epochs=n_epochs,
        seed=seed, warmup_epochs=0)
    queued = len(m.final_queue_rids) + len(m.in_flight_rids)
    assert m.arrived == m.served + m.dropped + m.shed + queued, \
        (m.arrived, m.served, m.dropped, m.shed, queued)
    assert m.served >= 1 and m.generated_tokens >= 1, \
        (m.served, m.generated_tokens)
    assert arena.free_pages == arena.total_pages, "leaked KV pages"
    summary = {"requests_sent": m.arrived, "served": m.served,
               "dropped": m.dropped, "shed": m.shed, "queued": queued,
               "tokens": m.generated_tokens}
    return summary, engine, arena, dict(ex.outputs)


def served_tree_report(engine, arena, bits) -> dict:
    """QTensor leaves of the served tree, and whether the lowered paged
    decode segment calls a Pallas kernel (``tpu_custom_call``)."""
    from repro.quant.ptq import QTensor
    params = engine.params_for(bits)
    leaves = jax.tree.leaves(params, is_leaf=lambda x: isinstance(x, QTensor))
    B, n = engine.batch_capacity, engine.n_max
    nb = engine.cache_len // arena.block_tokens

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    text = engine._decode_chunk_paged.lower(
        params, arena.buffers(), i32(B, nb), i32(B), i32(B, n), i32(B),
        jax.ShapeDtypeStruct((B,), jnp.bool_), i32(B), i32(), i32(),
        i32(B, n), i32(B)).as_text()
    return {"qtensor_leaves": sum(isinstance(x, QTensor) for x in leaves),
            "decode_calls_kernel": "tpu_custom_call" in text}


def kernel_vs_xla_logits(engine, arena, bits, seed: int = 0) -> dict:
    """First decode step of one cohort through the paged decode step with
    and without ``use_kernel``, on the same weights and pages.  Both
    steps attend over the pages plus the same token and write it at the
    same slot, so the second sees what the first saw; the pages are
    donated, as in serving."""
    rng = np.random.default_rng(seed)
    B = engine.batch_capacity
    prompts = [rng.integers(1, engine.cfg.vocab, size=engine.s_max).tolist()
               for _ in range(B)]
    state = engine.start_chunked(prompts, [engine.n_max] * B,
                                 quant_bits=bits, arena=arena)
    params = engine.params_for(bits)
    step = jax.jit(engine.model.decode_step_paged,
                   static_argnames=("use_kernel",), donate_argnums=(1,))
    out = {}
    for flag in (False, True):
        logits, pages = step(params, arena.buffers(), state.table.device,
                             state.cur[:, None], engine.s_max,
                             use_kernel=flag)
        arena.set_buffers(pages)
        out[flag] = logits[:, :engine.cfg.vocab]
    engine.release_all(state)
    agree = jnp.mean(jnp.argmax(out[True], -1) == jnp.argmax(out[False], -1))
    return {"first_step_logits_err": float(_rel_err(out[True], out[False])),
            "first_step_greedy_agree": float(agree)}


def token_agreement(a: dict, b: dict) -> float:
    """Share of generated positions at which two runs' greedy tokens agree
    (request ids and positions present in both)."""
    same = total = 0
    for rid in set(a) & set(b):
        n = min(len(a[rid]), len(b[rid]))
        same += int(np.sum(a[rid][:n] == b[rid][:n]))
        total += n
    return same / total if total else float("nan")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, inputs and traffic")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found no TPU (platform "
              f"{device['platform']!r}, kind {device['kind']!r}, "
              f"{device['count']} device(s))", file=sys.stderr)
        return 1
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    _emit({"phase": "device", **device, "compile_cache": cache})

    from repro.config import get_arch
    from repro.kernels import ops
    from repro.models.api import build_model
    from repro.quant.ptq import quantize_tree
    assert not ops.INTERPRET, "Pallas kernels in interpret mode on a TPU"
    jax.monitoring.register_event_duration_secs_listener(Phase._listen)
    cfg = get_arch("bloom-3b")

    with Phase("kernels") as ph:
        D, F = cfg.d_model, cfg.d_ff
        shapes = [(8, D, F), (8, F, D), (BATCH * S_MAX, D, F)]
        errs = kernel_phase(shapes, cfg, get_arch("bloom-7b1"),
                            seed=args.seed)
    _emit(ph.record(max_rel_err={n: e for n, (e, _) in errs.items()},
                    tol={n: t for n, (_, t) in errs.items()}))
    bad = {n: e for n, (e, t) in errs.items() if not e <= t}
    assert not bad, f"kernels above tolerance: {bad}"

    params = jax.jit(build_model(cfg).init)(jax.random.key(args.seed))
    traffic = smoke_traffic(args.seed)
    outputs = {}
    for name, bits, use_kernel in (("serve_fp", 0, False),
                                   ("serve_w8a16", 8, False),
                                   ("serve_w8a16_kernel", 8, True)):
        with Phase(name) as ph:
            summary, engine, arena, outputs[name] = serve_phase(
                cfg, params, bits, traffic, use_kernel=use_kernel,
                seed=args.seed)
            extra = served_tree_report(engine, arena, bits) if bits else {}
            if use_kernel:
                extra.update(kernel_vs_xla_logits(engine, arena, bits,
                                                  args.seed))
                extra["greedy_token_agree_vs_xla"] = token_agreement(
                    outputs[name], outputs["serve_w8a16"])
        _emit(ph.record(bits=bits, use_kernel=use_kernel, **summary,
                        **extra))
        if bits:
            assert extra["qtensor_leaves"] > 0, "served tree not quantized"
            assert extra["decode_calls_kernel"], "decode has no Pallas call"
        if use_kernel:
            assert extra["first_step_logits_err"] <= LOGITS_TOL, extra
        del engine, arena
        if name == "serve_fp":
            # both W8A16 runs share one int8 tree, and no fp master: the
            # fp weights (6 GB) would not fit beside it, the KV arena
            # and the decode segment's temporaries on a 16 GB chip
            params = quantize_tree(params, 8)
        gc.collect()

    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
