"""Batched-inference engine: executes scheduled batches on the real JAX model.

This is the data plane behind the paper's scheduler (the control plane).
A scheduled batch of prompts is padded to the epoch's s' (exactly the
paper's 'extend all prompts to the maximum length' assumption), prefilled
in one pass, then decoded by a single **device-resident**
``jax.lax.while_loop``: greedy sampling, EOS detection and per-request
output caps are all ``jnp`` ops inside one compiled program, which exits
early once every row is done.  The host never sees a token until the
whole batch finishes — per ``generate`` call there is exactly ONE
host→device transfer (the padded prompts + caps, a single
``jax.device_put``) and ONE device→host transfer (the token buffer +
lengths, a single ``jax.device_get``).  The KV cache produced by prefill
is donated into the decode-loop executable (``donate_argnums``, on
backends that support donation) so the loop carries it in place instead
of copying it at entry.  The historical token-by-token Python loop — one
blocking ``argmax`` transfer per token — survives only as
``generate_reference``, the interpret-style oracle the equivalence tests
compare against.

Static shapes: (batch_capacity, s') for prefill and a KV cache capacity of
s' + n_max — one compiled executable serves every epoch (TPU-friendly, and
why the paper's padded cost model maps 1:1 onto this engine).

The fused loop also exists in RESUMABLE form for continuous batching:
``start_chunked`` prefills a cohort into a device-resident ``DecodeState``,
``generate_chunked(state, k)`` advances it by at most k tokens per call
(one jitted while-loop segment, no host transfer), and ``refill_chunked``
prefills new prompts into slots freed by finished rows of the LIVE cohort
— splicing their cache rows in without touching still-decoding rows.
Driven to completion, chunked decode is bit-identical to ``generate`` for
every chunk size (the equivalence suite in
tests/test_continuous_engine.py).

Weights can be served quantized: ``quant_bits`` picks the DEFAULT
precision, and a per-call ``generate(..., quant_bits=...)`` override lets
the scheduler serve each epoch at the method it decided.  Each requested
bit-width is quantized once from the full-precision weights and kept in a
small multi-precision cache (``params_for``), so swapping precision per
epoch costs a dict lookup.  A precision is an int (weight bits) or a
``(weight_bits, act_bits)`` pair — W8A8 routes the dense matmuls through
the int8-accumulation kernel tier on TPU.  On the CPU backend every
family dequantizes at load (see ``params_for`` / DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, get_arch
from repro.core.metrics import span
from repro.kernels.ops import INTERPRET as _INTERPRET
from repro.models.api import Model, build_model
from repro.quant.ptq import QTensor, dequantize_tree, quantize_tree
from repro.serving.kv_arena import (TRASH_PAGE, ZERO_PAGE, BlockTable,
                                    KVArena)


@dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, n_max) generated ids (post-prompt)
    lengths: np.ndarray         # (B,) emitted length per request
    batch: int


@dataclass
class DecodeState:
    """Device-resident, re-entrant decode state of one batch cohort.

    Produced by ``start_chunked`` and advanced by ``generate_chunked``;
    everything except ``bits``/``caps_host`` lives on the device, so
    re-entering costs no transfer.  A state passed to ``generate_chunked``
    or ``refill_chunked`` is CONSUMED (its buffers may be donated into the
    compiled segment) — always continue from the returned state.

    ``t`` is the cohort's global decode step: the shared KV-cache write
    position is ``s_max + t``, bounded by ``n_max`` because every row's
    cap (including refills, clamped to the remaining headroom) fits inside
    the cache capacity ``s_max + n_max``.  Rows track their own emission
    via ``lengths``, so rows admitted mid-cohort emit into their row of
    ``out`` from 0 regardless of ``t``.
    """
    cache: Any                  # KV / recurrent cache, full batch capacity
    cur: jax.Array              # (B,) next token to emit per row
    out: jax.Array              # (B, n_max) emitted tokens per row
    lengths: jax.Array          # (B,) emitted count per row
    done: jax.Array             # (B,) bool, EOS seen
    caps: jax.Array             # (B,) per-row output cap (0 = empty slot)
    t: jax.Array                # scalar i32, cohort decode step
    bits: Any = 0               # precision spec (int or (w, a) pair)
    caps_host: np.ndarray = None  # host mirror of caps (no sync needed)
    forced: jax.Array = None    # (B, n_max) forced-replay tokens: a row
                                # emits forced[i, lengths[i]] instead of
                                # its argmax while lengths[i] < n_forced[i]
                                # — the preemption-resume mechanism that
                                # keeps an already-delivered prefix exact
                                # (DESIGN.md §2.4); all-zero outside resume
    n_forced: jax.Array = None  # (B,) forced-prefix length per row

    @property
    def batch_capacity(self) -> int:
        return int(self.caps_host.shape[0])


@dataclass
class PagedDecodeState:
    """Arena-backed sibling of :class:`DecodeState` (DESIGN.md §2.3).

    The cohort's KV lives in its node-wide :class:`KVArena` — the state
    holds no cache slab, only the cohort's :class:`BlockTable` and the
    same per-row emission fields as ``DecodeState`` (so ``poll_chunked``
    / ``exhausted`` work unchanged).  Rows lease pages from the arena at
    admission and release them through ``ServingEngine.release_slots``
    the moment they complete — which is what makes freed KV from any
    cohort immediately reusable by any other cohort on the node."""
    arena: KVArena
    table: BlockTable
    cur: jax.Array              # (B,) next token to emit per row
    out: jax.Array              # (B, n_max) emitted tokens per row
    lengths: jax.Array          # (B,) emitted count per row
    done: jax.Array             # (B,) bool, EOS seen
    caps: jax.Array             # (B,) per-row output cap (0 = empty slot)
    t: jax.Array                # scalar i32, cohort decode step
    bits: Any = 0               # precision spec (int or (w, a) pair)
    caps_host: np.ndarray = None  # host mirror of caps (no sync needed)
    forced: jax.Array = None    # (B, n_max) forced-replay tokens (see
                                # DecodeState.forced)
    n_forced: jax.Array = None  # (B,) forced-prefix length per row
    # cap-aware incremental leasing (DESIGN.md §2.3): per row, one past
    # the highest block currently leased and one past the last block its
    # cap ``t0 + n`` can ever need.  Blocks in [lease_end, lease_last)
    # are TRASH in the table until a segment-boundary top-up
    # (``_extend_leases``) leases them — never mid-segment.
    lease_end: np.ndarray = None   # (B,) next block index to lease
    lease_last: np.ndarray = None  # (B,) one past last block of the cap
    t_host: int = 0             # host upper bound on ``t`` (a segment may
                                # exit early; the bound only ever
                                # OVER-covers, inside the reservation)

    @property
    def batch_capacity(self) -> int:
        return int(self.caps_host.shape[0])


def tiny_engine(arch_id: str, **engine_kw) -> "ServingEngine":
    """A CPU-sized reduced engine for ``arch_id`` (1 layer, d_model 64,
    vocab 256) — the ONE copy of the reduced-model shape the multi-engine
    benchmarks, examples and tests build their "identical reduced
    engines on both protocols" premise on.  ``engine_kw`` passes through
    to ``ServingEngine`` (``params=``, ``batch_capacity=``, ...)."""
    cfg = get_arch(arch_id).scaled(n_layers=1, d_model=64, n_heads=2,
                                   n_kv_heads=2, d_ff=128, vocab=256)
    return ServingEngine(cfg, **engine_kw)


class ServingEngine:
    """Fixed-shape batched prefill + fused-decode executor for one model."""

    def __init__(self, cfg: ModelConfig, params: Any = None,
                 batch_capacity: int = 8, s_max: int = 512,
                 n_max: int = 128, quant_bits: int = 0,
                 eos_id: int = 0, seed: int = 0,
                 use_kernel: bool = False):
        self.cfg = cfg
        self.model: Model = build_model(cfg)
        self.batch_capacity = batch_capacity
        self.s_max = s_max
        self.n_max = n_max
        self.eos_id = eos_id
        # route decode attention through the Pallas kernel tiers
        # (flash_decode / flash_decode_fused when the served tree is
        # fusable) instead of the XLA gather path; only the transformer
        # families' decode steps accept the flag
        if use_kernel and cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"use_kernel=True needs a transformer-family model "
                f"(dense/moe/vlm), got family {cfg.family!r}")
        self.use_kernel = bool(use_kernel)
        self._decode_kw = {"use_kernel": True} if use_kernel else {}
        if params is None:
            params = self.model.init(jax.random.key(seed))
        self._params_cache: dict = {}        # weight_bits -> param tree
        q = next((x for x in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor))
            if isinstance(x, QTensor)), None)
        if q is None:
            self._raw_params = params        # full precision master copy
        else:
            # an already-quantized tree is the ONE precision this engine
            # serves: no fp master is kept (at full width it would not
            # fit beside the quantized copy and the KV arena)
            bits = self._canon_bits((q.bits, q.act_bits))
            if quant_bits and self._canon_bits(quant_bits) != bits:
                raise ValueError(f"params are quantized at {bits}, not "
                                 f"quant_bits={quant_bits}")
            self._raw_params = None
            self._params_cache[bits] = self._servable(params)
            quant_bits = bits
        self.default_bits = self._canon_bits(quant_bits)
        self.params = self.params_for(quant_bits)
        self.precisions_served: set = set()  # bit-widths generate() ran at
        self.cache_len = s_max + n_max
        self._prefill = jax.jit(self._prefill_fn)
        self._decode = jax.jit(self._decode_fn)
        # the fused decode loop consumes the prefill cache in place; CPU
        # does not implement donation (it would only warn), so gate it
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self._decode_loop = jax.jit(self._decode_loop_fn,
                                    donate_argnums=donate)
        # chunked decode: the segment loop consumes the carried state
        # (cache, cur, out, lengths, done) — argnums 1-5 — and the refill
        # merge consumes the old cache it splices the new slots into
        seg_donate = (1, 2, 3, 4, 5) if donate else ()
        self._decode_chunk = jax.jit(self._decode_chunk_fn,
                                     donate_argnums=seg_donate)
        self._refill_merge = jax.jit(self._refill_merge_fn,
                                     donate_argnums=(0,) if donate else ())
        # paged path (DESIGN.md §2.3): the segment loop consumes the
        # arena page buffers + per-row emission state; the block-scatter
        # consumes the old pages AND the contiguous prefill cache it
        # splices in
        self._decode_chunk_paged = jax.jit(
            self._decode_chunk_paged_fn,
            donate_argnums=(1, 3, 4, 5, 6) if donate else ())
        self._page_scatter = jax.jit(
            self._page_scatter_fn,
            donate_argnums=(0, 1) if donate else ())
        self._refill_rows = jax.jit(self._refill_rows_fn)
        self._cache_axes = None              # per-leaf batch axis (lazy)
        self.lease_topups = 0                # pages leased via segment-
                                             # boundary top-up (metrics)

    # -- multi-precision weight cache ---------------------------------------

    @staticmethod
    def _canon_bits(bits):
        """Canonical precision spec.

        Accepts an int (weight bits; 0/16 both mean full precision) or a
        ``(weight_bits, act_bits)`` pair (a QuantMethod.serve_bits — W8A8
        serves as ``(8, 8)``).  On the CPU backend the activation tag
        is canonicalized away — quantized trees are dequantized at load
        there (see ``params_for``), so (8, 8) and 8 would be the same
        tree and must share one cache entry."""
        if isinstance(bits, (tuple, list)):
            w, a = bits
            w = 0 if not w or w >= 16 else int(w)
            a = 16 if not a or a >= 16 else int(a)
            if w == 0 or a == 16 or _INTERPRET:
                return w
            return (w, a)
        return 0 if not bits or bits >= 16 else int(bits)

    def params_for(self, bits):
        """Weights at ``bits`` precision (int or (w, a) pair), quantized
        once and cached so the scheduler can swap the served method every
        epoch.  On TPU, dense/moe/vlm trees keep their QTensor leaves and
        serve through the Pallas kernel tiers (W8A16/W4A16, W8A8 when
        tagged act_bits=8).  On the CPU backend EVERY family
        dequantizes at load: int8 compute cannot beat the f32 BLAS there
        (measured, DESIGN.md §3), so quantized serving keeps fake-quant
        numerics but runs fp-speed XLA matmuls — quantization pays in
        bytes and on TPU, never as an interpret-mode slowdown."""
        bits = self._canon_bits(bits)
        if bits not in self._params_cache:
            if self._raw_params is None:
                raise ValueError(
                    f"no precision {bits}: built from a quantized tree, "
                    f"the engine has no full-precision master")
            if bits == 0:
                p = self._raw_params
            else:
                w, a = bits if isinstance(bits, tuple) else (bits, 16)
                p = self._servable(
                    quantize_tree(self._raw_params, w, act_bits=a))
            self._params_cache[bits] = p
        return self._params_cache[bits]

    def _servable(self, p):
        """A quantized tree as this engine serves it: QTensor leaves for
        the transformer families on the TPU; dequantized at load for the
        families whose matmuls bypass ``common.mm`` (recurrent, encdec)
        and on the CPU backend."""
        if self.cfg.family not in ("dense", "moe", "vlm") or _INTERPRET:
            return dequantize_tree(p)
        return p

    def decode_tier(self, bits=None) -> str:
        """The Pallas decode-attention tier ``use_kernel=True`` serving
        at ``bits`` (engine default when None) routes to — ``"kv8"`` /
        ``"fused"`` / ``"flash"``, see ``kernels.ops.decode_kernel_tier``.
        The CPU backend dequantizes quantized trees at load, so there it
        reports ``"flash"`` even for int8 methods."""
        from repro.kernels import ops as kops
        params = self.params_for(self.default_bits if bits is None
                                 else bits)
        layer = params.get("layers", params) if isinstance(params, dict) \
            else params
        return kops.decode_kernel_tier(layer, self.cfg)

    # -- compiled step functions --------------------------------------------

    def _prefill_fn(self, params, batch):
        """Prompt pass; returns (first sampled token (B,), KV cache)."""
        logits, cache = self.model.prefill(params, batch, self.cache_len)
        cur = jnp.argmax(logits[..., :self.cfg.vocab], -1).astype(jnp.int32)
        return cur, cache

    def _decode_fn(self, params, cache, tokens, pos):
        return self.model.decode_step(params, cache, tokens, pos,
                                      **self._decode_kw)

    def _decode_loop_fn(self, params, cache, cur, caps):
        """The entire autoregressive stage as ONE ``lax.while_loop``.

        Carries ``(cache, cur, out, lengths, done, t)`` on device; emits
        ``cur`` into ``out[:, t]`` for rows still alive (not done, under
        cap), flags EOS rows, steps the model, and exits as soon as no row
        can emit again.  Mirrors ``generate_reference`` bit for bit: dead
        rows keep stepping through the model (their cache writes are
        irrelevant — they never emit again), exactly like the legacy loop.
        """
        B = cur.shape[0]
        out0 = jnp.zeros((B, self.n_max), jnp.int32)
        lengths0 = jnp.zeros((B,), jnp.int32)
        done0 = jnp.zeros((B,), bool)

        def alive_mask(done, t):
            return (~done) & (t < caps)

        def cond(state):
            _, _, _, _, done, t = state
            return (t < self.n_max) & jnp.any(alive_mask(done, t))

        def body(state):
            cache, cur, out, lengths, done, t = state
            alive = alive_mask(done, t)
            out = out.at[:, t].set(jnp.where(alive, cur, out[:, t]))
            lengths = lengths + alive.astype(jnp.int32)
            done = done | ((cur == self.eos_id) & alive)
            logits, cache = self.model.decode_step(
                params, cache, cur[:, None], self.s_max + t,
                **self._decode_kw)
            cur = jnp.argmax(logits[..., :self.cfg.vocab],
                             -1).astype(jnp.int32)
            return cache, cur, out, lengths, done, t + 1

        state = (cache, cur, out0, lengths0, done0, jnp.int32(0))
        _, _, out, lengths, _, _ = jax.lax.while_loop(cond, body, state)
        return out, lengths

    def _decode_chunk_fn(self, params, cache, cur, out, lengths, done,
                         caps, t, t_end, forced, n_forced):
        """One re-entrant SEGMENT of the fused decode loop.

        Identical per-step ops to ``_decode_loop_fn``, but (a) the carried
        state enters and leaves as arguments so the loop can be resumed,
        and (b) rows emit at their own ``lengths[i]`` instead of the
        cohort step ``t`` — equal while every row started at t=0 (which
        makes chunked decode bit-identical to the single fused loop), and
        what lets rows admitted mid-cohort by ``refill_chunked`` fill
        their row of ``out`` from 0.  ``t_end`` bounds this segment;
        passing it as an operand keeps ONE compiled executable for every
        chunk size k.

        While ``lengths[i] < n_forced[i]`` a row emits (and feeds the
        model) ``forced[i, lengths[i]]`` instead of its argmax — the
        preempt-resume replay: a resumed row re-prefills its ORIGINAL
        prompt and replays the tokens it already delivered, pinning the
        user-visible prefix bit-exactly regardless of the cohort
        alignment it rejoins at (DESIGN.md §2.4).  ``n_forced`` is zero
        outside resume, making the override a no-op.
        """
        B = cur.shape[0]
        rows = jnp.arange(B)

        def alive_mask(done, lengths):
            return (~done) & (lengths < caps)

        def cond(state):
            _, _, _, lengths, done, t = state
            return (t < t_end) & jnp.any(alive_mask(done, lengths))

        def body(state):
            cache, cur, out, lengths, done, t = state
            alive = alive_mask(done, lengths)
            idx = jnp.minimum(lengths, self.n_max - 1)
            cur = jnp.where(lengths < n_forced, forced[rows, idx], cur)
            out = out.at[rows, idx].set(
                jnp.where(alive, cur, out[rows, idx]))
            lengths = lengths + alive.astype(jnp.int32)
            done = done | ((cur == self.eos_id) & alive)
            logits, cache = self.model.decode_step(
                params, cache, cur[:, None], self.s_max + t,
                **self._decode_kw)
            cur = jnp.argmax(logits[..., :self.cfg.vocab],
                             -1).astype(jnp.int32)
            return cache, cur, out, lengths, done, t + 1

        state = (cache, cur, out, lengths, done, t)
        return jax.lax.while_loop(cond, body, state)

    def _cache_batch_axes(self):
        """Per-leaf batch axis of the cache pytree (recurrent families put
        scan-stacked leading dims before batch), derived structurally by
        diffing cache shapes at two batch sizes — no family-specific
        layout knowledge."""
        if self._cache_axes is None:
            a = jax.eval_shape(lambda: self.model.init_cache(2,
                                                             self.cache_len))
            b = jax.eval_shape(lambda: self.model.init_cache(3,
                                                             self.cache_len))

            def axis(sa, sb):
                diff = [i for i, (x, y) in enumerate(zip(sa.shape, sb.shape))
                        if x != y]
                assert len(diff) == 1, (sa.shape, sb.shape)
                return diff[0]

            self._cache_axes = jax.tree_util.tree_map(axis, a, b)
        return self._cache_axes

    def _refill_merge_fn(self, old_cache, new_cache, cur, new_cur, out,
                         lengths, done, caps, new_caps, refill):
        """Splice freshly prefilled rows into a live decode state.

        ``refill`` is the (B,) bool slot mask; refilled rows take the new
        prefill's cache/cur and reset their emission state, live rows are
        untouched."""
        axes = self._cache_batch_axes()

        def mix(ax, old, new):
            m = refill.reshape((1,) * ax + (-1,)
                               + (1,) * (old.ndim - ax - 1))
            return jnp.where(m, new, old)

        cache = jax.tree_util.tree_map(
            lambda ax, o, n: mix(ax, o, n), axes, old_cache, new_cache)
        cur = jnp.where(refill, new_cur, cur)
        out = jnp.where(refill[:, None], 0, out)
        lengths = jnp.where(refill, 0, lengths)
        done = jnp.where(refill, False, done)
        caps = jnp.where(refill, new_caps, caps)
        return cache, cur, out, lengths, done, caps

    # -- paged-arena compiled step functions (DESIGN.md §2.3) ----------------

    def _decode_chunk_paged_fn(self, params, pages, table, cur, out,
                               lengths, done, caps, t, t_end, forced,
                               n_forced):
        """The re-entrant decode segment over the PAGED cache: identical
        per-step ops to ``_decode_chunk_fn`` (including the forced-replay
        override) but the KV reads/writes go through
        ``model.decode_step_paged`` — the node-wide page buffers
        are the carried cache and the cohort's block table (static within
        a segment; rows only change at admission/release boundaries) is
        an operand."""
        B = cur.shape[0]
        rows = jnp.arange(B)

        def alive_mask(done, lengths):
            return (~done) & (lengths < caps)

        def cond(state):
            _, _, _, lengths, done, t = state
            return (t < t_end) & jnp.any(alive_mask(done, lengths))

        def body(state):
            pages, cur, out, lengths, done, t = state
            alive = alive_mask(done, lengths)
            idx = jnp.minimum(lengths, self.n_max - 1)
            cur = jnp.where(lengths < n_forced, forced[rows, idx], cur)
            out = out.at[rows, idx].set(
                jnp.where(alive, cur, out[rows, idx]))
            lengths = lengths + alive.astype(jnp.int32)
            done = done | ((cur == self.eos_id) & alive)
            logits, pages = self.model.decode_step_paged(
                params, pages, table, cur[:, None], self.s_max + t,
                **self._decode_kw)
            cur = jnp.argmax(logits[..., :self.cfg.vocab],
                             -1).astype(jnp.int32)
            return pages, cur, out, lengths, done, t + 1

        state = (pages, cur, out, lengths, done, t)
        return jax.lax.while_loop(cond, body, state)

    def _page_scatter_fn(self, pages, cache, ids):
        """Splice a contiguous prefill cache into the arena, block-wise.

        ``ids`` is (B * n_blocks,) int32: the physical page receiving
        logical block (b, j) — ``TRASH_PAGE`` for rows/blocks that were
        not (re)filled, so their scatter lands in the don't-care page
        (duplicate trash indices are benign: nothing live reads it).
        Page tails can exceed this engine's cache tail (node pool sized
        to the max over cohorts) — the scatter fills only the leading
        corner, matching the reads in ``decode_attention_paged``."""
        out = {}
        for name, pleaf in pages.items():
            cleaf = cache[name]
            L, B, W = cleaf.shape[:3]
            bt = pleaf.shape[2]
            vals = cleaf.reshape((L, B * (W // bt), bt) + cleaf.shape[3:])
            idx = (slice(None), ids, slice(None)) \
                + tuple(slice(0, d) for d in vals.shape[3:])
            out[name] = pleaf.at[idx].set(vals.astype(pleaf.dtype))
        return out

    def _refill_rows_fn(self, cur, new_cur, out, lengths, done, caps,
                        new_caps, refill):
        """Per-row emission-state splice of a paged refill (the cache
        splice happened in ``_page_scatter_fn``)."""
        cur = jnp.where(refill, new_cur, cur)
        out = jnp.where(refill[:, None], 0, out)
        lengths = jnp.where(refill, 0, lengths)
        done = jnp.where(refill, False, done)
        caps = jnp.where(refill, new_caps, caps)
        return cur, out, lengths, done, caps

    # -- public API ----------------------------------------------------------

    def synth_prompts(self, requests: Sequence, rng: np.random.Generator):
        """Synthesize random-token prompts + output caps for scheduled
        requests, clamped to this engine's static shapes (the cost-model
        lengths s_i/n_i may exceed a reduced engine's s_max/n_max)."""
        prompts = [rng.integers(1, self.cfg.vocab,
                                size=min(r.s, self.s_max)).tolist()
                   for r in requests]
        caps = [min(r.n, self.n_max) for r in requests]
        return prompts, caps

    def pad_prompts(self, prompts: Sequence[Sequence[int]]) -> np.ndarray:
        """Left-truncate/right-pad prompts to (batch_capacity, s_max)."""
        B = self.batch_capacity
        out = np.zeros((B, self.s_max), np.int32)
        for i, p in enumerate(prompts[:B]):
            p = list(p)[-self.s_max:]
            out[i, -len(p):] = p        # right-aligned => last slot is last
        return out

    def _prepare(self, prompts, n_tokens, quant_bits):
        """Shared generate() front half: resolve weights, pad the batch and
        ship (prompts, caps) to the device in ONE ``jax.device_put``."""
        bits = self.default_bits if quant_bits is None \
            else self._canon_bits(quant_bits)
        params = self.params_for(bits)
        self.precisions_served.add(bits)
        return (params, bits) + self._pad_and_ship(prompts, n_tokens)

    def _pad_and_ship(self, prompts, n_tokens):
        B = self.batch_capacity
        nb = len(prompts)
        assert nb <= B, (nb, B)
        caps = np.full((B,), self.n_max, np.int32)
        if n_tokens is not None:
            caps[:nb] = np.minimum(np.asarray(n_tokens, np.int32), self.n_max)
        caps[nb:] = 0

        tokens, caps_j = jax.device_put((self.pad_prompts(prompts), caps))
        return self._as_batch(tokens), caps_j, caps, nb

    def _as_batch(self, tokens):
        """Wrap device-resident prompt tokens as a model input batch."""
        B = self.batch_capacity
        batch = {"tokens": tokens}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (B, self.cfg.vlm.n_img_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "audio":
            batch["audio_embeds"] = jnp.zeros(
                (B, self.cfg.encdec.n_audio_frames, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return batch

    def generate(self, prompts: Sequence[Sequence[int]],
                 n_tokens: Optional[Sequence[int]] = None,
                 greedy: bool = True,
                 quant_bits: Optional[int] = None) -> GenerationResult:
        """Prefill + fused device-resident decode of one batch.

        ``n_tokens`` caps each request's output; ``quant_bits`` serves this
        batch at an explicit weight precision (via the multi-precision
        cache), ``None`` uses the engine default.  Exactly one
        host→device and one device→host transfer happen per call — every
        token decision (sampling, EOS, caps) stays on device inside
        ``_decode_loop_fn``.
        """
        params, _, batch, caps_j, _, nb = self._prepare(prompts, n_tokens,
                                                        quant_bits)
        cur, cache = self._prefill(params, batch)
        out_j, lengths_j = self._decode_loop(params, cache, cur, caps_j)
        out, lengths = jax.device_get((out_j, lengths_j))
        return GenerationResult(tokens=out[:nb], lengths=lengths[:nb],
                                batch=nb)

    def generate_reference(self, prompts: Sequence[Sequence[int]],
                           n_tokens: Optional[Sequence[int]] = None,
                           greedy: bool = True,
                           quant_bits: Optional[int] = None
                           ) -> GenerationResult:
        """The legacy host-driven decode loop, kept as the interpret-style
        oracle: one blocking device→host transfer PER TOKEN.  The fused
        path must match it bit for bit (see tests/test_serving.py)."""
        params, _, batch, _, caps, nb = self._prepare(prompts, n_tokens,
                                                      quant_bits)
        B = self.batch_capacity
        cur_j, cache = self._prefill(params, batch)
        cur = np.asarray(jax.device_get(cur_j), np.int32)

        out = np.zeros((B, self.n_max), np.int32)
        lengths = np.zeros((B,), np.int32)
        done = np.zeros((B,), bool)

        for t in range(int(caps.max(initial=0))):
            alive = (~done) & (t < caps)
            if not alive.any():
                break
            out[alive, t] = cur[alive]
            lengths[alive] += 1
            done |= (cur == self.eos_id) & alive
            step_tok = jnp.asarray(cur)[:, None]
            pos = jnp.int32(self.s_max + t)
            logits, cache = self._decode(params, cache, step_tok, pos)
            cur = np.asarray(
                jax.device_get(
                    jnp.argmax(logits[..., :self.cfg.vocab], -1)), np.int32)
        return GenerationResult(tokens=out[:nb], lengths=lengths[:nb],
                                batch=nb)

    # -- chunked (re-entrant) decode: the continuous-batching data plane ----

    @property
    def paged_capable(self) -> bool:
        """Whether this engine's family can serve through a paged KV
        arena: a slot-cache layout with no rolling sliding window (page
        identity must be position-stable) and a paged decode step.  MoE
        is excluded: capacity dispatch couples rows, so a released row's
        trash-page garbage could perturb live rows' expert routing — the
        per-row independence the bit-exactness contract relies on."""
        return self.model.decode_step_paged is not None \
            and not self.cfg.sliding_window and not self.cfg.is_moe

    def pages_for_admission(self, t: int, n: int,
                            block_tokens: int) -> int:
        """Pages one row admitted at cohort step ``t`` with output cap
        ``n`` will lease over its whole life — CAP-AWARE, not worst-case.

        The row's writes land at the cohort-shared position ``s_max + τ``
        for ``τ in [t, min(t + n, n_max))``, so it needs exactly its
        prompt-prefix blocks plus the blocks covering that write span:
        the fully-dead junk-gap blocks ``[ceil(s_max/bt), (s_max+t)//bt)``
        map to the shared zero page and cost nothing, and blocks past the
        cap's last write block are NEVER leased — any overflow write
        (a finished row keeps stepping until released) routes to
        ``TRASH_PAGE`` through the block table.  Admission (``accepts``)
        reserves this count; ``start/refill_chunked`` lease the prompt
        prefix + first write block up front and ``_extend_leases`` tops
        the rest up at segment boundaries, so the reservation equals the
        pages subsequently leased (tests pin the identity) and a row
        never writes an unleased block WITHIN a segment."""
        nb = self.cache_len // block_tokens
        t = max(0, int(t))
        end = min(t + int(n), self.n_max)
        if end <= t:
            return 0            # no headroom / cap 0: nothing to lease
        npb = -(-self.s_max // block_tokens)
        b_w = min((self.s_max + t) // block_tokens, nb - 1)
        b_last = (self.s_max + end - 1) // block_tokens
        return npb + max(0, b_last + 1 - max(npb, b_w))

    def _lease_row(self, arena: KVArena, t: int, cap: int):
        """Initial cap-aware lease plan for one row admitted at cohort
        step ``t`` with output cap ``cap``: the blocks to lease NOW
        (prompt prefix + the first write block, which must be scattered
        from the prefill cache so the gap-tail positions inside it read
        as the slab's zeros), the table row mapping (ZERO for the
        fully-dead junk gap, TRASH beyond the lease span), and the
        ``(lease_end, lease_last)`` bookkeeping the segment-boundary
        top-up advances."""
        bt = arena.block_tokens
        nb = self.cache_len // bt
        npb = -(-self.s_max // bt)
        b_w = min((self.s_max + int(t)) // bt, nb - 1)
        row = np.full((nb,), TRASH_PAGE, np.int32)
        row[npb:b_w] = ZERO_PAGE        # junk gap [s_max, s_max + t)
        blocks = list(range(npb))
        if b_w >= npb:
            blocks.append(b_w)
        lease_end = b_w + 1 if b_w >= npb else npb
        end = min(int(t) + int(cap), self.n_max)
        b_last = (self.s_max + end - 1) // bt if end > int(t) else 0
        lease_last = max(lease_end, b_last + 1)
        return blocks, row, lease_end, lease_last

    def _extend_leases(self, state: PagedDecodeState, k: int) -> None:
        """Segment-boundary lease top-up (DESIGN.md §2.3): before a
        segment of at most ``k`` steps launches, every row's lease must
        cover the blocks the segment can write — a block is read
        UNMASKED once the cursor passes it, so it must be leased before
        the cursor enters it, never after.  Host-side ``BlockTable``
        remap + ONE device re-ship (the lazy mirror), never a
        mid-segment allocation.  ``t_host`` is a host-side upper bound
        on the cohort step (segments may exit early), so the cover can
        only OVERSHOOT — bounded by ``lease_last``, i.e. inside the
        admission-time reservation the runtime charged."""
        arena = state.arena
        bt = arena.block_tokens
        nb = self.cache_len // bt
        cover = min(state.t_host + int(k), self.n_max)
        need_end = min((self.s_max + cover - 1) // bt + 1, nb)
        for b in range(state.lease_end.shape[0]):
            tgt = min(need_end, int(state.lease_last[b]))
            le = int(state.lease_end[b])
            if tgt > le:
                state.table.extend_row(b, le, arena.alloc(tgt - le))
                state.lease_end[b] = tgt
                self.lease_topups += tgt - le
        state.t_host = cover

    def lease_commitment(self, state: Optional[PagedDecodeState]) -> int:
        """Pages a live cohort is still ENTITLED to lease via future
        top-ups (Σ ``lease_last - lease_end``).  Admission must keep
        this many pages un-promised on top of the free list, so a
        boundary's top-ups can never hit :class:`ArenaExhausted`."""
        if state is None or state.lease_end is None:
            return 0
        return int(np.maximum(0, state.lease_last.astype(np.int64)
                              - state.lease_end).sum())

    def _forced_buffers(self, prefixes, slots=None):
        """Host (B, n_max) forced-replay token buffer + (B,) lengths from
        per-row resume prefixes (``None`` entries = no replay).  ``slots``
        maps prefix i to its row (defaults to ``0..len-1``)."""
        B = self.batch_capacity
        forced = np.zeros((B, self.n_max), np.int32)
        nf = np.zeros((B,), np.int32)
        if prefixes is not None:
            rows = range(len(prefixes)) if slots is None else slots
            for row, pre in zip(rows, prefixes):
                if pre is not None and len(pre):
                    pre = list(pre)[:self.n_max]
                    forced[row, :len(pre)] = pre
                    nf[row] = len(pre)
        return forced, nf

    def start_chunked(self, prompts: Sequence[Sequence[int]],
                      n_tokens: Optional[Sequence[int]] = None,
                      quant_bits: Optional[int] = None,
                      arena: Optional[KVArena] = None,
                      prefixes: Optional[Sequence] = None):
        """Prefill a new cohort and return its device-resident decode
        state (ONE host→device transfer; decoding hasn't started).
        Prompts occupy slots ``0..len(prompts)-1``; the remaining slots
        are empty (cap 0) and refillable.  With ``arena=`` the cohort is
        arena-backed: the prefill cache is scattered block-wise into
        leased pages and a :class:`PagedDecodeState` is returned.
        ``prefixes`` seeds per-row forced-replay tokens (one entry per
        prompt, ``None`` = fresh row) for preemption resume — see
        ``_decode_chunk_fn``."""
        with span("prefill.prepare"):
            params, bits, batch, caps_j, caps, _ = self._prepare(
                prompts, n_tokens, quant_bits)
            cur, cache = self._prefill(params, batch)
            B = self.batch_capacity
            if prefixes is None:       # keep the one-put-at-start invariant
                forced = jnp.zeros((B, self.n_max), jnp.int32)
                nf = jnp.zeros((B,), jnp.int32)
            else:
                forced, nf = jax.device_put(self._forced_buffers(prefixes))
            if arena is None:
                return DecodeState(
                    cache=cache, cur=cur,
                    out=jnp.zeros((B, self.n_max), jnp.int32),
                    lengths=jnp.zeros((B,), jnp.int32),
                    done=jnp.zeros((B,), bool),
                    caps=caps_j, t=jnp.int32(0), bits=bits, caps_host=caps,
                    forced=forced, n_forced=nf)
            assert self.paged_capable, self.cfg.arch_id
            bt = arena.block_tokens
            assert self.cache_len % bt == 0, (self.cache_len, bt)
            nb = self.cache_len // bt
            table = BlockTable(B, nb, n_pages=arena.n_pages)
            ids = np.full((B * nb,), TRASH_PAGE, np.int32)
            lease_end = np.zeros((B,), np.int32)
            lease_last = np.zeros((B,), np.int32)
            for b in range(B):
                if caps[b] > 0:
                    # cap-aware lease: prompt blocks + first write block now
                    # (blocks past it stay TRASH until a segment-boundary
                    # top-up), instead of the historical full-span alloc(nb)
                    blocks, row, le, ll = self._lease_row(arena, 0, caps[b])
                    leases = arena.alloc(len(blocks))
                    row[blocks] = leases
                    table.set_row(b, row)
                    ids[b * nb + np.asarray(blocks)] = leases
                    lease_end[b], lease_last[b] = le, ll
        pages = self._page_scatter(arena.buffers(), cache,
                                   jax.device_put(ids))
        arena.set_buffers(pages)
        return PagedDecodeState(
            arena=arena, table=table, cur=cur,
            out=jnp.zeros((B, self.n_max), jnp.int32),
            lengths=jnp.zeros((B,), jnp.int32),
            done=jnp.zeros((B,), bool),
            caps=caps_j, t=jnp.int32(0), bits=bits, caps_host=caps,
            forced=forced, n_forced=nf,
            lease_end=lease_end, lease_last=lease_last, t_host=0)

    def generate_chunked(self, state, k: int):
        """Advance a cohort by AT MOST ``k`` decode steps (one jitted
        re-entrant while-loop segment, no host transfer) and return the
        re-entrant state.  The input state is consumed (donated on
        backends that support it).  Driven to completion this is
        bit-identical to the single fused loop for any k (see
        tests/test_continuous_engine.py).  A :class:`PagedDecodeState`
        advances through the paged segment loop — the arena page buffers
        are checked out, carried through the while-loop, and checked
        back in."""
        with span("segment.launch"):
            params = self.params_for(state.bits)
            t_end = jnp.minimum(state.t + jnp.int32(k),
                                jnp.int32(self.n_max))
            if isinstance(state, PagedDecodeState):
                # boundary top-up: lease every block this segment can
                # write BEFORE launching it (one host-side remap + one
                # table re-ship; the jitted segment never allocates)
                with span("segment.lease_topup"):
                    self._extend_leases(state, k)
                pages, cur, out, lengths, done, t = \
                    self._decode_chunk_paged(
                        params, state.arena.buffers(), state.table.device,
                        state.cur, state.out, state.lengths, state.done,
                        state.caps, state.t, t_end, state.forced,
                        state.n_forced)
                state.arena.set_buffers(pages)
                return dataclasses.replace(state, cur=cur, out=out,
                                           lengths=lengths, done=done, t=t)
            cache, cur, out, lengths, done, t = self._decode_chunk(
                params, state.cache, state.cur, state.out, state.lengths,
                state.done, state.caps, state.t, t_end, state.forced,
                state.n_forced)
            return dataclasses.replace(state, cache=cache, cur=cur,
                                       out=out, lengths=lengths, done=done,
                                       t=t)

    def release_slots(self, state: PagedDecodeState,
                      slots: Sequence[int]) -> PagedDecodeState:
        """Return completed rows' page leases to the arena and remap
        their table rows to the trash page (their continued writes — dead
        rows keep stepping, exactly like the slab path — become
        don't-care scatters no live row reads).  Freed pages are
        allocatable by ANY cohort at the very next admission boundary,
        and the row's remaining lease entitlement is CANCELLED — the
        un-leased tail of its reservation returns to the node's
        admission budget (``lease_commitment``) the same moment."""
        for slot in slots:
            state.arena.free(state.table.row_leases(slot))
            state.table.clear_row(slot)
            if state.lease_end is not None:
                state.lease_end[slot] = 0
                state.lease_last[slot] = 0
        return state

    def release_all(self, state: PagedDecodeState) -> PagedDecodeState:
        """Release every leased page of a drained cohort."""
        return self.release_slots(state,
                                  range(state.table.host.shape[0]))

    def poll_chunked(self, state: DecodeState, with_tokens: bool = True):
        """Read a cohort's progress back to the host: ONE device→host
        transfer returning ``(out, lengths, done, t)`` as numpy + int.

        ``with_tokens=False`` skips the (B, n_max) token buffer — the
        per-segment hot path (``EngineContinuousExecutor``) only needs
        the few-hundred-byte ``(lengths, done, t)`` occupancy view, and
        at production shapes ``out`` is the dominant transfer; ``out``
        comes back as None."""
        if not with_tokens:
            with span("poll.fetch"):
                lengths, done, t = jax.device_get(
                    (state.lengths, state.done, state.t))
            return None, lengths, done, int(t)
        with span("poll.fetch"):
            out, lengths, done, t = jax.device_get(
                (state.out, state.lengths, state.done, state.t))
        return out, lengths, done, int(t)

    def exhausted(self, lengths, done, caps_host, t) -> bool:
        """True when no row of a polled cohort can emit again."""
        return t >= self.n_max or \
            not bool(np.any(~done & (lengths < caps_host)))

    def headroom(self, t: int) -> int:
        """Output tokens a row admitted at cohort step ``t`` can still
        emit before the shared cache position hits capacity."""
        return max(0, self.n_max - t)

    def evict_slots(self, state, slots: Sequence[int]):
        """Preempt resident rows at a segment boundary: flag them done
        and zero their caps so the next segment treats them exactly like
        finished rows (dead rows keep stepping; their writes are
        don't-care scatters).  Paged rows additionally return their page
        leases, so the freed memory is allocatable at the very next
        admission boundary.  The caller is responsible for having
        polled any progress it wants to spill BEFORE evicting."""
        slots = list(slots)
        if not slots:
            return state
        B = self.batch_capacity
        mask = np.zeros((B,), bool)
        mask[slots] = True
        mask_j = jax.device_put(mask)
        done = jnp.where(mask_j, True, state.done)
        caps = jnp.where(mask_j, 0, state.caps)
        caps_host = np.where(mask, 0, state.caps_host)
        if isinstance(state, PagedDecodeState):
            for slot in slots:
                state.arena.free(state.table.row_leases(slot))
                state.table.clear_row(slot)
                if state.lease_end is not None:
                    state.lease_end[slot] = 0     # cancel the remaining
                    state.lease_last[slot] = 0    # lease entitlement too
        return dataclasses.replace(state, done=done, caps=caps,
                                   caps_host=caps_host)

    def refill_chunked(self, state, slots: Sequence[int],
                       prompts: Sequence[Sequence[int]],
                       n_tokens: Sequence[int], t_now: int,
                       cap_max: Optional[int] = None,
                       prefixes: Optional[Sequence] = None):
        """Prefill new prompts into freed slots of a LIVE cohort.

        The new prompts are padded into their slot rows, prefilled as one
        full-capacity batch (positions ``[0, s_max)`` — one device_put +
        one compiled prefill), and spliced into the resident cache with
        ``_refill_merge`` so live rows keep decoding untouched.  A
        refilled row's cap is clamped to ``headroom(t_now)`` so its cache
        writes stay inside ``s_max + n_max``; callers gate admission on
        that headroom.  ``cap_max`` tightens the clamp further (an
        explicit caller-side bound; admission control normally makes it
        redundant with the cohort's own headroom).  When the clamp
        bottoms out at 0 — or ``slots`` is empty — the refill is a
        NO-OP returning ``state`` untouched: prefilling rows that could
        never emit would occupy slots until drain for nothing.  Cache
        slots between a refilled row's prompt and the cohort's current
        position hold zero K/V — junk attention positions of the same
        class as the engine's padded prompts (the paper's s' padding);
        recurrent-state families have no such gap.  For a
        :class:`PagedDecodeState` the splice is block-wise and
        CAP-AWARE: fresh pages are leased for the prompt blocks plus the
        first write block only, the fully-dead junk-gap blocks map to
        the shared zero page (no physical memory), and the rest of the
        row's ``t + n`` span stays TRASH until the segment-boundary
        top-up leases it (DESIGN.md §2.3).
        """
        with span("prefill.prepare"):
            B = self.batch_capacity
            params = self.params_for(state.bits)
            toks = np.zeros((B, self.s_max), np.int32)
            new_caps = np.zeros((B,), np.int32)
            refill = np.zeros((B,), bool)
            cap_lim = min(self.n_max, self.headroom(t_now))
            if cap_max is not None:
                cap_lim = min(cap_lim, max(0, int(cap_max)))
            if not slots or cap_lim <= 0:
                return state
            for slot, p, n in zip(slots, prompts, n_tokens):
                p = list(p)[-self.s_max:]
                if p:
                    toks[slot, -len(p):] = p
                new_caps[slot] = min(int(n), cap_lim)
                refill[slot] = True
            toks_j, caps_j, refill_j = jax.device_put((toks, new_caps, refill))
            new_cur, new_cache = self._prefill(params, self._as_batch(toks_j))
            caps_host = np.where(refill, new_caps, state.caps_host)
            # Forced-replay splice (preemption resume): refilled rows take
            # their resume prefix (or reset to no-replay); live rows keep
            # theirs.  Outside the jitted merges — it's a few KB — and the
            # no-resume path skips the extra transfer entirely.
            if prefixes is None:
                forced = jnp.where(refill_j[:, None], 0, state.forced)
                n_forced = jnp.where(refill_j, 0, state.n_forced)
            else:
                forced_j, nf_j = jax.device_put(
                    self._forced_buffers(prefixes, slots=slots))
                forced = jnp.where(refill_j[:, None], forced_j, state.forced)
                n_forced = jnp.where(refill_j, nf_j, state.n_forced)
            paged = isinstance(state, PagedDecodeState)
            if paged:
                arena = state.arena
                bt = arena.block_tokens
                nb = self.cache_len // bt
                ids = np.full((B * nb,), TRASH_PAGE, np.int32)
                for slot in slots:
                    arena.free(state.table.row_leases(slot))  # stale leases
                    # cap-aware lease: prompt blocks + the first write block
                    # (scattered so its gap-tail positions read as the
                    # slab's zeros); the junk gap maps to ZERO, everything
                    # past the first write block stays TRASH until the
                    # segment-boundary top-up reaches it
                    blocks, row, le, ll = self._lease_row(
                        arena, t_now, new_caps[slot])
                    leases = arena.alloc(len(blocks))
                    row[blocks] = leases
                    state.table.set_row(slot, row)
                    ids[slot * nb + np.asarray(blocks)] = leases
                    state.lease_end[slot] = le
                    state.lease_last[slot] = ll
        if paged:
            pages = self._page_scatter(arena.buffers(), new_cache,
                                       jax.device_put(ids))
            arena.set_buffers(pages)
            cur, out, lengths, done, caps = self._refill_rows(
                state.cur, new_cur, state.out, state.lengths, state.done,
                state.caps, caps_j, refill_j)
            return dataclasses.replace(state, cur=cur, out=out,
                                       lengths=lengths, done=done,
                                       caps=caps, caps_host=caps_host,
                                       forced=forced, n_forced=n_forced,
                                       t_host=int(t_now))
        cache, cur, out, lengths, done, caps = self._refill_merge(
            state.cache, new_cache, state.cur, new_cur, state.out,
            state.lengths, state.done, state.caps, caps_j, refill_j)
        return dataclasses.replace(state, cache=cache, cur=cur, out=out,
                                   lengths=lengths, done=done, caps=caps,
                                   caps_host=caps_host,
                                   forced=forced, n_forced=n_forced)

    def generate_via_chunks(self, prompts: Sequence[Sequence[int]],
                            n_tokens: Optional[Sequence[int]] = None,
                            k: Optional[int] = None,
                            quant_bits: Optional[int] = None,
                            arena: Optional[KVArena] = None
                            ) -> GenerationResult:
        """Drive ``start_chunked`` + ``generate_chunked`` segments to
        completion — the equivalence harness against ``generate`` /
        ``generate_reference`` (one device→host poll per segment).  With
        ``arena=`` the cohort runs arena-backed (and its pages are
        released on completion) — the paged-vs-slab equivalence oracle."""
        k = self.n_max if k is None else k
        state = self.start_chunked(prompts, n_tokens, quant_bits,
                                   arena=arena)
        while True:
            state = self.generate_chunked(state, k)
            out, lengths, done, t = self.poll_chunked(state)
            if self.exhausted(lengths, done, state.caps_host, t):
                break
        if arena is not None:
            self.release_all(state)
        nb = len(prompts)
        return GenerationResult(tokens=out[:nb], lengths=lengths[:nb],
                                batch=nb)
