"""Paged KV arena (DESIGN.md §2.3): the kernel's block-table indirection
must be BIT-identical to the contiguous oracle, the allocator must never
double-lease a page, the arena-backed engine path must reproduce the
slab path token-for-token across every PR-3/PR-4 edge case (cap=0,
immediate EOS, padding-only rows, quant 0/8/4, int8 KV, mid-cohort
refill), and the continuous executor must gate admission on free pages
while returning every lease at completion.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.environment import paper_env
from repro.core.multi import MultiLLMEnv, random_tagger
from repro.core.request import Request, RequestGenerator
from repro.kernels import ops
from repro.serving.engine import ServingEngine, tiny_engine
from repro.serving.kv_arena import (N_RESERVED, TRASH_PAGE, ZERO_PAGE,
                                    ArenaError, ArenaExhausted, BlockTable,
                                    KVArena)
from repro.serving.runtime import ContinuousRuntime, EngineContinuousExecutor

# -- paged flash-decode kernel: bit-identity to the contiguous oracle --------

PAGED_FD_CASES = [
    # (B, nh, nkv, dh, W, bt) — GQA, MHA, MQA; bt in {16, 64}; dh that
    # needs lane padding (80) and dh that doesn't (64/128)
    (4, 8, 2, 128, 256, 16),
    (3, 4, 4, 64, 128, 64),
    (2, 6, 6, 128, 64, 16),
    (2, 8, 1, 80, 128, 16),
]


def _paged_layout(k, v, bt, seed):
    """Scatter a contiguous (B, W, nkv, dh) cache into a scrambled
    physical page pool, garbage everywhere a logical block doesn't
    live."""
    B, W, nkv, dh = k.shape
    nb = W // bt
    P = N_RESERVED + B * nb + 3
    rng = np.random.default_rng(seed)
    phys = rng.permutation(np.arange(N_RESERVED, P))[:B * nb]
    table = phys.reshape(B, nb).astype(np.int32)
    kp = jax.random.normal(jax.random.key(90 + seed), (P, bt, nkv, dh),
                           k.dtype)
    vp = jax.random.normal(jax.random.key(91 + seed), (P, bt, nkv, dh),
                           v.dtype)
    kb = k.reshape(B, nb, bt, nkv, dh)
    vb = v.reshape(B, nb, bt, nkv, dh)
    for b in range(B):
        for j in range(nb):
            kp = kp.at[table[b, j]].set(kb[b, j])
            vp = vp.at[table[b, j]].set(vb[b, j])
    return kp, vp, jnp.asarray(table)


@pytest.mark.parametrize("case", PAGED_FD_CASES)
def test_paged_flash_decode_bit_identical_to_contiguous(case):
    B, nh, nkv, dh, W, bt = case
    q = jax.random.normal(jax.random.key(1), (B, nh, dh), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, W, nkv, dh), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, W, nkv, dh), jnp.float32)
    nv = np.random.default_rng(case[0]).integers(1, W + 1, B)
    kp, vp, table = _paged_layout(k, v, bt, seed=7)
    got = ops.flash_decode_paged(q, kp, vp, table, jnp.asarray(nv))
    # BITWISE equality against the contiguous kernel at block_s == bt:
    # the paged grid walks the same logical blocks in the same order with
    # the same arithmetic — the physical scramble must be invisible
    want = ops.flash_decode(q, k, v, jnp.asarray(nv), block_s=bt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and numerically equal to the default blocking (different online-
    # softmax accumulation order, same attention)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ops.flash_decode(q, k, v,
                                                     jnp.asarray(nv))),
        rtol=2e-5, atol=2e-5)


def test_paged_flash_decode_ragged_includes_block_edges():
    """n_valid exactly on, one under, and one over block boundaries."""
    B, nh, nkv, dh, W, bt = 6, 4, 2, 64, 128, 16
    q = jax.random.normal(jax.random.key(4), (B, nh, dh), jnp.float32)
    k = jax.random.normal(jax.random.key(5), (B, W, nkv, dh), jnp.float32)
    v = jax.random.normal(jax.random.key(6), (B, W, nkv, dh), jnp.float32)
    nv = jnp.asarray([1, bt - 1, bt, bt + 1, W - 1, W])
    kp, vp, table = _paged_layout(k, v, bt, seed=11)
    got = ops.flash_decode_paged(q, kp, vp, table, nv)
    want = ops.flash_decode(q, k, v, nv, block_s=bt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- allocator ---------------------------------------------------------------


def _tiny_specs():
    return {"k": jax.ShapeDtypeStruct((1, 1, 8, 2, 4), jnp.float32),
            "v": jax.ShapeDtypeStruct((1, 1, 8, 2, 4), jnp.float32)}


def test_arena_alloc_free_roundtrip():
    arena = KVArena(_tiny_specs(), n_pages=10, block_tokens=8)
    assert arena.total_pages == 10 - N_RESERVED
    assert arena.free_pages == arena.total_pages
    a = arena.alloc(3)
    b = arena.alloc(2)
    assert len(set(a) | set(b)) == 5                # disjoint leases
    assert all(p >= N_RESERVED for p in a + b)      # reserved never leased
    assert arena.pages_in_use == 5
    arena.free(a)
    arena.free(b)
    assert arena.free_pages == arena.total_pages
    assert arena.alloc_peak == 5


def test_arena_exhaustion_raises():
    arena = KVArena(_tiny_specs(), n_pages=5, block_tokens=8)
    arena.alloc(arena.total_pages)
    with pytest.raises(ArenaExhausted):
        arena.alloc(1)


def test_arena_buffer_layout_and_zero_init():
    arena = KVArena(_tiny_specs(), n_pages=6, block_tokens=8)
    for leaf in arena.buffers().values():
        assert leaf.shape == (1, 6, 8, 2, 4)
        assert not np.asarray(leaf).any()           # ZERO_PAGE relies on it


def test_block_table_rows_and_leases():
    tbl = BlockTable(batch=3, n_blocks=4)
    assert tbl.row_leases(0) == []                  # all TRASH initially
    tbl.set_row(1, [5, ZERO_PAGE, 6, 7])
    assert tbl.row_leases(1) == [5, 6, 7]           # reserved ids excluded
    dev0 = tbl.device
    tbl.clear_row(1)
    assert tbl.row_leases(1) == []
    assert np.all(tbl.host[1] == TRASH_PAGE)
    assert tbl.device is not dev0                   # mutation re-ships


def test_block_table_device_mirror_is_a_snapshot():
    """Row edits after a re-ship never reach a device table already
    handed to a computation, even where the host array is aligned so
    that the CPU backend could take it without a copy."""
    tbl = BlockTable(batch=8, n_blocks=3)
    raw = np.empty(tbl.host.size + 16, np.int32)
    start = (-raw.ctypes.data % 64) // 4           # 64-byte aligned view
    tbl.host = raw[start:start + tbl.host.size].reshape(tbl.host.shape)
    tbl.host[:] = TRASH_PAGE
    tbl.set_row(0, [5, 6, 7])
    dev = tbl.device
    tbl.clear_row(0)
    tbl.extend_row(1, 0, [8, 9])
    np.testing.assert_array_equal(np.asarray(dev)[:2],
                                  [[5, 6, 7], [TRASH_PAGE] * 3])


def test_arena_free_rejects_double_free_and_reserved_pages():
    """The free-path guards are REAL ``ArenaError`` raises, not asserts
    — CI re-runs this file under ``python -O`` (which strips asserts)
    and these ``pytest.raises`` blocks must still bite there."""
    arena = KVArena(_tiny_specs(), n_pages=10, block_tokens=8)
    lease = arena.alloc(2)
    arena.free(lease)
    with pytest.raises(ArenaError, match="double free"):
        arena.free([lease[0]])
    for p in range(N_RESERVED):
        with pytest.raises(ArenaError, match="reserved"):
            arena.free([p])
    # failed frees must not have mutated the free list
    assert arena.free_pages == arena.total_pages
    assert len(set(arena.alloc(arena.total_pages))) == arena.total_pages


def test_arena_free_rejects_out_of_range_page_ids():
    """Regression: an out-of-range id handed to ``free`` used to grow
    the free list silently, letting a later ``alloc`` lease a page the
    device buffers don't have."""
    arena = KVArena(_tiny_specs(), n_pages=10, block_tokens=8)
    free0 = arena.free_pages
    for bogus in (arena.n_pages, arena.n_pages + 7, 99):
        with pytest.raises(ArenaError, match="out-of-range"):
            arena.free([bogus])
    assert arena.free_pages == free0
    got = arena.alloc(arena.free_pages)
    assert all(N_RESERVED <= p < arena.n_pages for p in got)


def test_arena_free_list_keeps_lifo_reuse_order():
    """The set-backed membership check must not change reuse order:
    most-recently-freed pages are leased first (warm pages stay warm)."""
    arena = KVArena(_tiny_specs(), n_pages=12, block_tokens=8)
    a = arena.alloc(3)
    arena.free(a)
    assert arena.alloc(3) == a[::-1]


def test_block_table_validates_page_ids_and_extends_rows():
    """``set_row``/``extend_row`` on a pool-bound table reject negative
    and beyond-pool page ids without partially mutating the row;
    ``extend_row`` splices a lease tail in place."""
    tbl = BlockTable(batch=2, n_blocks=3, n_pages=8)
    with pytest.raises(ArenaError, match="out of range"):
        tbl.set_row(0, [2, 3, 8])
    with pytest.raises(ArenaError, match="out of range"):
        tbl.set_row(0, [-1, 3, 4])
    assert tbl.row_leases(0) == []                  # row untouched
    tbl.set_row(0, [2, 3, TRASH_PAGE])
    with pytest.raises(ArenaError, match="out of range"):
        tbl.extend_row(0, 2, [8])
    assert tbl.row_leases(0) == [2, 3]
    tbl.extend_row(0, 2, [7])
    assert tbl.row_leases(0) == [2, 3, 7]
    # an unbound table (no pool size known) keeps the legacy behavior
    BlockTable(batch=1, n_blocks=2).set_row(0, [5, 99])


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                 # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(data=st.data())
    def test_arena_never_double_allocates(data):
        """Random alloc/free interleavings: live leases stay disjoint,
        reserved pages never leave the pool, and freeing everything
        restores the initial free count."""
        n_pages = data.draw(st.integers(N_RESERVED + 1, 24))
        arena = KVArena(_tiny_specs(), n_pages, block_tokens=8)
        live = []
        for _ in range(data.draw(st.integers(1, 30))):
            if live and data.draw(st.booleans()):
                i = data.draw(st.integers(0, len(live) - 1))
                arena.free(live.pop(i))
            else:
                n = data.draw(st.integers(0, arena.free_pages))
                lease = arena.alloc(n)
                flat = [p for ls in live for p in ls]
                assert not set(lease) & set(flat)
                assert all(p >= N_RESERVED for p in lease)
                live.append(lease)
            held = sum(len(ls) for ls in live)
            assert arena.free_pages + held == arena.total_pages
        for ls in live:
            arena.free(ls)
        assert arena.free_pages == arena.total_pages

    _PROP_ENG = {}

    def _prop_engine():
        # one reduced engine shared across examples (construction re-jits
        # the segment loops; the schedule varies, the engine need not).
        # eos_id=-1 can never be sampled, so non-evicted rows ALWAYS run
        # to their cap — the case where reservation == leases is exact.
        if not _PROP_ENG:
            _PROP_ENG["eng"] = tiny_engine("bloom-3b", batch_capacity=3,
                                           s_max=8, n_max=8, eos_id=-1)
        return _PROP_ENG["eng"]

    @settings(max_examples=6, deadline=None)
    @given(data=st.data())
    def test_admission_reservation_equals_pages_leased(data):
        """Across random admission steps, caps, refill times, chunk
        sizes and evictions: the pages ``pages_for_admission`` reserved
        for a row exactly equal the pages it has leased (initial lease +
        boundary top-ups) by the time it runs to its cap, never-exceeded
        for rows evicted early, the paged cohort stays bitwise identical
        to an identically-driven slab twin, and the arena drains."""
        eng = _prop_engine()
        bt = data.draw(st.sampled_from([4, 8]))
        arena = KVArena.for_engines([eng], block_tokens=bt)
        B, n_max = eng.batch_capacity, eng.n_max
        rng = np.random.default_rng(data.draw(st.integers(0, 2 ** 16)))

        def mk_prompt():
            s = int(rng.integers(1, eng.s_max + 1))
            return rng.integers(1, eng.cfg.vocab, size=s).tolist()

        n0 = data.draw(st.integers(1, B))
        prompts = [mk_prompt() for _ in range(n0)]
        caps = [data.draw(st.integers(1, n_max)) for _ in range(n0)]
        sp = eng.start_chunked(prompts, caps, arena=arena)
        ss = eng.start_chunked(prompts, caps)
        res = {b: eng.pages_for_admission(0, caps[b], bt)
               for b in range(n0)}
        for b in res:
            assert len(sp.table.row_leases(b)) <= res[b]
        free_slots = list(range(n0, B))
        for _ in range(24):
            k = data.draw(st.integers(1, 4))
            sp = eng.generate_chunked(sp, k)
            ss = eng.generate_chunked(ss, k)
            op, lp, dp, tp = eng.poll_chunked(sp)
            os_, ls_, ds_, ts_ = eng.poll_chunked(ss)
            np.testing.assert_array_equal(op, os_)      # bitwise twin
            np.testing.assert_array_equal(lp, ls_)
            np.testing.assert_array_equal(dp, ds_)
            assert tp == ts_
            done_now = [b for b in list(res)
                        if lp[b] >= sp.caps_host[b] and not dp[b]]
            for b in done_now:                          # ran to cap:
                assert len(sp.table.row_leases(b)) == res.pop(b), b
            # park finished rows on BOTH states the same way (evict flags
            # done + zeroes caps on either state type, and returns the
            # paged row's leases) so the twins stay bitwise comparable
            sp = eng.evict_slots(sp, done_now)
            ss = eng.evict_slots(ss, done_now)
            free_slots += done_now
            if res and data.draw(st.booleans()):        # random preemption
                b = data.draw(st.sampled_from(sorted(res)))
                assert len(sp.table.row_leases(b)) <= res.pop(b)
                sp = eng.evict_slots(sp, [b])
                ss = eng.evict_slots(ss, [b])
                free_slots.append(b)
            if free_slots and eng.headroom(tp) > 0 \
                    and data.draw(st.booleans()):       # random refill
                b = free_slots.pop(data.draw(
                    st.integers(0, len(free_slots) - 1)))
                cap = min(data.draw(st.integers(1, n_max)),
                          eng.headroom(tp))
                p = [mk_prompt()]
                sp = eng.refill_chunked(sp, [b], p, [cap], t_now=tp)
                ss = eng.refill_chunked(ss, [b], p, [cap], t_now=tp)
                assert sp.caps_host[b] == cap
                res[b] = eng.pages_for_admission(tp, cap, bt)
            if not res:                                 # everyone settled
                break
        assert not res                                  # everyone settled
        eng.release_all(sp)
        assert arena.free_pages == arena.total_pages    # fully drained


# -- for_engines sizing / geometry validation --------------------------------


def _fake_engine(cache_len=32, shape=(1, 1, 32, 2, 8),
                 dtype=jnp.bfloat16, leaves=("k", "v"), batch=2):
    class _Model:
        @staticmethod
        def init_cache(b, w):
            return {n: jnp.zeros(shape, dtype) for n in leaves}

    class _Eng:
        paged_capable = True
        model = _Model()
    e = _Eng()
    e.cache_len = cache_len
    e.batch_capacity = batch
    return e


def test_for_engines_rejects_indivisible_cache_len():
    with pytest.raises(ValueError, match="divisible"):
        KVArena.for_engines([_fake_engine(cache_len=30)], block_tokens=16)


def test_for_engines_requires_a_paged_engine():
    with pytest.raises(ValueError, match="paged-capable"):
        KVArena.for_engines([], block_tokens=16)


def test_for_engines_rejects_layer_or_dtype_mismatch():
    a = _fake_engine(shape=(1, 1, 32, 2, 8))
    with pytest.raises(ValueError, match="layer count"):
        KVArena.for_engines([a, _fake_engine(shape=(2, 1, 32, 2, 8))],
                            block_tokens=16)
    with pytest.raises(ValueError, match="dtype"):
        KVArena.for_engines([a, _fake_engine(dtype=jnp.float32)],
                            block_tokens=16)
    with pytest.raises(ValueError, match="leaf names"):
        KVArena.for_engines([a, _fake_engine(leaves=("k", "v", "ks"))],
                            block_tokens=16)


def test_for_engines_pads_tails_to_cohort_max():
    """Cohorts with different head geometry share one pool: pages carry
    the elementwise-max tail, head dim rounded up to 128 lanes, and each
    engine uses its leading corner."""
    a = _fake_engine(shape=(1, 1, 32, 2, 8))
    b = _fake_engine(shape=(1, 1, 32, 4, 4))
    arena = KVArena.for_engines([a, b], block_tokens=16, shrink=1.0)
    assert arena.buffers()["k"].shape[3:] == (4, 128)
    # 2 engines x batch 2 x (32/16 blocks) = 8 allocatable pages
    assert arena.total_pages == 8
    half = KVArena.for_engines([a, b], block_tokens=16, shrink=0.5)
    assert half.total_pages == 4


# -- admission-reservation arithmetic ----------------------------------------


def test_pages_for_admission_is_cap_aware():
    """The reservation checked at admission must equal the DISTINCT
    blocks the row can touch given its cap — prompt-prefix blocks plus
    the blocks under the write span [t, min(t+n, n_max)) — checked
    against an independent set-based oracle.  It must collapse to the
    old worst-case count only when the cap fills the remaining
    headroom, and shrink below it for short caps (the over-reservation
    this PR fixes)."""
    eng = tiny_engine("bloom-3b", batch_capacity=2, s_max=8, n_max=8)
    shrunk = False
    for bt in (4, 8):
        nb = eng.cache_len // bt
        npb = -(-eng.s_max // bt)
        assert eng.pages_for_admission(0, 0, bt) == 0       # cap-0 row
        assert eng.pages_for_admission(eng.n_max, 4, bt) == 0  # no headroom
        for t in range(eng.n_max):
            worst = eng.pages_for_admission(t, eng.n_max, bt)
            assert worst <= nb
            for n in range(1, eng.n_max + 1):
                span = range(t, min(t + n, eng.n_max))
                blocks = set(range(npb)) \
                    | {(eng.s_max + tau) // bt for tau in span}
                got = eng.pages_for_admission(t, n, bt)
                assert got == len(blocks), (bt, t, n)
                assert got <= worst
                shrunk |= got < worst
    # at bt=4 the write region spans 2 blocks, so short caps really do
    # reserve fewer pages than the worst case (at bt=8 it is one block)
    assert shrunk


# -- engine path: arena-backed generation is bit-identical to the slab -------


@pytest.fixture(scope="module")
def hetero_node():
    """Two cohorts with DIFFERENT head dims (80 vs 128 after reduction)
    sharing one padded-tail pool — the cross-cohort reuse case."""
    engines = {a: tiny_engine(a, batch_capacity=4, s_max=32, n_max=16)
               for a in ("bloom-3b", "bloom-7b1")}
    arena = KVArena.for_engines(engines, block_tokens=16)
    return engines, arena


def assert_same_generation(a, b):
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_array_equal(a.lengths, b.lengths)
    assert a.batch == b.batch


@pytest.mark.parametrize("bits", [0, 8, 4])
def test_paged_engine_matches_slab_edge_cases(hetero_node, bits):
    """cap=0 rows, pad-token prompts, padding-only slots and all weight
    precisions: paged == slab == reference, and every lease comes back."""
    engines, arena = hetero_node
    eng = engines["bloom-3b"]
    prompts = [[1, 2, 3], [0, 0], [7]]          # slot 4 stays padding-only
    caps = [16, 0, 7]
    ref = eng.generate(prompts, n_tokens=caps, quant_bits=bits)
    for k in (1, 3, 16):
        free0 = arena.free_pages
        got = eng.generate_via_chunks(prompts, n_tokens=caps, k=k,
                                      quant_bits=bits, arena=arena)
        assert arena.free_pages == free0        # all leases returned
        assert_same_generation(got, ref)
    assert got.lengths[1] == 0                  # cap=0 row emits nothing


def test_paged_engine_matches_slab_across_cohorts(hetero_node):
    """The 128-head cohort writes the same pool the 80-head cohort uses
    (padded tails) — both must stay bit-identical to their slabs."""
    engines, arena = hetero_node
    for arch, eng in engines.items():
        prompts = [[4, 5, 6], [9]]
        ref = eng.generate(prompts, n_tokens=[6, 16])
        got = eng.generate_via_chunks(prompts, n_tokens=[6, 16], k=3,
                                      arena=arena)
        assert_same_generation(got, ref)
    assert arena.free_pages == arena.total_pages


def test_paged_engine_immediate_eos(hetero_node):
    """A row whose first sampled token is EOS emits exactly one token
    through the paged path too."""
    engines, arena = hetero_node
    eng = engines["bloom-3b"]
    ref = eng.generate_reference([[9, 8, 7]], n_tokens=[6])
    tok0 = int(ref.tokens[0, 0])
    eng2 = ServingEngine(eng.cfg, params=eng._raw_params,
                         batch_capacity=4, s_max=32, n_max=16, eos_id=tok0)
    got = eng2.generate_via_chunks([[9, 8, 7]], n_tokens=[6], k=3,
                                   arena=arena)
    assert_same_generation(got, eng2.generate([[9, 8, 7]], n_tokens=[6]))
    assert got.lengths[0] == 1
    assert got.tokens[0, 0] == tok0


def test_paged_engine_int8_kv_cache(hetero_node):
    """kv_bits=8 engines carry quantized value pages PLUS scale pages;
    the paged path must reproduce the slab's int8-KV decode bitwise."""
    cfg = reduced_cfg("qwen3-1.7b").scaled(kv_bits=8)
    eng = ServingEngine(cfg, batch_capacity=2, s_max=32, n_max=16)
    assert eng.paged_capable
    arena = KVArena.for_engines([eng], block_tokens=16)
    assert set(arena.buffers()) >= {"k", "v"}
    assert len(arena.buffers()) == 4            # + per-token scale leaves
    prompts = [[3, 1, 4, 1, 5], [9, 2]]
    ref = eng.generate(prompts, n_tokens=[16, 5])
    for k in (1, 16):
        got = eng.generate_via_chunks(prompts, n_tokens=[16, 5], k=k,
                                      arena=arena)
        assert_same_generation(got, ref)
    assert arena.free_pages == arena.total_pages


def test_paged_refill_matches_slab_refill(hetero_node):
    """Mid-cohort refill into a freed slot: the paged splice (scatter +
    lease swap + ZERO-mapped junk gap) must reproduce the slab splice
    bit-for-bit, and the ZERO page must still be all-zero afterwards."""
    engines, arena = hetero_node
    eng = engines["bloom-3b"]
    prompts = [[1, 2, 3], [4, 5]]

    def drive(paged):
        st = eng.start_chunked(prompts, n_tokens=[16, 2],
                               arena=arena if paged else None)
        st = eng.generate_chunked(st, 3)        # row 1 (cap 2) finishes
        _, lengths, done, t = eng.poll_chunked(st)
        assert lengths[1] == 2
        st = eng.refill_chunked(st, [1], [[9, 9, 9]], [8], t_now=t)
        while True:
            st = eng.generate_chunked(st, 2)
            out, lengths, done, t = eng.poll_chunked(st)
            if eng.exhausted(lengths, done, st.caps_host, t):
                break
        if paged:
            eng.release_all(st)
        return out, lengths

    slab_out, slab_len = drive(paged=False)
    free0 = arena.free_pages
    paged_out, paged_len = drive(paged=True)
    np.testing.assert_array_equal(paged_out, slab_out)
    np.testing.assert_array_equal(paged_len, slab_len)
    assert arena.free_pages == free0
    for leaf in arena.buffers().values():       # ZERO page never written
        assert not np.asarray(leaf[:, ZERO_PAGE]).any()


def _paged_view(leaf, table_row, n_pos, tail):
    """A row's logical positions [0, n_pos) read back from an arena leaf
    through its block-table row, sliced to the engine's tail."""
    L, _, bt = leaf.shape[:3]
    v = np.asarray(leaf)[:, table_row].reshape((L, -1) + leaf.shape[3:])
    return v[(slice(None), slice(0, n_pos))
             + tuple(slice(0, d) for d in tail)]


@pytest.mark.parametrize("kv_bits", [16, 8])
def test_paged_segment_matches_slab_segment_at_block_edges(kv_bits):
    """Blocks of 4 tokens put decode writes at every offset, a block's
    first and last included; rows 1 and 2 run past their caps and write
    to TRASH_PAGE in the same step; row 1 is then refilled mid-cohort
    over a ZERO_PAGE gap.  The paged segment's tokens, lengths, done
    and t equal the slab segment's, and every row that still writes to
    its own pages holds the slab's keys and values (and scales, for
    int8 KV) at every position written so far."""
    cfg = reduced_cfg("bloom-3b").scaled(n_layers=3, kv_bits=kv_bits)
    eng = ServingEngine(cfg, batch_capacity=4, s_max=16, n_max=16)
    arena = KVArena.for_engines([eng], block_tokens=4)
    prompts, caps = [[1, 2, 3], [4, 5], [6], [7, 8, 9, 10]], [16, 2, 3, 16]

    def drive(paged):
        st = eng.start_chunked(prompts, caps, arena=arena if paged else None)
        st = eng.generate_chunked(st, 5)        # steps 0-4, positions 16-20
        _, lengths, _, t = eng.poll_chunked(st)
        np.testing.assert_array_equal(lengths[1:3], caps[1:3])
        if paged:                               # step 4 wrote rows 1, 2
            assert (st.table.host[1:3, 20 // 4] == TRASH_PAGE).all()
        st = eng.refill_chunked(st, [1], [[9, 9, 9]], [8], t_now=t)
        if paged:                               # gap [16, 21) -> block 4
            assert st.table.host[1, 16 // 4] == ZERO_PAGE
        for _ in range(3):
            st = eng.generate_chunked(st, 2)
        return st, eng.poll_chunked(st)

    slab, want = drive(paged=False)
    paged, got = drive(paged=True)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    n_pos = eng.s_max + got[3]
    for name, leaf in arena.buffers().items():
        cache = np.asarray(slab.cache[name])
        for row in (0, 1, 3):
            np.testing.assert_array_equal(
                _paged_view(leaf, paged.table.host[row], n_pos,
                            cache.shape[3:]),
                cache[:, row, :n_pos])
        assert not np.asarray(leaf[:, ZERO_PAGE]).any()
    eng.release_all(paged)
    assert arena.free_pages == arena.total_pages


def _jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr nested in its equations (loop bodies,
    branches, calls), depth first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _jaxprs(sub)


@pytest.mark.parametrize("kv_bits,use_kernel",
                         [(16, False), (8, False), (16, True)])
def test_paged_decode_layer_scan_reads_the_arena_in_place(kv_bits,
                                                          use_kernel):
    """The layer scan of the paged decode segment takes the arena as a
    scan-invariant operand: no scan carries, scans over or stacks a value
    of an arena leaf's shape (a stacked ys rewrites the whole arena every
    step), so the step's tokens reach the arena only through the write
    after the scan."""
    cfg = reduced_cfg("bloom-3b").scaled(n_layers=3, kv_bits=kv_bits)
    eng = ServingEngine(cfg, batch_capacity=2, s_max=16, n_max=16,
                        use_kernel=use_kernel)
    pages = KVArena.for_engines([eng], block_tokens=4).buffers()
    B, n = eng.batch_capacity, eng.n_max

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    closed = jax.make_jaxpr(eng._decode_chunk_paged_fn)(
        eng.params, pages, i32(B, eng.cache_len // 4), i32(B), i32(B, n),
        i32(B), jnp.zeros((B,), bool), i32(B), i32(), i32(), i32(B, n),
        i32(B))
    arena_shapes = {leaf.shape for leaf in pages.values()}
    scans = [e for j in _jaxprs(closed.jaxpr) for e in j.eqns
             if e.primitive.name == "scan"]
    consts = set()
    for eqn in scans:
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        scanned = eqn.invars[nc:] + eqn.outvars
        assert not {v.aval.shape for v in scanned} & arena_shapes
        consts |= {v.aval.shape for v in eqn.invars[:nc]}
    assert arena_shapes <= consts               # the layer scan reads it


# -- continuous executor: per-block admission + lease lifecycle --------------


def _node(batch=4, s_max=16, n_max=8, archs=("bloom-3b", "bloom-7b1")):
    return {a: tiny_engine(a, batch_capacity=batch, s_max=s_max,
                           n_max=n_max) for a in archs}


def test_executor_gates_admission_on_free_pages():
    """With slots free but pages short, ``accepts`` must refuse — and
    pending reservations from ``place`` count against later admissions
    within the same boundary."""
    engines = _node(batch=2, s_max=8, n_max=8, archs=("bloom-3b",))
    arena = KVArena.for_engines(engines, block_tokens=8, shrink=0.5)
    eng = engines["bloom-3b"]
    need = eng.pages_for_admission(0, 4, 8)     # r1's span: nb = 16/8 = 2
    assert arena.total_pages == need            # room for exactly one row
    menv = MultiLLMEnv.host({"bloom-3b": paper_env("bloom-3b", "W8A16")})
    ex = EngineContinuousExecutor(engines, seed=0, arena=arena)
    ex.bind(menv)
    r1 = Request(rid=0, s=2, n=4, tau=50.0, a=0.0, h=1.0,
                 model_id="bloom-3b")
    r2 = Request(rid=1, s=2, n=4, tau=50.0, a=0.0, h=1.0,
                 model_id="bloom-3b")
    assert ex.accepts("bloom-3b", r1)
    ex.place("bloom-3b", r1)
    # a slot is still free, but the page reservation is spoken for
    assert ex.node_headroom("bloom-3b") == eng.n_max
    assert not ex.accepts("bloom-3b", r2)


def test_executor_e2e_conservation_and_lease_drain():
    """Full ContinuousRuntime over a shared arena: request conservation,
    every page back on the free list after the drain, and the block
    metrics populated (occupancy from real pages, fragmentation from
    the junk-gap accounting)."""
    engines = _node()
    arena = KVArena.for_engines(engines, block_tokens=8)
    menv = MultiLLMEnv.host({m: paper_env(m, "W8A16") for m in engines})
    ex = EngineContinuousExecutor(engines, seed=0, arena=arena)
    tagger = random_tagger(sorted(menv.envs), seed=3)
    m = ContinuousRuntime(menv, "multi-dftsp", ex, k=2).run(
        gen=RequestGenerator(rate=6, seed=0, lengths=(2, 4, 8)),
        n_epochs=3, seed=0, warmup_epochs=0, tag_arrivals=tagger)
    assert m.arrived == m.served + m.dropped + len(m.final_queue_rids)
    assert m.served > 0
    assert arena.free_pages == arena.total_pages    # no leaked leases
    assert arena.alloc_peak > 0
    assert m.kv_alloc_tokens > 0
    assert 0 < m.mean_block_occupancy <= 1
    assert 0 <= m.fragmentation < 1
    assert all(t.kv_blocks_total == arena.total_pages
               for t in m.traces if t.kv_blocks_in_use)


def test_executor_slab_fallback_block_usage():
    """Without an arena the executor reports slot-level block usage —
    the same accounting interface, so the metrics stay comparable."""
    engines = _node(archs=("bloom-3b",))
    menv = MultiLLMEnv.host({"bloom-3b": paper_env("bloom-3b", "W8A16")})
    ex = EngineContinuousExecutor(engines, seed=0)
    ex.bind(menv)
    used, total, live, alloc = ex.block_usage()
    assert used == 0 and total == sum(e.batch_capacity
                                      for e in engines.values())
    assert live == alloc == 0
