"""Priority preemption: bit-exact spill/resume through the engine's
forced-prefix replay, and the runtime's preemption accounting.

The load-bearing property (DESIGN.md §2.4): a preempted-and-resumed
request's final output is BIT-IDENTICAL to the same request served
uninterrupted.  The engine gets there by replaying, not trusting, the
delivered prefix — the resumed row re-prefills its ORIGINAL prompt and
the decode loop forces the already-delivered tokens back out position
by position (``forced``/``n_forced``), so the prefix the user saw is
pinned exactly and the continuation re-derives from the same cache
trajectory.  Checked on both the slab and the paged-arena decode paths.
"""
from __future__ import annotations

import numpy as np
import pytest

from conftest import reduced_cfg
from repro.core.environment import paper_env
from repro.core.multi import MultiLLMEnv
from repro.core.request import Request, RequestGenerator
from repro.serving.kv_arena import KVArena
from repro.serving.runtime import (AnalyticContinuousExecutor,
                                   ContinuousRuntime,
                                   EngineContinuousExecutor)
from repro.serving.slo import SpillRecord

ENV = paper_env("bloom-3b", "W8A16")


@pytest.fixture(scope="module")
def eng():
    from repro.serving.engine import ServingEngine
    return ServingEngine(reduced_cfg("bloom-3b"), batch_capacity=3,
                         s_max=16, n_max=8)


def _drive(eng, st, k=3):
    """Run a cohort to exhaustion; returns (state, out, lengths)."""
    while True:
        st = eng.generate_chunked(st, k)
        out, lengths, done, t = eng.poll_chunked(st)
        if eng.exhausted(lengths, done, st.caps_host, t):
            return st, out, lengths


def _arena(eng, paged):
    return KVArena.for_engines([eng], block_tokens=8) if paged else None


# -- engine level: the bit-exactness contract --------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_preempted_resume_bit_identical(eng, paged):
    prompt = [3, 5, 7, 2]

    # reference: the request served alone, never interrupted
    st, out, lengths = _drive(eng, st := eng.start_chunked(
        [prompt], [8], arena=_arena(eng, paged)))
    ref = np.asarray(out[0][:lengths[0]]).copy()
    assert lengths[0] == 8
    if paged:
        eng.release_all(st)

    # interrupted: same prompt inside a busy cohort, evicted mid-flight
    st = eng.start_chunked([prompt, [1, 2], [9, 4, 6]], [8, 8, 8],
                           arena=_arena(eng, paged))
    st = eng.generate_chunked(st, 3)
    out, lengths, done, t = eng.poll_chunked(st)
    prefix = [int(x) for x in out[0][:lengths[0]]]
    assert 0 < len(prefix) < len(ref)
    # batched rows decode independently: the delivered prefix already
    # matches the solo reference
    assert np.array_equal(prefix, ref[:len(prefix)])
    st = eng.evict_slots(st, [0])
    st, _, _ = _drive(eng, st)          # survivors drain past the eviction
    if paged:
        eng.release_all(st)

    # resume: fresh cohort, ORIGINAL prompt, delivered prefix replayed
    st = eng.start_chunked([prompt], [8], arena=_arena(eng, paged),
                           prefixes=[prefix])
    st, out, lengths = _drive(eng, st)
    resumed = np.asarray(out[0][:lengths[0]])
    assert np.array_equal(resumed, ref), (resumed, ref)
    if paged:
        eng.release_all(st)


@pytest.mark.parametrize("paged", [False, True], ids=["slab", "paged"])
def test_evict_slots_frees_rows_and_pages(eng, paged):
    arena = _arena(eng, paged)
    st = eng.start_chunked([[1, 2], [3, 4], [5, 6]], [8, 8, 8],
                           arena=arena)
    if paged:
        free_before = arena.free_pages
    st = eng.evict_slots(st, [0, 2])
    _, lengths, done, _ = eng.poll_chunked(st, with_tokens=False)
    assert done[0] and done[2] and not done[1]
    assert st.caps_host[0] == 0 and st.caps_host[2] == 0
    if paged:
        assert arena.free_pages > free_before     # leases returned
    # dead rows keep stepping as don't-care work; the cohort still drains
    st, _, lengths = _drive(eng, st)
    assert lengths[1] == 8
    if paged:
        eng.release_all(st)


# -- runtime level: preemption end-to-end on the real engine -----------------


def conserved(m):
    assert m.arrived == m.served + m.dropped + m.shed \
        + len(m.final_queue_rids) + len(m.in_flight_rids), \
        (m.arrived, m.served, m.dropped, m.shed,
         len(m.final_queue_rids), len(m.in_flight_rids))


def test_engine_runtime_preempts_and_resumes(eng):
    gen = RequestGenerator(rate=8, seed=3, lengths=(4, 8),
                           tau_range=(0.5, 6.0), priorities=(0, 1, 2))
    cexec = EngineContinuousExecutor(eng, seed=0, collect_tokens=True)
    rt = ContinuousRuntime(ENV, "dftsp", cexec, k=2, preemption=True,
                           max_preemptions=2, backoff_boundaries=1)
    m = rt.run(gen=gen, n_epochs=4, warmup_epochs=0)
    conserved(m)
    assert m.preempted > 0
    assert m.resumed > 0
    served = [rid for t in m.traces for rid in t.finished_rids]
    assert len(served) == len(set(served)) == m.served
    # every served row's tokens were collected exactly once
    assert sorted(cexec.outputs) == sorted(served)


def test_analytic_runtime_preempts_with_spill_accounting():
    gen = RequestGenerator(rate=30, seed=0, tau_range=(0.5, 6.0),
                           priorities=(0, 1, 2))
    rt = ContinuousRuntime(ENV, "dftsp",
                           AnalyticContinuousExecutor(capacity=4), k=64,
                           preemption=True)
    m = rt.run(gen=gen, n_epochs=6, warmup_epochs=0)
    conserved(m)
    assert m.preempted > 0
    # a resume is only counted when the preempted rid actually re-lands
    assert 0 <= m.resumed <= m.preempted + m.served


def _req(rid=0, s=4, n=8, tau=30.0, priority=0):
    return Request(rid=rid, s=s, n=n, tau=tau, a=0.5, h=1e-3,
                   arrival=0.0, priority=priority)


def test_engine_preempt_payload_reports_remaining(eng):
    """Regression: the engine preempt payload historically carried only
    (prompt, prefix), so the deadline gate re-judged a spilled request
    on its FULL n — a half-served long request looked hopeless even
    when its remaining half met the deadline.  Both payloads now carry
    ``remaining``."""
    cexec = EngineContinuousExecutor(eng, seed=0)
    cexec.bind(ENV)
    r = _req()
    cexec.place(None, r)
    cexec.step(ENV, 3)
    payload = cexec.preempt(None, r.rid)
    assert 0 < len(payload["prefix"]) < 8
    assert payload["remaining"] == 8 - len(payload["prefix"])


def test_hopeless_judges_spilled_requests_on_remaining_tokens():
    rt = ContinuousRuntime(ENV, "dftsp",
                           AnalyticContinuousExecutor(capacity=4), k=4,
                           deadline_gated=True)
    rt._tnow = 0.0
    dt = rt.T_E / rt.segments_per_epoch
    r = _req(n=64, tau=4.5 * dt)
    assert rt._hopeless(r, None)          # 16 segments from scratch
    rec = SpillRecord(request=r, payload={"remaining": 8})
    assert not rt._hopeless(r, rec)       # 2 segments left: feasible
    rec = SpillRecord(request=r, payload={"remaining": 60})
    assert rt._hopeless(r, rec)


# -- cross-pool preemption under shared-arena pressure (DESIGN.md §2.3/2.4) --


MENV = MultiLLMEnv.host({
    "bloom-3b": paper_env("bloom-3b", "W8A16"),
    "bloom-7b1": paper_env("bloom-7b1", "W8A16"),
})


def _two_pool_cexec(**kw):
    from repro.serving.engine import ServingEngine
    ea = ServingEngine(reduced_cfg("bloom-3b"), batch_capacity=2,
                       s_max=16, n_max=8, eos_id=-1)
    eb = ServingEngine(reduced_cfg("bloom-7b1"), batch_capacity=2,
                       s_max=16, n_max=8, eos_id=-1)
    arena = KVArena.for_engines([ea, eb], block_tokens=8, shrink=0.5)
    return EngineContinuousExecutor({"bloom-3b": ea, "bloom-7b1": eb},
                                    seed=0, arena=arena, **kw), arena


def test_arena_blocked_flags_cross_pool_memory_pressure():
    """Regression: preemption historically searched victims only in the
    CANDIDATE's pool, but when the shared arena binds, any cohort's
    freed pages help — ``arena_blocked`` is the signal that widens the
    victim search, and evicting another pool's resident must actually
    unblock the admission."""
    cexec, arena = _two_pool_cexec()
    cexec.bind(MENV)
    residents = [_req(rid=10 + i) for i in range(2)]
    for r in residents:
        assert cexec.accepts("bloom-7b1", r)
        cexec.place("bloom-7b1", r)
    cexec.step(MENV, 1)
    rc = _req(rid=0, priority=1)
    assert cexec.free_slots("bloom-3b") > 0
    assert not cexec.accepts("bloom-3b", rc)      # page budget refuses
    assert cexec.arena_blocked("bloom-3b", rc)    # ...and says why
    assert cexec.refusal("bloom-3b", rc) == "pages"
    # evicting the OTHER pool's resident returns its pages to the node
    payload = cexec.preempt("bloom-7b1", residents[0].rid)
    assert payload["remaining"] > 0
    assert cexec.accepts("bloom-3b", rc)
    assert not cexec.arena_blocked("bloom-3b", rc)
    assert cexec.refusal("bloom-3b", rc) is None


def test_cross_pool_preemption_run_conserves():
    cexec, _ = _two_pool_cexec(collect_tokens=True)

    def tagger(arrivals):
        for i, r in enumerate(arrivals):
            r.model_id = "bloom-3b" if i % 2 == 0 else "bloom-7b1"
        return arrivals

    rt = ContinuousRuntime(MENV, "multi-dftsp", cexec, k=2,
                           preemption=True, max_preemptions=2,
                           backoff_boundaries=1)
    m = rt.run(gen=RequestGenerator(rate=10, seed=3, lengths=(4, 8),
                                    tau_range=(0.5, 8.0),
                                    priorities=(0, 1, 2)),
               n_epochs=4, warmup_epochs=0, tag_arrivals=tagger)
    conserved(m)
    assert m.served > 0
    served = [rid for t in m.traces for rid in t.finished_rids]
    assert len(served) == len(set(served)) == m.served
    assert sorted(cexec.outputs) == sorted(served)


def test_preemption_respects_attempt_cap():
    """max_preemptions=0 pins every resident: nothing is ever evicted."""
    gen = RequestGenerator(rate=30, seed=0, tau_range=(0.5, 6.0),
                           priorities=(0, 1, 2))
    rt = ContinuousRuntime(ENV, "dftsp",
                           AnalyticContinuousExecutor(capacity=4), k=64,
                           preemption=True, max_preemptions=0)
    m = rt.run(gen=gen, n_epochs=6, warmup_epochs=0)
    conserved(m)
    assert m.preempted == 0 and m.resumed == 0
